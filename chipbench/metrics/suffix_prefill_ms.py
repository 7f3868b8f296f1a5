"""suffix_prefill_ms: mean self time of the window's
``kvf.prefill.suffix`` spans in ms: the host's time in a fetched
request's suffix prefill less its ``kvf.prefill.await`` children, in
which it waits for a layer's KV (and restores it)."""
from chipbench import spans


def read(ctx):
    suffix = spans.named(ctx, "kvf.prefill.suffix")
    if not suffix:
        return None
    waits = spans.named(ctx, "kvf.prefill.await")
    return sum(spans.self_ns(s, waits) for s in suffix) / len(suffix) / 1e6
