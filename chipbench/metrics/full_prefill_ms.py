"""full_prefill_ms: mean self time of the window's ``kvf.prefill.full``
spans in ms: the host's time in a whole prompt's prefill
(`paged_model.prefill_collect_kv`) less its ``kvf.cache.write``
children, in which the prompt's K and V rows go into the pages."""
from chipbench import spans


def read(ctx):
    full = spans.named(ctx, "kvf.prefill.full")
    if not full:
        return None
    writes = spans.named(ctx, "kvf.cache.write")
    return sum(spans.self_ns(s, writes) for s in full) / len(full) / 1e6
