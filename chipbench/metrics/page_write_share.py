"""page_write_share: percent of the traced window the host spends in
``kvf.cache.write`` spans, `PagedKVCache.write_prefill` (suffix and full
prefill rows, and every decode token's row)."""
from chipbench import spans


def read(ctx):
    writes = spans.named(ctx, "kvf.cache.write")
    if not writes:
        return None
    lo, hi = ctx.window_ns
    return 100.0 * sum(e.dur_ns for e in writes) / (hi - lo)
