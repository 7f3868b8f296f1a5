"""decode_step_ms: median length of the window's ``kvf.decode.step``
spans in ms: one batched ``decode_paged`` call of `LiveEngine.step`,
through the host's read of the next tokens."""
import statistics

from chipbench import spans


def read(ctx):
    steps = spans.named(ctx, "kvf.decode.step")
    if not steps:
        return None
    return statistics.median(e.dur_ns for e in steps) / 1e6
