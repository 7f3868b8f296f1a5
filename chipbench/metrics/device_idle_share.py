"""device_idle_share: percent of the traced window with no operation
running on the device (the union of the device's op intervals)."""
from chipbench import trace as trace_mod


def read(ctx):
    if ctx.trace is None or ctx.window_ns is None or not ctx.trace.ops:
        return None
    lo, hi = ctx.window_ns
    inside = [e for e in ctx.trace.device_ops(0)
              if e.end_ns > lo and e.start_ns < hi]
    busy = trace_mod.union_ns(
        trace_mod.Event(e.name, max(e.start_ns, lo),
                        min(e.end_ns, hi) - max(e.start_ns, lo))
        for e in inside)
    return 100.0 * (1.0 - busy / (hi - lo))
