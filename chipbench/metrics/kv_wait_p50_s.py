"""kv_wait_p50_s: median, over the requests sent in the window that
fetched their prefix, of the seconds from send to the start of their
``kvf.prefill.suffix`` span: the wait for the fetch to start and for
the first layer's KV to be restored. A request sent before the window
is skipped.

The trace's reduction keeps no span argument, so a prefill span (suffix
or full) is paired with the first token that follows it: the engine
hands out a request's first token at the end of its prefill, one request
at a time, so the window's prefill spans and first tokens come in the
same order. The send time goes onto the trace's clock through the
window's start (``ctx.t0`` at the ``chipbench.window`` span's start)."""
import statistics

from chipbench import spans


def read(ctx):
    prefills = sorted(spans.named(ctx, "kvf.prefill.suffix")
                      + spans.named(ctx, "kvf.prefill.full"),
                      key=lambda e: e.start_ns)
    firsts = sorted((t, rid) for t, rid, idx in ctx.clients.token_log
                    if idx == 0 and ctx.t0 <= t <= ctx.t_stop)
    if not prefills or len(prefills) != len(firsts):
        return None
    lo = ctx.window_ns[0]
    waits = []
    for span, (_, rid) in zip(prefills, firsts):
        sent = ctx.clients.sent[rid]
        if span.name == "kvf.prefill.suffix" and sent.t_send >= ctx.t0:
            start = ctx.t0 + (span.start_ns - lo) / 1e9
            waits.append(start - sent.t_send)
    return statistics.median(waits) if waits else None
