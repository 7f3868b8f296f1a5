"""h2d_mb_s: host-to-device copies of decoded KV, in MB/s (1e6 bytes):
the bytes copied over the summed time of the window's
``kvf.restore.h2d`` spans. Each span copies what one ``kv_restore``
call takes in, uint8 tokens [n, K, hd] and K scales, and lasts as long
as the host waits for the copy to be handed over."""
from chipbench import spans


def read(ctx):
    calls = spans.restores(ctx)
    copies = spans.named(ctx, "kvf.restore.h2d")
    if calls is None or len(copies) != len(calls):
        return None
    secs = sum(e.dur_ns for e in copies) / 1e9
    if secs <= 0:
        return None
    nbytes = sum(n * K * hd * ti + K * si
                 for (n, K, hd), _, ti, si in calls)
    return nbytes / 1e6 / secs
