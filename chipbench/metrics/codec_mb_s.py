"""codec_mb_s: KV the host codec decodes per second of its own time, in
MB/s (1e6 bytes).

Time: the summed ``kvf.codec.frame`` spans inside the window's
``kvf.restore.chunk`` spans (each blob's parse and each frame's rANS
read, inverse prediction and unpacking; not the restore that follows a
frame). Bytes: the uint8 tokens those frames decoded, which the
window's ``kv_restore`` calls take in, one layer of one frame each."""
from chipbench import spans


def read(ctx):
    calls = spans.restores(ctx)
    if calls is None:
        return None
    frames = spans.inside(spans.named(ctx, "kvf.codec.frame"),
                          spans.named(ctx, "kvf.restore.chunk"))
    secs = sum(e.dur_ns for e in frames) / 1e9
    if secs <= 0:
        return None
    nbytes = sum(n * K * hd * ti for (n, K, hd), _, ti, _ in calls)
    return nbytes / 1e6 / secs
