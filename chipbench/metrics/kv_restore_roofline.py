"""kv_restore_roofline: the `kv_restore` Pallas kernel's least time over
its device time in the traced window, in percent.

Least time is the larger of operations over peak and bytes over HBM
bandwidth. The kernel moves bytes and does no matrix work: per call it
must read the decoded uint8 tokens [n, K, hd], the K per-head scales and
the n destination slots, and write n page rows [K, hd] in the pages'
dtype. It is bound by bytes."""
from chipbench import trace as trace_mod

KERNEL = "kv_restore_pallas"


def call_bytes(n, K, hd, page_itemsize, token_itemsize=1, scale_itemsize=4):
    """HBM bytes one call cannot avoid moving."""
    return (n * K * hd * token_itemsize + K * scale_itemsize + n * 4
            + n * K * hd * page_itemsize)


def call_flops(n, K, hd):
    """Dequantize: one subtract and one multiply per element (VPU)."""
    return 2 * n * K * hd


def read(ctx):
    if ctx.trace is None or not ctx.restore_calls:
        return None
    secs = trace_mod.kernel_seconds(ctx.trace, KERNEL)
    if secs <= 0:
        return None
    nbytes = sum(call_bytes(n, K, hd, pi, ti, si)
                 for (n, K, hd), pi, ti, si in ctx.restore_calls)
    flops = sum(call_flops(n, K, hd) for (n, K, hd), *_ in ctx.restore_calls)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
