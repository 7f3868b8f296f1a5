"""paged_attention_roofline: the `paged_attention` Pallas kernel's least
time over its device time in the traced window, in percent.

Least time is the larger of operations over peak and bytes over HBM
bandwidth. Per call, for each sequence b with context c_b: operations
4 * H * hd * c_b (query-key scores and the weighted sum of values);
bytes: the query and output [H, hd] and the context's K and V rows
[c_b, K, hd] in the pages' dtype. Decode attention at these shapes is
bound by bytes."""
from chipbench import trace as trace_mod

KERNEL = "paged_attention_pallas"


def call_flops(H, hd, context_lens):
    return sum(4 * H * hd * int(c) for c in context_lens)


def call_bytes(H, K, hd, itemsize, context_lens):
    qo = 2 * len(context_lens) * H * hd * itemsize
    kv = sum(2 * int(c) * K * hd * itemsize for c in context_lens)
    return qo + kv + 4 * len(context_lens)


def read(ctx):
    if ctx.trace is None or not ctx.attend_calls:
        return None
    secs = trace_mod.kernel_seconds(ctx.trace, KERNEL)
    if secs <= 0:
        return None
    flops = nbytes = 0
    for (B, H, hd), (P, ps, K, _), itemsize, lens in ctx.attend_calls:
        flops += call_flops(H, hd, lens)
        nbytes += call_bytes(H, K, hd, itemsize, lens)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
