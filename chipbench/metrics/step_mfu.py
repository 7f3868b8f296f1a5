"""step_mfu: model operations of the tokens served in the traced window
over the window's length times the chip's bf16 peak, in percent.

Counted from the served tokens and their shapes: a first token is a
suffix prefill of s = prompt - n_pre tokens attending over the stored
prefix and causally over itself, with the output head at its last
position; a later token is one decode step at its context. Restored
prefix tokens are not computed, so they are not counted. Causal
attention counts only the scores it needs."""


def layer_matmul_flops(cfg):
    """Operations of one token through one layer's weight matrices."""
    d, H, K, hd, f = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.d_ff)
    return 2 * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * f)


def attention_flops(cfg, context):
    """One query token attending over ``context`` positions, one layer."""
    return 4 * cfg.num_heads * cfg.head_dim * context


def head_flops(cfg):
    return 2 * cfg.d_model * cfg.vocab_size


def prefill_flops(cfg, n_pre, s):
    L = cfg.num_layers
    att = sum(attention_flops(cfg, n_pre + i + 1) for i in range(s))
    return L * (s * layer_matmul_flops(cfg) + att) + head_flops(cfg)


def decode_flops(cfg, context):
    return cfg.num_layers * (layer_matmul_flops(cfg)
                             + attention_flops(cfg, context)) \
        + head_flops(cfg)


def read(ctx):
    cfg = ctx.cfg
    total = 0
    for t, rid, idx in ctx.clients.token_log:
        if not ctx.t0 <= t <= ctx.t_stop:
            continue
        s = ctx.clients.sent[rid]
        if idx == 0:
            total += prefill_flops(cfg, s.n_pre, len(s.prompt) - s.n_pre)
        else:
            # the decode step fed token idx-1 at position len(prompt)+idx-1
            total += decode_flops(cfg, len(s.prompt) + idx)
    if total == 0 or ctx.window_s <= 0:
        return None
    return 100.0 * total / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
