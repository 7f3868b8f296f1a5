"""step_mfu: model operations of the tokens served in the traced window
over the window's length times the chip's bf16 peak, in percent.

Counted from the served tokens and their shapes by the cell's
architecture module (``ctx.arch``): a first token is a prefill of
s = prompt - n_pre tokens after the n_pre reused ones (all of the prompt
where nothing is reused), with the output head at its last position; a
later token is one decode step at its context. Restored prefix tokens
are not computed, so they are not counted."""


def read(ctx):
    arch, cfg = ctx.arch, ctx.cfg
    total = 0
    for t, rid, idx in ctx.clients.token_log:
        if not ctx.t0 <= t <= ctx.t_stop:
            continue
        s = ctx.clients.sent[rid]
        if idx == 0:
            total += arch.prefill_flops(cfg, s.n_pre,
                                        len(s.prompt) - s.n_pre)
        else:
            # the decode step fed token idx-1 at position len(prompt)+idx-1
            total += arch.decode_flops(cfg, len(s.prompt) + idx)
    if total == 0 or ctx.window_s <= 0:
        return None
    return 100.0 * total / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
