"""decode_batch_mean: running sequences per decode step of `LiveEngine`
in the window (steps that decoded nothing are not counted). Counted by
the harness from the tokens each engine step hands to ``on_token``."""


def read(ctx):
    n = [k for t, k in ctx.clients.steps if ctx.t0 <= t <= ctx.t_stop and k]
    return sum(n) / len(n) if n else None
