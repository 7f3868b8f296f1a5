"""restore_dispatches_per_ktok: ``kv_restore`` dispatches (the
``kvf.cache.restore`` spans inside the window's ``kvf.restore.chunk``
spans) per 1000 prefix tokens restored in the window. A prefix token
counts once its K and V rows of every layer are restored, so the tokens
are the rows the window's calls wrote over 2 x layers."""
from chipbench import spans


def read(ctx):
    calls = spans.restores(ctx)
    if calls is None:
        return None
    rows = sum(n for (n, _, _), *_ in calls)
    tokens = rows / (2 * ctx.cfg.num_layers)
    return len(calls) / (tokens / 1000)
