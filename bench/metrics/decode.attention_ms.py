"""Device time of attention per decode step, in ms: the busy union of
the ops under the ``decode.attention`` scope (projections, KV repeat,
scores, output; every layer), mean over the chips, over the window's
steps (``bench.scopes``)."""
from bench.scopes import per_step_ms


def read(m):
    return per_step_ms(m, "scope_busy_ns", "decode.attention")
