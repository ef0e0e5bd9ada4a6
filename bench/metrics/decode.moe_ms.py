"""Device time of the MoE layers per decode step, in ms: the busy union
of the ops under the ``decode.moe`` scope (router, held experts, shared
experts; every MoE layer), mean over the chips, over the window's steps
(``bench.scopes``)."""
from bench.scopes import per_step_ms


def read(m):
    return per_step_ms(m, "scope_busy_ns", "decode.moe")
