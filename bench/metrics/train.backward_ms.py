"""Device time of the backward pass per training step, in ms: the busy
union of the ops under the ``transpose(jvp(train.loss))`` scope (the
rematerialised forward included), mean over the chips, over the
window's steps (``bench.scopes``)."""
from bench.scopes import per_step_ms


def read(m):
    return per_step_ms(m, "scope_busy_ns", "transpose(jvp(train.loss))")
