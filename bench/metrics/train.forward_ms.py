"""Device time of the forward pass per training step, in ms: the busy
union of the ops under the ``jvp(train.loss)`` scope (the loss's
forward, its logits and loss included; not its transpose), mean over
the chips, over the window's steps (``bench.scopes``)."""
from bench.scopes import per_step_ms


def read(m):
    return per_step_ms(m, "scope_busy_ns", "jvp(train.loss)")
