"""Device time of the optimizer per training step, in ms: the busy
union of the ops under the ``train.optimizer`` scope (lr schedule, clip,
AdamW), mean over the chips, over the window's steps
(``bench.scopes``)."""
from bench.scopes import per_step_ms


def read(m):
    return per_step_ms(m, "scope_busy_ns", "train.optimizer")
