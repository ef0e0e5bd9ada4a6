"""Device time of the KV-cache writes per decode step, in ms: the busy
union of the ops under the ``decode.cache_write`` scope (each layer's
key and value update), mean over the chips, over the window's steps
(``bench.scopes``)."""
from bench.scopes import per_step_ms


def read(m):
    return per_step_ms(m, "scope_busy_ns", "decode.cache_write")
