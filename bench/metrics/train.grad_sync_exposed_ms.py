"""Exposed gradient-sync time per training step, in ms: the part of the
busy union of the ops under the ``train.grad_sync`` scope (flatten,
collectives, division, unflatten) during which no op outside that scope
runs on the device, mean over the chips, over the window's steps
(``bench.scopes``)."""
from bench.scopes import per_step_ms


def read(m):
    return per_step_ms(m, "scope_exposed_ns", "train.grad_sync")
