"""Idle share of the device over the traced window, in %: 1 minus the
union of the intervals in which an operation ran (mean over the chips)
over the window."""


def read(m):
    return None if m.summary is None else 100.0 * m.summary.idle_share
