"""Device time of the collective ops per training step, in ms: the
summed durations of the HLO collectives in the traced window (mean over
the chips), over the steps the window completed."""


def read(m):
    s = m.summary
    if s is None or not m.window.facts.get("steps"):
        return None
    ns = sum(s.collective_ns.values()) / len(s.collective_ns)
    return ns * 1e-6 / m.window.facts["steps"] if ns > 0 else None
