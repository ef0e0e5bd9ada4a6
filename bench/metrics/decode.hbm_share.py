"""Share of the HBM roofline that decoding's necessary reads take on
the device, in %: the bytes the window's steps must read (each reads
every parameter once, and the keys and values of the positions filled
so far; ``bench.counts``) over the seconds in which the device ran an
operation (from the trace, mean over the chips), over the chips' HBM
bandwidth.  Host gaps between steps are the idle share's."""


def read(m):
    peak = m.peaks.get("hbm_bytes_per_s")
    if not peak or not m.device_s:
        return None
    moved = m.counts["bytes_per_step"] * m.window.facts["steps"]
    return 100.0 * moved / (m.device_s * m.chips * peak)
