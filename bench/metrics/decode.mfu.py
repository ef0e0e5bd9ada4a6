"""Model FLOP/s utilisation of the decode step on the device, in %: the
analytic FLOPs of the window's decoded tokens at the round's mean
context (``bench.counts.decode_flops_per_token``) over the seconds in
which the device ran an operation (from the trace, mean over the
chips), over the chips' bf16 peak."""


def read(m):
    peak = m.peaks.get("bf16_flops")
    if not peak or not m.device_s:
        return None
    flops = m.counts["flops_per_token"] * m.window.facts["tokens"]
    return 100.0 * flops / (m.device_s * m.chips * peak)
