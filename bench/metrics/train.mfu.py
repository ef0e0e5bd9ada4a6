"""Model FLOP/s utilisation of the training step on the device, in %:
the analytic FLOPs of the window's tokens
(``bench.counts.train_flops_per_token``, recomputation not counted)
over the seconds in which the device ran an operation (from the trace,
mean over the chips), over the chips' bf16 peak.  Host gaps between
steps are the idle share's, not this metric's."""


def read(m):
    peak = m.peaks.get("bf16_flops")
    if not peak or not m.device_s:
        return None
    flops = m.counts["flops_per_token"] * m.window.facts["tokens"]
    return 100.0 * flops / (m.device_s * m.chips * peak)
