"""Device milliseconds per round of convolution ops (HLO category
convolution, fused or not), per chip. Only the member model convolves, so
this is the local update's forward and backward convolutions."""


def is_conv(op):
    return "convolution" in str(op.stats.get("hlo_category", "")).lower()


def read(run):
    if run.trace is None or not run.rounds:
        return None
    t = run.trace.op_time(is_conv)
    if t <= 0:
        return None
    return 1e3 * t / run.rounds
