"""Share of its roofline that the ``sqdist_rows`` Pallas kernel reaches,
in %: the HBM bytes one call must move (the chip's rows of the plane,
the reference row, one distance per row; ``flops.sqdist_rows_bytes``)
over the chip's HBM bandwidth, divided by the kernel's mean device time
per call. Memory bandwidth bounds this kernel. Column tails shorter than
the kernel's block are reduced outside it, so the bytes count is high by
at most that tail (1.7 % of P for mnist_cnn, 5.9 % for deepdrive_cnn)."""

from perfbench import flops, peaks
from perfbench.trace import op_name

KERNEL = "sqdist_rows"


def is_kernel(op):
    return op_name(op.name).split(".")[0] == KERNEL


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.op_calls(is_kernel)
    t = run.trace.op_time(is_kernel)
    if calls == 0 or t <= 0:
        return None
    cfg = run.cell.config
    P = flops.weight_count(cfg["cnn_spec"], cfg["input_shape"])
    rows = run.m // run.chips
    bw = peaks.chip_peaks(run.devices[0].device_kind).hbm_bw
    return 100.0 * calls * flops.sqdist_rows_bytes(rows, P) / bw / t
