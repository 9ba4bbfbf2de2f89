"""Model FLOP/s utilisation of the whole step, in %: the operations the
member's SGD step requires per learner-sample (``flops.py``: forward,
weight gradients, input gradients but the first layer's) times the
learner-samples per second of the traced window, over the chips' bf16
peak. Sync, sampler and host time count against it."""

from perfbench import flops, peaks


def read(run):
    if run.trace is None or not run.rounds or not run.trace.devices:
        return None
    cfg = run.cell.config
    per_sample = flops.model_flops_per_sample(cfg["cnn_spec"],
                                              cfg["input_shape"])
    rate = run.rounds * run.m * run.batch / run.trace.window_s
    peak = peaks.chip_peaks(run.devices[0].device_kind).flops_bf16
    return 100.0 * per_sample * rate / (run.chips * peak)
