"""Model FLOP/s utilisation of the whole step, in %: the operations the
member's SGD step requires per learner-sample (one draw of a learner's
stream; the member module's ``flops_per_sample``) times the
learner-samples per second of the traced window, over the chips' bf16
peak. Sync, sampler and host time count against it."""

from perfbench import peaks


def read(run):
    if run.trace is None or not run.rounds or not run.trace.devices:
        return None
    per_sample = run.cell.member.flops_per_sample(run.cell.config)
    rate = run.rounds * run.m * run.batch / run.trace.window_s
    peak = peaks.chip_peaks(run.devices[0].device_kind).flops_bf16
    return 100.0 * per_sample * rate / (run.chips * peak)
