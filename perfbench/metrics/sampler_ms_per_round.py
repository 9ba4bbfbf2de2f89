"""Device milliseconds per round of the fused chunk sampler
(``LearnerStreams.next_chunk``'s ``sample_chunk`` program), mean over
chips."""

SAMPLER = "jit_sample_chunk"


def read(run):
    if run.trace is None or not run.rounds:
        return None
    t = run.trace.module_time(SAMPLER)
    if t <= 0:
        return None
    return 1e3 * t / run.rounds
