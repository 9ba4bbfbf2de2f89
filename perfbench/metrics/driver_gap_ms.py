"""Device-idle milliseconds per chunk boundary: from the end of one
chunk's fold program to the start of the next chunk's sampler program,
the time in which no op ran, mean over boundaries and chips. It is the
host's per-chunk bookkeeping in ``train/loop.py`` as the device sees it."""

FOLD, SAMPLER = "jit_fold", "jit_sample_chunk"


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_between(FOLD, SAMPLER)
    if not idle:
        return None
    return 1e3 * sum(idle) / len(idle)
