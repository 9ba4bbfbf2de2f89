"""Learner-samples per second, all chips together: rounds completed in
the window x m x B / window seconds (host clock)."""


def read(run):
    return run.rounds * run.m * run.batch / run.window_s
