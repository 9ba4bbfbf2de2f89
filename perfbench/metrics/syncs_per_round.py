"""Syncs per round in the window, from the fleet's own comm counter
(``DecentralizedLearner.comm_totals["syncs"]``): how often the sync
layer fires."""


def read(run):
    if not run.rounds:
        return None
    return run.syncs / run.rounds
