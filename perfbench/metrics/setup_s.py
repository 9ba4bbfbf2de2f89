"""Seconds from process start to the window's start (host clock):
imports, fleet construction, compilation or compile-cache loads, the
warm call and the timed call's first chunk."""


def read(run):
    return run.setup_s
