"""Seconds from process start to the window's start: weights, engine build,
compiling or loading every step shape, and the traffic's lead-in."""


def read(run):
    return run.setup_s
