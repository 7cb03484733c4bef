"""Seconds from process start to the window's start: geometry, solver
construction, compile or cache load, the seeded initial state and the one
warm call (host clock)."""


def read(run):
    return run.setup_s
