"""Fluid-node updates per second over the whole window, in millions:
window steps x fluid nodes / seconds from the window's start to the end of
its last call (host clock, after ``block_until_ready``)."""


def read(run):
    return run.steps * run.n_fluid / run.window_s / 1e6
