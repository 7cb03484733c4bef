"""Device time per step of the NEBB pass's tile-list pull, found by its own
name: the union of the device events whose HLO text begins with
``%nebb_stream`` (the name the program gives that ``pallas_call``), over
the window's steps (device trace).  The pass's rebuild, collision and
scatter around it are not counted.  Nothing to read where no event carries
the name: a program without the pull, or a geometry without open
boundaries."""

KERNEL = r"^%nebb_stream\b"


def read(run):
    if run.trace is None or run.steps == 0:
        return None
    kernel_s = run.trace.union_s(KERNEL)
    if kernel_s <= 0:
        return None
    return 1e3 * kernel_s / run.steps
