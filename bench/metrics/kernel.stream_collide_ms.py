"""Device time per step of the fused stream+collide kernel, found by its
own name: the union of the device events whose HLO text begins with
``%stream_collide`` (the name the program gives its ``pallas_call``), over
the window's steps (device trace).  A Pallas kernel of another name, or
one with none, is not counted.  Nothing to read where no event carries the
name."""

KERNEL = r"^%stream_collide\b"


def read(run):
    if run.trace is None or run.steps == 0:
        return None
    kernel_s = run.trace.union_s(KERNEL)
    if kernel_s <= 0:
        return None
    return 1e3 * kernel_s / run.steps
