"""Device busy time per step outside the fused kernel's intervals: the
NEBB boundary pass, the layout copies around each call and the scratch-row
reset (device trace).  Nothing to read where the trace holds no kernel."""


def read(run):
    if run.trace is None or run.steps == 0:
        return None
    kernel_s = run.trace.union_s(run.kernel)
    if kernel_s <= 0:
        return None
    return 1e3 * (run.trace.busy_s - kernel_s) / run.steps
