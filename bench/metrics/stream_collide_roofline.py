"""Share of its roofline that the fused stream+collide kernel reaches: the
least time the chip needs for the window's Eqn (10) bytes and LBGK
operations (bench/peaks.py), over the union of the kernel's intervals in the
device trace.  Nothing to read where the trace holds no such kernel."""
from bench import peaks


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.union_s(run.kernel)
    if kernel_s <= 0:
        return None
    nbytes = peaks.eqn10_bytes(run.q, run.n_fluid, run.itemsize) * run.steps
    flops = peaks.lbgk_flops_per_node(run.e) * run.n_fluid * run.steps
    return 100.0 * peaks.roofline_seconds(
        nbytes, flops, run.device["kind"]) / kernel_s
