"""Device time per step of the halo exchange, found by the program's own
scope: the device events of the ops that the compiled ``run(k)`` program
(``run.hlo``, its optimised HLO text) puts under ``lbm.phase.halo`` (the
row gathers of the boundary tile layers, their relayout copies, the
``ppermute``'s collective-permute start and done, the masked selects and
the row scatters into the state), each collective also counted from its
start to its done, so that the transfer counts while other ops run.  The
union per device, averaged over the devices, over the window's steps
(device trace).  Nothing to read without the program's text or a trace, or
where no event belongs to the exchange: one slab, an untraced run."""
import re

from bench import trace_reduce

SCOPE = "/lbm.phase.halo/"
INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
OP_NAME = re.compile(r'op_name="([^"]*)"')
DONE = re.compile(r"collective-permute-done\([^%)]*%([\w.\-]+)")


def scoped(hlo: str, scope: str = SCOPE) -> set:
    """Names of the instructions whose ``op_name`` holds ``scope``."""
    out = set()
    for line in hlo.splitlines():
        m, op = INSTR.match(line), OP_NAME.search(line)
        if m and op and scope in op.group(1) + "/":
            out.add(m.group(1))
    return out


def spans(ops, names: set) -> list:
    """The events named in ``names``, and for each collective-permute done
    among them the interval from its start's latest event to its end."""
    out, started = [], {}
    for o in sorted(ops, key=lambda o: o.start):
        m = INSTR.match(o.name)
        if not m or m.group(1) not in names:
            continue
        out.append(o)
        started[m.group(1)] = o.start
        done = DONE.search(o.name)
        if done and done.group(1) in started:
            out.append(trace_reduce.Span(o.name, started[done.group(1)],
                                         o.end))
    return out


def read(run):
    hlo = getattr(run, "hlo", None)
    if run.trace is None or not hlo or run.steps == 0:
        return None
    names = scoped(hlo)
    per_device = [trace_reduce.total(trace_reduce.merge(spans(ops, names)))
                  for ops in run.trace.ops.values()]
    halo_s = sum(per_device) / max(1, len(per_device))
    if halo_s <= 0:
        return None
    return 1e3 * halo_s / run.steps
