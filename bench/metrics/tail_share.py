"""Share of device busy time spent in the serial tail's program (%).

The tail is the jitted ``provider_tail`` (``core/coloring.py``); its
executions are the trace's ``XLA Modules`` events whose name holds that
name.  A traced window in which the tail never ran reads 0."""
from bench import profile_reduce as pr

PROGRAM = "provider_tail"


def read(ctx):
    summary = ctx.get("trace")
    if summary is None or not ctx.get("busy_s"):
        return None
    lo, hi = ctx["window_ns"]
    tail_ns = sum(pr.program_ns(d.programs, lo, hi, PROGRAM)
                  for d in summary.devices) / len(summary.devices)
    return 100.0 * tail_ns * 1e-9 / ctx["busy_s"]
