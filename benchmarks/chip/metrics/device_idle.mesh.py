"""Share of the window, in percent, in which no operation ran on a
chip: 1 - busy / window for each device plane of the trace (the chips
of the mesh), averaged over the planes."""
import devtrace


def read(run):
    if run.device_ops is None or not run.device_ops:
        return None
    lo, hi = run.window
    idle = [1.0 - devtrace.busy(ops, lo, hi) / run.window_s
            for ops in run.device_ops.values()]
    return 100.0 * sum(idle) / len(idle)
