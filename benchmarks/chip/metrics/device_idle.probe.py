"""Share of the window, in percent, in which no operation ran on the
device: 1 - busy / window, busy being the union of the device's
operations in the profiler's trace."""


def read(run):
    busy = run.busy_s()
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / run.window_s)
