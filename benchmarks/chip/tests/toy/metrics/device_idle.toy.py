"""Share of the window, in percent, in which no operation ran on the
devices, averaged over the devices that ran anything."""


def read(run):
    busy = run.busy_s()
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / run.window_s)
