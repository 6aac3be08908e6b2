"""Host time per call spent preparing and enqueueing device programs
(row gathers, padding, host-to-device copies, dispatch): the union of
the program's ``exec.launch`` spans in the window, over the calls."""
import devtrace


def read(run):
    if run.kind != "probe" or run.spans is None:
        return None
    spans = run.spans_named({"exec.launch"})
    if not spans:
        return None
    return devtrace.length(spans) * 1e3 / len(run.calls)
