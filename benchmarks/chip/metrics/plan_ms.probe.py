"""Planner time per call: the program's ``plan`` spans (pricing every
leaf by its fence bound) in the window, over the calls."""
import devtrace


def read(run):
    if run.kind != "probe" or run.spans is None:
        return None
    spans = run.spans_named({"plan"})
    if not spans:
        return None
    return devtrace.length(spans) * 1e3 / len(run.calls)
