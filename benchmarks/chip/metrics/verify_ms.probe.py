"""Verification time per call: the program's ``verify`` spans (gather
of the surviving rows, their exact distances, the pool update; each
ends in a copy to the host, so it holds the device's work), over the
calls."""
import devtrace


def read(run):
    if run.kind != "probe" or run.spans is None:
        return None
    spans = run.spans_named({"verify"})
    if not spans:
        return None
    return devtrace.length(spans) * 1e3 / len(run.calls)
