"""Host time per call blocked on the device: the union of the program's
``exec.sync`` spans in the window (each a read of a device result: the
wait for the device to compute it and the copy to the host), over the
calls."""
import devtrace


def read(run):
    if run.kind != "probe" or run.spans is None:
        return None
    spans = run.spans_named({"exec.sync"})
    if not spans:
        return None
    return devtrace.length(spans) * 1e3 / len(run.calls)
