"""Host time per call spent folding verified distances into the
per-query k-NN pools: the union of the program's ``exec.pool`` spans in
the window, over the calls."""
import devtrace


def read(run):
    if run.kind != "probe" or run.spans is None:
        return None
    spans = run.spans_named({"exec.pool"})
    if not spans:
        return None
    return devtrace.length(spans) * 1e3 / len(run.calls)
