"""Host time per call of the sharded engine's one-launch mesh scan:
the union of the program's ``mesh_launch`` spans in the window (the
launch, its wait for the device and the reads of its answers), over
the calls."""
import devtrace


def read(run):
    if run.kind != "probe" or run.spans is None:
        return None
    spans = run.spans_named({"mesh_launch"})
    if not spans:
        return None
    return devtrace.length(spans) * 1e3 / len(run.calls)
