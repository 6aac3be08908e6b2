"""Time spent in ``fsync`` per acknowledged batch: the union of the
program's ``fsync`` spans in the window (the log's, the segments', the
manifest's and their directory's), over the batches acknowledged."""
import devtrace


def read(run):
    if run.kind != "ingest" or run.spans is None:
        return None
    spans = run.spans_named({"fsync"})
    acks = sum(len(c.get("acks", ())) for c in run.calls)
    if not spans or not acks:
        return None
    return devtrace.length(spans) * 1e3 / acks
