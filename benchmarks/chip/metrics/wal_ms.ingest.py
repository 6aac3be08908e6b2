"""Write-ahead-log time per acknowledged batch: the union of the
program's ``wal.append`` spans in the window (the record built and
checksummed, written, flushed and synced), over the batches
acknowledged."""
import devtrace


def read(run):
    if run.kind != "ingest" or run.spans is None:
        return None
    spans = run.spans_named({"wal.append"})
    acks = sum(len(c.get("acks", ())) for c in run.calls)
    if not spans or not acks:
        return None
    return devtrace.length(spans) * 1e3 / acks
