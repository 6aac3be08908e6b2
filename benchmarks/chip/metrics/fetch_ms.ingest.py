"""Time per commit spent fetching new runs off the device before they
are written as segments: the union of the program's ``segment.fetch``
spans in the window (the copies to the host, and the wait for the
flush and merge programs that compute them), over the
``compact.commit`` spans."""
import devtrace


def read(run):
    if run.kind != "ingest" or run.spans is None:
        return None
    spans = run.spans_named({"segment.fetch"})
    commits = run.spans_named({"compact.commit"})
    if not spans or not commits:
        return None
    return devtrace.length(spans) * 1e3 / len(commits)
