"""Bytes written to storage (write-ahead log and segment files, the
program's ``io.bytes_written`` counter) per byte of raw series
acknowledged in the window."""


def read(run):
    if run.kind != "ingest":
        return None
    rows = sum(c.get("rows", 0) for c in run.calls if not c.get("error"))
    written = run.counters.get("io.bytes_written", 0)
    if not rows or not written:
        return None
    return written / (rows * run.config["series_len"] * 4)
