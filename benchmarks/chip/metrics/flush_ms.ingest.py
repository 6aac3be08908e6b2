"""Mean time of one flush (a buffered batch built into a sorted run on
the device) in the window: the program's ``compact.flush_ms``
histogram, its sum over its count."""


def read(run):
    if run.kind != "ingest":
        return None
    n = run.counters.get("compact.flush_ms.count", 0)
    return run.counters["compact.flush_ms.sum"] / n if n else None
