"""Raw series made queryable per second over the window: rows whose
insert was acknowledged (the write-ahead log is synced before the ack,
and each round drains before it ends) in the ingest mixes, rows bulk
loaded into a finished tree in the build mix.  Host clock."""


def read(run):
    if run.kind not in ("ingest", "build"):
        return None
    return sum(c.get("rows", 0) for c in run.calls
               if not c.get("error")) / run.window_s
