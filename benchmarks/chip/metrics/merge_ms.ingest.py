"""Device time of one merge of two sorted runs in the window: the
``jit__merge_cols`` programs (both runs' columns concatenated and
sorted by key) on the device, their total over their count.  Device
trace."""


def read(run):
    if run.kind != "ingest" or run.device_programs is None:
        return None
    t = run.program_times("jit__merge_cols")
    return 1e3 * sum(t) / len(t) if t else None
