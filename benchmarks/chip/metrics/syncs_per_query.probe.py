"""Reads of a device result the host waited for, per query answered:
the program's ``query.device_syncs_total`` counter over the window (one
for each ``exec.sync`` span), over the queries of the calls that
returned."""


def read(run):
    if run.kind != "probe":
        return None
    syncs = run.counters.get("query.device_syncs_total")
    answered = sum(c["queries"] for c in run.calls if not c.get("error"))
    if not syncs or not answered:
        return None
    return syncs / answered
