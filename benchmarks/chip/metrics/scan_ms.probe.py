"""Executor time per call outside verification: the self time of the
program's ``seed``, ``prune`` and ``scan`` spans (the host's leaf loop,
the lower bound and its device syncs), that is their union less the
``verify`` spans inside it, over the calls."""
import devtrace


def read(run):
    if run.kind != "probe" or run.spans is None:
        return None
    outer = run.spans_named({"seed", "prune", "scan"})
    if not outer:
        return None
    inner = run.spans_named({"verify"})
    return devtrace.minus(outer, inner) * 1e3 / len(run.calls)
