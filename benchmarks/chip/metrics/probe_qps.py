"""Queries answered per second: every query of every call that
returned, over the whole window.  Host clock."""


def read(run):
    if run.kind != "probe":
        return None
    answered = sum(c["queries"] for c in run.calls if not c.get("error"))
    return answered / run.window_s
