"""Queries answered per second by the toy loop.  Host clock."""


def read(run):
    if run.kind != "toy_blocks":
        return None
    return sum(c["queries"] for c in run.calls
               if not c.get("error")) / run.window_s
