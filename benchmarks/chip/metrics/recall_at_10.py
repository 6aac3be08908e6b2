"""Recall@10 of the budgeted answers of the window's first pass through
its query pool, against the reference's exact top 10: the mean over the
queries of the share of the exact ten that the answer holds.  The pool
is fixed by the seed and taken in a fixed order, and the window holds
one whole pass, so the number does not depend on how fast the calls
are."""


def read(run):
    return run.extra.get("recall_at_10")
