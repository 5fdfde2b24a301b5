"""Milliseconds per step of the in-tree reference step: all the time of
the window's reference blocks (host clock, each block ending on its
last loss) over their steps."""


def read(r):
    n = r.values.get("ref_steps")
    return r.values["ref_time_s"] / n * 1e3 if n else None
