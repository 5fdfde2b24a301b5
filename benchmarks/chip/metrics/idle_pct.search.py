"""Share of the traced window in which the device ran no operation."""


def read(r):
    if r.trace is None or not r.trace["devices"]:
        return None
    import tracereduce
    return tracereduce.idle_share(r.trace) * 100.0
