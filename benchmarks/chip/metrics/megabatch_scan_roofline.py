"""The device scan's share of its roofline: the bytes the recurrence
needs (``counts.recurrence_bytes`` from each traced question's T and K)
over the scan programs' device time in the trace times the peak HBM
bandwidth. The recurrence does one add per byte or less, so bandwidth
bounds it."""


def read(r):
    if r.trace is None:
        return None
    import tracereduce
    seconds = tracereduce.module_seconds(r.trace, "scan_program")
    if seconds <= 0:
        return None
    return (r.values["scan_bytes"] / seconds
            / r.peaks["hbm_bytes_per_s"] * 100.0)
