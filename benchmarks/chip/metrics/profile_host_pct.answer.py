"""Share of ``MeasuredProvider``'s run phase (warm-up call and timed
repetitions, its ``timing_seconds``) in which the device ran none of
the profiler's GEMM programs: the dispatch and sync share of profiling.
The programs are the device modules named ``jit_profile_group``, summed
over the device planes (the profiler runs on one chip however many the
cell holds); a program whose modules carry another name gives nothing
to read."""


def read(r):
    n = r.values.get("answers")
    if r.trace is None or not r.trace["devices"] or not n:
        return None
    import tracereduce
    device = (tracereduce.module_seconds(r.trace, "profile_group")
              * len(r.trace["devices"]))
    if device <= 0:
        return None
    return (1.0 - device / r.values["run_s"]) * 100.0
