"""Seconds ``MeasuredProvider`` spent running its GEMM programs on the
chip per answer (warm-up call and timed repetitions): its
``timing_seconds`` counter."""


def read(r):
    n = r.values.get("answers")
    return r.values["run_s"] / n if n else None
