"""Seconds ``MeasuredProvider`` spent compiling (or fetching from the
compile cache) per answer: its ``compile_seconds`` counter."""


def read(r):
    n = r.values.get("answers")
    return r.values["compile_s"] / n if n else None
