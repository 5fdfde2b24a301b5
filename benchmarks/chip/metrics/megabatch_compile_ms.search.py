"""Milliseconds per question in ``MegaBatch(engines)``: the host array
compile of the surviving candidates."""


def read(r):
    n = r.values.get("questions")
    return r.values["megabatch_compile_s"] / n * 1e3 if n else None
