"""Milliseconds per candidate outside the two mega-batch spans: search
wall time less ``MegaBatch(engines)`` and ``MegaBatch.predict``, i.e.
the memory mask, the engine builds and the pruning replay."""


def read(r):
    v = r.values
    if not v.get("candidates"):
        return None
    rest = v["question_wall_s"] - v["megabatch_compile_s"] - v["scan_wall_s"]
    return rest / v["candidates"] * 1e3
