"""Milliseconds per answer outside the profiler: the benchmark's span
around ``DistSim(...).simulate()`` less the provider's compile and run
seconds, i.e. graph, event and engine build plus the predict."""


def read(r):
    n = r.values.get("answers")
    if not n:
        return None
    v = r.values
    return (v["answer_wall_s"] - v["compile_s"] - v["run_s"]) / n * 1e3
