"""DistSim's error against the real step: ``abs(mean predicted step /
measured step - 1) x 100``. The mean is over the window's answers; the
measured step is all the time of the window's reference blocks over
their steps (host clock, each block ending on its last loss)."""


def read(r):
    v = r.values
    if not v.get("answers") or not v.get("ref_steps"):
        return None
    step = v["ref_time_s"] / v["ref_steps"]
    return abs(v["pred_mean_s"] / step - 1.0) * 100.0
