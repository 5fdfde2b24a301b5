"""The reference step's share of the chips' bf16 peak: the FLOPs a
forward and backward step needs (``counts.decoder_step_flops``, no
recomputation) over the measured step time, the chips and the peak."""


def read(r):
    n = r.values.get("ref_steps")
    if not n:
        return None
    step = r.values["ref_time_s"] / n
    return (r.values["ref_step_flops"] / step
            / (r.chips * r.peaks["bf16_flops_per_s"]) * 100.0)
