"""Work counts from shapes alone: the numerators of the utilization
and roofline metrics. They count what the algorithm needs, not what an
implementation happens to do, so a later change to the implementation
reads against the same count."""
from __future__ import annotations

from typing import Any, Dict


def decoder_forward_flops(conf: Dict[str, Any], batch: int, seq: int
                          ) -> float:
    """Multiply-add FLOPs (2 per MAC) of one forward pass of a dense
    decoder with a two-matrix MLP and an output head over the whole
    vocabulary. Attention counts the full seq x seq score and value
    products (the model computes them all; the causal mask zeroes half
    of the scores after the product)."""
    d, f, v = conf["d_model"], conf["d_ff"], conf["vocab"]
    nh, kv = conf["n_heads"], conf["n_kv_heads"]
    hd = d // nh
    t = batch * seq
    proj = 2 * t * d * (2 * nh * hd + 2 * kv * hd)   # q, o and k, v
    attn = 2 * 2 * batch * nh * seq * seq * hd       # scores and values
    mlp = 2 * 2 * t * d * f
    head = 2 * t * d * v
    return float(conf["n_layers"] * (proj + attn + mlp) + head)


def decoder_step_flops(conf: Dict[str, Any], batch: int, seq: int
                       ) -> float:
    """Forward and backward (twice the forward); no recomputation."""
    return 3.0 * decoder_forward_flops(conf, batch, seq)


#: bytes per lane and step of the mega-batch recurrence at 4-byte
#: values and indices: three dependency indices and the three end times
#: they gather, two delays (the device-order dependency has none), the
#: duration, the output index and the one end time written
RECURRENCE_BYTES_PER_LANE_STEP = 4 * (3 + 3 + 2 + 1 + 1 + 1)


def recurrence_bytes(steps: int, lanes: int) -> float:
    """Bytes the (T, K) recurrence must move at the least."""
    return float(RECURRENCE_BYTES_PER_LANE_STEP * steps * lanes)
