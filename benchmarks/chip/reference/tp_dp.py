"""Plain reference of DistSim's prediction for tensor and data
parallelism without a pipeline (mp x dp, pp = 1, one microbatch per
data-parallel rank): every rank runs the forward of every layer, then
the backward of every layer, then all ranks reduce their gradients and
step the optimizer over their shard.

Written from the event semantics DistSim documents (``core/events.py``,
``core/costmodel.py``, the paper's §4): a layer's forward is its
compute event followed by its tensor-parallel all-reduce; the backward
is the compute listed twice (data and weight gradients) followed by the
same all-reduce; the data-parallel level adds one gradient all-reduce
and the optimizer.

- Each rank holds ``batch // dp`` rows: t = (batch // dp) x seq tokens.
- A layer's GEMM group is that of ``single_device`` at t tokens, each
  GEMM split ``mp`` ways (floored, never below 1) on the axis the layer
  graph states: q, k, v projections and the first MLP matrix on n (the
  columns); scores and scores x values on m (the heads); the output
  projection and the second MLP matrix on k (the rows). The head splits
  the vocabulary on n. The embedding lookup has no GEMM and costs
  nothing.
- With mp > 1 each decoder layer all-reduces its attention and MLP
  outputs over the mp ranks, forward and backward: 2 x 2 bytes x t x
  d_model per pass. The head and the embedding exchange nothing.
- With dp > 1 the ranks all-reduce their gradients: the parameter
  bytes of ``single_device.param_bytes`` over mp.
- A ring all-reduce over n ranks moves 2 (n - 1) / n of the tensor per
  rank at ``ring_bytes_per_s`` and pays ``ring_hop_latency_s`` on each
  of its 2 (n - 1) hops. Every rank lies in one island of the cluster.
- The optimizer streams the rank's parameter bytes (over mp) six times
  at two bytes each, twice over, at the HBM bandwidth.

The profiled times are the input: a table from GEMM group to seconds.
At mp = dp = 1 this is ``single_device``'s composition.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from reference.single_device import Group, param_bytes


def layout_groups(conf: Dict, batch: int, seq: int, mp: int = 1,
                  dp: int = 1) -> Dict[str, Group]:
    d, f, v = conf["d_model"], conf["d_ff"], conf["vocab"]
    nh, kv = conf["n_heads"], conf["n_kv_heads"]
    hd = d // nh
    rows = batch // dp
    t = rows * seq

    def split(x: int) -> int:
        return max(1, x // mp)

    block = ((t, split(nh * hd), d), (t, split(kv * hd), d),
             (t, split(kv * hd), d),
             (split(rows * nh * seq), seq, hd),
             (split(rows * nh * seq), hd, seq),
             (t, d, split(nh * hd)), (t, split(f), d), (t, d, split(f)))
    head = ((t, split(v), d),)
    return {"block_fwd": block, "block_bwd": block + block,
            "head_fwd": head, "head_bwd": head + head}


def ring_all_reduce(nbytes: float, n: int, bytes_per_s: float,
                    hop_latency_s: float, dtype=float) -> float:
    """Seconds of a ring all-reduce of ``nbytes`` over ``n`` ranks."""
    if n <= 1:
        return dtype(0.0)
    vol = dtype(dtype(2.0 * (n - 1) / n) * dtype(nbytes))
    return dtype(dtype(vol / dtype(bytes_per_s))
                 + dtype(2 * (n - 1) * hop_latency_s))


def compose(conf: Dict, batch: int, seq: int, hbm_bw: float,
            table: Dict[Group, float], dtype=float, *, mp: int = 1,
            dp: int = 1, ring_bytes_per_s: Optional[float] = None,
            ring_hop_latency_s: float = 0.0) -> Dict[str, float]:
    """The predicted step from the profiled ``table`` and its parts:
    ``step``, ``compute`` (GEMM groups), ``tp`` (tensor-parallel
    all-reduces), ``dp`` (gradient all-reduce) and ``opt``; ``dtype``
    (e.g. ``numpy.float32``) computes them in another precision."""
    if (mp > 1 or dp > 1) and ring_bytes_per_s is None:
        raise ValueError("mp or dp above 1 needs ring_bytes_per_s")
    g = layout_groups(conf, batch, seq, mp, dp)
    n = conf["n_layers"]
    t = (batch // dp) * seq
    tp = ring_all_reduce(2 * 2.0 * t * conf["d_model"], mp,
                         ring_bytes_per_s, ring_hop_latency_s, dtype)
    compute = tp_total = dtype(0.0)
    passes = []
    for phase in ("fwd", "bwd"):
        acc = dtype(0.0)
        for _ in range(n):
            acc = dtype(acc + dtype(table[g[f"block_{phase}"]]))
            compute = dtype(compute + dtype(table[g[f"block_{phase}"]]))
            if mp > 1:
                acc = dtype(acc + tp)
                tp_total = dtype(tp_total + tp)
        acc = dtype(acc + dtype(table[g[f"head_{phase}"]]))
        compute = dtype(compute + dtype(table[g[f"head_{phase}"]]))
        passes.append(acc)
    shard = param_bytes(conf) / mp
    grad_ar = ring_all_reduce(shard, dp, ring_bytes_per_s,
                              ring_hop_latency_s, dtype)
    opt = dtype(6.0 * shard * 2 / hbm_bw)
    step = dtype(passes[0] + passes[1])
    if dp > 1:
        step = dtype(step + grad_ar)
    step = dtype(step + opt)
    return {"step": float(step), "compute": float(compute),
            "tp": float(tp_total), "dp": float(grad_ar), "opt": float(opt)}


def missing_groups(conf: Dict, batch: int, seq: int,
                   profiled: List[Group], mp: int = 1, dp: int = 1,
                   **_ring) -> int:
    """How many groups the layout needs and the profile lacks, plus how
    many it profiled that the layout does not have."""
    want = set(layout_groups(conf, batch, seq, mp, dp).values())
    got = {gr for gr in profiled if gr}
    return len(want ^ got)
