"""Plain reference of DistSim's prediction for one device (1M1P1D,
one microbatch): the step is the forward of every layer, then the
backward of every layer, then the optimizer.

- A layer's forward costs the profiled time of its GEMM group; its
  backward the group listed twice (data and weight gradients). The
  embedding lookup has no GEMM and costs nothing.
- A dense decoder layer's GEMMs, as (m, n, k) for t = batch x seq
  tokens: q, k, v projections (t, heads x hd, d); scores
  (batch x heads x seq, seq, hd); scores x values
  (batch x heads x seq, hd, seq); output projection (t, d, heads x hd);
  MLP (t, d_ff, d) and (t, d, d_ff). The head is (t, vocab, d).
- The optimizer streams the parameters six times at two bytes each,
  twice over, at the cluster's HBM bandwidth; parameters are the
  matrices at two bytes (embedding, and per layer the four attention
  and two MLP matrices; a tied head adds none).

The profiled times are the input: a table from GEMM group to seconds.
The layout's ``mp`` and ``dp`` are taken and have to be 1.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

Group = Tuple[Tuple[int, int, int], ...]


def layout_groups(conf: Dict, batch: int, seq: int) -> Dict[str, Group]:
    d, f, v = conf["d_model"], conf["d_ff"], conf["vocab"]
    nh, kv = conf["n_heads"], conf["n_kv_heads"]
    hd = d // nh
    t = batch * seq
    block = ((t, nh * hd, d), (t, kv * hd, d), (t, kv * hd, d),
             (batch * nh * seq, seq, hd), (batch * nh * seq, hd, seq),
             (t, d, nh * hd), (t, f, d), (t, d, f))
    head = ((t, v, d),)
    return {"block_fwd": block, "block_bwd": block + block,
            "head_fwd": head, "head_bwd": head + head}


def param_bytes(conf: Dict) -> float:
    d, f, v = conf["d_model"], conf["d_ff"], conf["vocab"]
    nh, kv = conf["n_heads"], conf["n_kv_heads"]
    hd = d // nh
    layer = 2 * d * hd * (2 * nh + 2 * kv) + 2 * 2 * d * f
    head = 0 if conf.get("tie_embeddings") else 2 * d * v
    return 2 * v * d + conf["n_layers"] * layer + head


def _one_device(mp: int, dp: int) -> None:
    if (mp, dp) != (1, 1):
        raise ValueError(f"a one-device composition, asked for mp {mp} "
                         f"x dp {dp}")


def compose(conf: Dict, batch: int, seq: int, hbm_bw: float,
            table: Dict[Group, float], dtype=float, *, mp: int = 1,
            dp: int = 1) -> Dict[str, float]:
    """The predicted step from the profiled ``table`` and its parts:
    ``step``, ``compute`` (GEMM groups) and ``opt``; ``dtype`` (e.g.
    ``numpy.float32``) computes them in another precision."""
    _one_device(mp, dp)
    g = layout_groups(conf, batch, seq)
    n = conf["n_layers"]
    fwd = dtype(0.0)
    for _ in range(n):
        fwd = dtype(fwd + dtype(table[g["block_fwd"]]))
    fwd = dtype(fwd + dtype(table[g["head_fwd"]]))
    bwd = dtype(0.0)
    for _ in range(n):
        bwd = dtype(bwd + dtype(table[g["block_bwd"]]))
    bwd = dtype(bwd + dtype(table[g["head_bwd"]]))
    opt = dtype(6.0 * param_bytes(conf) * 2 / hbm_bw)
    compute = dtype(fwd + bwd)
    return {"step": float(dtype(compute + opt)), "compute": float(compute),
            "opt": float(opt)}


def missing_groups(conf: Dict, batch: int, seq: int,
                   profiled: List[Group], mp: int = 1, dp: int = 1) -> int:
    """How many groups the layout needs and the profile lacks, plus how
    many it profiled that the layout does not have."""
    _one_device(mp, dp)
    want = set(layout_groups(conf, batch, seq).values())
    got = {g for g in profiled if g}
    return len(want ^ got)
