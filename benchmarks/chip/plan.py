"""Plan cells: DistSim answers measured against the real step.

Set-up makes the weights from the seed, builds the in-tree train step
with its optimizer state, drives it through its first three steps on
distinct rows (read for the correctness check), and makes one uncounted
answer. The window then repeats: one answer (a fresh
``MeasuredProvider`` profiles every unique event of the layout on the
chip, and ``DistSim.simulate()`` builds and predicts the step), then a
fixed block of reference steps through the same step object. An answer
or block in flight when the window closes completes and counts.

After the window, with the program's state freed, the plain references
run: the step composition over each answer's profiled table, and the
plain decoder over the first three steps.
"""
from __future__ import annotations

import gc
import importlib
import math
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from harness import Cell, Check, Readings, log


def _answer(cell: Cell, cfg, strategy, cluster):
    from repro.core import DistSim, MeasuredProvider
    tr = cell.traffic
    provider = MeasuredProvider(cluster, reps=tr["reps"])
    with cell.spans.span("bench.answer"):
        t0 = time.perf_counter()
        pred = DistSim(cfg, strategy, tr["global_batch"], tr["seq"],
                       provider).simulate().batch_time
        wall = time.perf_counter() - t0
    table = {tuple((g.m, g.n, g.k) for g in e.gemms): t
             for e, t in provider.cache_snapshot().items()
             if e.kind == "compute"}
    return {"wall": wall, "pred": pred,
            "compile": provider.compile_seconds,
            "run": provider.timing_seconds, "table": table}


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.linalg.norm(jnp.ravel(x)) for x in xs]
                    )([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   grad: Dict[str, float]) -> float:
    """Largest gap between two per-leaf norms, over the larger of the
    reference leaf's norm and the median leaf's. Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out:
    they move by round-off alone."""
    if set(got) != set(want):
        return float("inf")
    med_grad = statistics.median(grad.values())
    keep = [k for k in want if grad[k] >= 1e-3 * med_grad]
    med = statistics.median(want[k] for k in keep)
    return max(abs(got[k] - want[k]) / max(abs(want[k]), med)
               for k in keep)


def run(cell: Cell, control: bool = False) -> Dict[str, Any]:
    """The set-up and the window; ``control`` changes nothing here (the
    plan cell's control is computed in :func:`check`)."""
    import jax
    import jax.numpy as jnp
    from repro.core import Strategy
    from repro.models.api import build_model
    from repro.models.layers import ModelOptions
    from repro.train import optimizer as optlib
    from repro.train.step import TrainConfig, make_train_step

    from harness import arch_config, cluster_spec, seed_key
    from reference import decoder

    conf, tr = cell.conf, cell.traffic
    cfg = arch_config(conf)
    strategy = Strategy(**tr["strategy"])
    cluster = cluster_spec(tr["cluster"])
    batch, seq = tr["global_batch"], tr["seq"]
    if batch % len(cell.devices):
        raise ValueError("global batch must split over the chips")

    # ---- set-up: weights, step and state, the first three steps ----
    opts = ModelOptions(dtype=jnp.float32, remat=False)
    key = seed_key(cell.seed)
    weights = decoder.make_weights(conf, key)
    want = jax.eval_shape(build_model(cfg, opts).init, key)
    if (jax.tree.structure(want) != jax.tree.structure(weights)
            or any(a.shape != b.shape or a.dtype != b.dtype
                   for a, b in zip(jax.tree.leaves(want),
                                   jax.tree.leaves(weights)))):
        raise ValueError("the reference's parameter tree is not the "
                         "program's")
    adamw = optlib.AdamWConfig(**tr["adamw"])
    step_fn = jax.jit(make_train_step(cfg, opts, TrainConfig(adamw=adamw)),
                      donate_argnums=(0, 1))
    rows = decoder.token_pool(conf, cell.seed, tr["pool_batches"], batch,
                              seq)
    pool = [jax.device_put(b) for b in rows]
    params, weights = weights, None
    state = optlib.init(params)
    losses: List[float] = []
    grad_norms: Dict[str, float] = {}
    for s in range(3):
        params, state, metrics = step_fn(params, state, pool[s])
        losses.append(float(metrics["loss"]))
        if s == 0:
            grad_norms = {k: v / (1 - adamw.b1)
                          for k, v in _leaf_norms(state.mu).items()}
    # the weights were donated to the first step: make them again
    change = _leaf_norms(jax.tree.map(
        jnp.subtract, params, decoder.make_weights(conf, key)))
    _answer(cell, cfg, strategy, cluster)          # warm-up, uncounted
    jax.block_until_ready(params)
    setup_s = time.perf_counter() - cell.t_start
    log(f"plan: set-up {setup_s:.3f} s; first losses {losses}")

    # ---- the window ----
    answers: List[Dict[str, Any]] = []
    ref_time, ref_steps, step_i = 0.0, 0, 3
    block = tr["block_steps"]
    with cell.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < cell.seconds:
            answers.append(_answer(cell, cfg, strategy, cluster))
            with cell.spans.span("bench.ref_block"):
                b0 = time.perf_counter()
                for _ in range(block):
                    params, state, metrics = step_fn(
                        params, state, pool[step_i % len(pool)])
                    step_i += 1
                last_loss = float(metrics["loss"])
                ref_time += time.perf_counter() - b0
            ref_steps += block
        window_s = time.perf_counter() - t0
    return {
        "setup_s": setup_s, "window_s": window_s, "answers": answers,
        "ref_time": ref_time, "ref_steps": ref_steps,
        "last_loss": last_loss, "losses": losses, "grad": grad_norms,
        "change": change, "key": key, "rows": rows[:3],
        "_free": (params, state, pool),
    }


def check(cell: Cell, got: Dict[str, Any], control: bool = False
          ) -> List[Check]:
    """Compare the window's answers and the first three steps with the
    plain references; ``control`` puts the references, in the precision
    below the configuration's, in the program's place."""
    import jax.numpy as jnp

    from reference import decoder
    ref_mod = importlib.import_module(
        f"reference.{cell.traffic['prediction_reference']}")
    conf, tr = cell.conf, cell.traffic
    limits = tr["limits"]
    hbm_bw = tr["hbm_bytes_per_s"]
    batch, seq = tr["global_batch"], tr["seq"]
    pred_gap, mismatch = 0.0, 0
    for a in got["answers"]:
        mismatch = max(mismatch, ref_mod.missing_groups(
            conf, batch, seq, list(a["table"])))
        try:
            want = ref_mod.compose(conf, batch, seq, hbm_bw, a["table"])
            pred = (ref_mod.compose(conf, batch, seq, hbm_bw, a["table"],
                                    np.float32) if control else a["pred"])
            pred_gap = max(pred_gap, abs(pred - want) / want)
        except KeyError:
            pred_gap = float("inf")
    ref = decoder.run(conf, tr["adamw"], got["key"], got["rows"])
    prog = got
    if control:
        prog = decoder.run(conf, tr["adamw"], got["key"], got["rows"],
                           jnp.bfloat16)
    # the losses and the first gradient are read but not compared: the
    # control reads no more than three times what sound runs read, and
    # no fault of a one-chip step moves them
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    grad_gap = worst_leaf_gap(prog["grad"], ref["grad"], ref["grad"])
    log(f"plan: loss_gap {loss_gap!r} grad_gap {grad_gap!r} (not compared)")
    return [
        Check("pred_gap", pred_gap, limits["pred_gap"]),
        Check("event_mismatch", float(mismatch), 0.0),
        Check("change_gap", worst_leaf_gap(prog["change"], ref["change"],
                                           ref["grad"]),
              limits["change_gap"]),
    ]


def readings(cell: Cell, got: Dict[str, Any], r: Readings) -> None:
    """End-to-end values and what the per-layer readers take."""
    from counts import decoder_step_flops
    answers = got["answers"]
    n = len(answers)
    step = got["ref_time"] / got["ref_steps"]
    mean_pred = sum(a["pred"] for a in answers) / n
    r.values.update({
        "setup_s": got["setup_s"],
        "answer_s": sum(a["wall"] for a in answers) / n,
        "pred_mean_s": mean_pred,
        "answers": n,
        "compile_s": sum(a["compile"] for a in answers),
        "run_s": sum(a["run"] for a in answers),
        "answer_wall_s": sum(a["wall"] for a in answers),
        "ref_time_s": got["ref_time"],
        "ref_steps": got["ref_steps"],
        "ref_step_flops": decoder_step_flops(
            cell.conf, cell.traffic["global_batch"], cell.traffic["seq"]),
        "window_s": got["window_s"],
    })
    preds = sorted(a["pred"] * 1e3 for a in answers)
    log(f"plan: predicted ms min {preds[0]:.4f} median "
        f"{statistics.median(preds):.4f} max {preds[-1]:.4f}")
    log(f"plan: {n} answers, mean predicted {mean_pred * 1e3:.4f} ms; "
        f"{got['ref_steps']} reference steps, {step * 1e3:.4f} ms each; "
        f"last loss {got['last_loss']!r}")


def free(got: Dict[str, Any]) -> None:
    got.pop("_free", None)
    gc.collect()


def failed(got: Dict[str, Any]) -> int:
    return 0 if math.isfinite(got["last_loss"]) else 1


def attempted(got: Dict[str, Any]) -> int:
    return len(got["answers"]) + got["ref_steps"]
