"""Plan cells: DistSim answers measured against the real step.

The traffic file names the cell's references: ``step_reference``, a
module of ``reference/`` with the plain step (``make_weights``,
``token_pool``, ``run``); ``step_flops``, ``"<module>:<function>"`` of
this directory, the FLOPs of one step at a batch and a sequence length;
``prediction_reference``, a module of ``reference/`` with the plain
composition of DistSim's prediction (``compose``, which returns the
step and its parts, and ``missing_groups``), which takes the strategy's
``mp`` and ``dp`` and the traffic file's ``prediction_args``, if any,
as keywords; ``limits``, the limit of each number compared (a number
computed with no limit is logged, not compared).

Set-up makes the weights from the seed, builds the in-tree train step
with its optimizer state, drives it through its first three steps on
distinct rows (read for the correctness check), and makes one uncounted
answer. On more than one chip the step runs sharded over a
("data", "model") mesh of the strategy's dp x mp, with parameters,
optimizer state and batch placed by the program's sharding rules; the
profiler still times its GEMM programs on one chip, as DistSim profiles
a small slice. The window then repeats: one answer (a fresh
``MeasuredProvider`` profiles every unique event of the layout on the
chip, and ``DistSim.simulate()`` builds and predicts the step), then a
fixed block of reference steps through the same step object. An answer
or block in flight when the window closes completes and counts.

After the window, with the program's state freed, the plain references
run: the step composition over each answer's profiled table, and the
plain step, unsharded, over the first three steps.
"""
from __future__ import annotations

import gc
import importlib
import math
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from harness import BenchError, Cell, Check, Readings, log


def resolve(spec: str):
    """The function ``"<module>:<function>"`` names, its module found
    on the import path (this directory first)."""
    module, _, name = spec.partition(":")
    return getattr(importlib.import_module(module), name)


def _reference(name: str):
    return importlib.import_module(f"reference.{name}")


def cell_mesh(cell: Cell):
    """None on one chip; else the ("data", "model") mesh of shape
    (dp, mp) from the cell's strategy, which has to fill the chips."""
    n = len(cell.devices)
    if n == 1:
        return None
    from jax.sharding import Mesh
    st = cell.traffic["strategy"]
    if st["pp"] != 1 or st["dp"] * st["mp"] != n:
        raise BenchError(f"a plan cell on {n} chips needs pp 1 and "
                         f"dp x mp = {n}; its strategy is {st}")
    if cell.traffic["global_batch"] % st["dp"]:
        raise BenchError("the global batch must split over dp")
    return Mesh(np.asarray(cell.devices).reshape(st["dp"], st["mp"]),
                ("data", "model"))


def _shardings(mesh, param_shapes, batch):
    """The program's shardings of the parameters, the AdamW state and a
    batch: tensor parallelism over "model", the batch over "data"."""
    from repro.parallel import sharding
    from repro.train import optimizer as optlib
    pspecs = sharding.param_specs(param_shapes, mesh)
    return (sharding.to_shardings(pspecs, mesh),
            sharding.to_shardings(optlib.state_specs(pspecs), mesh),
            sharding.to_shardings(
                sharding.batch_specs(batch, mesh, ("data",)), mesh))


def _check_split(params, n: int) -> None:
    """The parameters lie on all ``n`` devices, some of them split."""
    import jax
    leaves = jax.tree.leaves(params)
    devs = {d for leaf in leaves for d in leaf.sharding.device_set}
    split = sum(1 for leaf in leaves
                if leaf.sharding.shard_shape(leaf.shape) != leaf.shape)
    if len(devs) != n or not split:
        raise BenchError(f"parameters on {len(devs)} of {n} devices, "
                         f"{split} leaves split")
    log(f"plan: parameters on {len(devs)} devices, {split} of "
        f"{len(leaves)} leaves split")


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

    conf, tr = cell.conf, cell.traffic
    step_ref = _reference(tr["step_reference"])
    cfg = arch_config(conf)
    strategy = Strategy(**tr["strategy"])
    cluster = cluster_spec(tr["cluster"])
    batch, seq = tr["global_batch"], tr["seq"]
    mesh = cell_mesh(cell)

    # ---- set-up: weights, step and state, the first three steps ----
    opts = ModelOptions(dtype=jnp.float32, remat=False)
    key = seed_key(cell.seed)
    want = jax.eval_shape(build_model(cfg, opts).init, key)
    rows = step_ref.token_pool(conf, cell.seed, tr["pool_batches"], batch,
                               seq)
    adamw = optlib.AdamWConfig(**tr["adamw"])
    tcfg = TrainConfig(adamw=adamw)
    if mesh is None:
        def make():
            return step_ref.make_weights(conf, key)
        step_fn = jax.jit(make_train_step(cfg, opts, tcfg),
                          donate_argnums=(0, 1))
        pool = [jax.device_put(b) for b in rows]
        init = optlib.init
    else:
        psh, osh, bsh = _shardings(mesh, want, rows[0])
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        make_sharded = jax.jit(lambda k: step_ref.make_weights(conf, k),
                               out_shardings=psh)
        mesh_key = jax.device_put(key, replicated)

        def make():
            return make_sharded(mesh_key)
        step_fn = jax.jit(
            make_train_step(cfg, opts, tcfg, grad_specs=psh),
            in_shardings=(psh, osh, bsh), out_shardings=(psh, osh, None),
            donate_argnums=(0, 1))
        pool = [jax.device_put(b, bsh) for b in rows]
        init = jax.jit(optlib.init, out_shardings=osh)
    weights = make()
    if (jax.tree.structure(want) != jax.tree.structure(weights)
            or any(a.shape != b.shape or a.dtype != b.dtype
                   for a, b in zip(jax.tree.leaves(want),
                                   jax.tree.leaves(weights)))):
        raise ValueError("the reference's parameter tree is not the "
                         "program's")
    if mesh is not None:
        _check_split(weights, len(cell.devices))
    params, weights = weights, None
    state = init(params)
    losses: List[float] = []
    grad_norms: Dict[str, float] = {}
    for s in range(3):
        params, state, metrics = step_fn(params, state, pool[s])
        losses.append(float(metrics["loss"]))
        if s == 0:
            grad_norms = {k: v / (1 - adamw.b1)
                          for k, v in _leaf_norms(state.mu).items()}
    # the weights were donated to the first step: make them again
    change = _leaf_norms(jax.tree.map(jnp.subtract, params, make()))
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

    conf, tr = cell.conf, cell.traffic
    step_ref = _reference(tr["step_reference"])
    ref_mod = _reference(tr["prediction_reference"])
    layout = {"mp": tr["strategy"]["mp"], "dp": tr["strategy"]["dp"],
              **tr.get("prediction_args", {})}
    hbm_bw = tr["hbm_bytes_per_s"]
    batch, seq = tr["global_batch"], tr["seq"]
    pred_gap, mismatch, parts = 0.0, 0, {}
    for a in got["answers"]:
        mismatch = max(mismatch, ref_mod.missing_groups(
            conf, batch, seq, list(a["table"]), **layout))
        try:
            parts = ref_mod.compose(conf, batch, seq, hbm_bw, a["table"],
                                    **layout)
            pred = (ref_mod.compose(conf, batch, seq, hbm_bw, a["table"],
                                    np.float32, **layout)["step"]
                    if control else a["pred"])
            pred_gap = max(pred_gap, abs(pred - parts["step"])
                           / parts["step"])
        except KeyError:
            pred_gap = float("inf")
    step = parts.pop("step", math.nan)
    log(f"plan: the reference's composition of an answer: step "
        f"{step * 1e3:.4f} ms; " + ", ".join(
            f"{k} {v * 1e3:.4f} ms ({v / step * 100:.4f} %)"
            for k, v in parts.items()))
    ref = step_ref.run(conf, tr["adamw"], got["key"], got["rows"])
    prog = got
    if control:
        prog = step_ref.run(conf, tr["adamw"], got["key"], got["rows"],
                            jnp.bfloat16)
    numbers = {
        "pred_gap": pred_gap,
        "event_mismatch": float(mismatch),
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": worst_leaf_gap(prog["grad"], ref["grad"], ref["grad"]),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"],
                                     ref["grad"]),
    }
    limits = {"event_mismatch": 0.0, **tr["limits"]}
    if set(limits) - set(numbers):
        raise BenchError(f"limits for numbers a plan cell does not have: "
                         f"{sorted(set(limits) - set(numbers))}")
    for k, v in numbers.items():
        if k not in limits:
            log(f"plan: {k} {v!r} (not compared)")
    return [Check(k, v, limits[k]) for k, v in numbers.items()
            if k in limits]


def readings(cell: Cell, got: Dict[str, Any], r: Readings) -> None:
    """End-to-end values and what the per-layer readers take."""
    tr = cell.traffic
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
        "ref_step_flops": resolve(tr["step_flops"])(
            cell.conf, tr["global_batch"], tr["seq"]),
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
