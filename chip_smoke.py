"""Smoke run of DistSim's measured path on a TPU, at gpt2_345m's full
width (24 layers, d_model 1024, 16 heads, vocab 50257, seq 1024).

    python3 chip_smoke.py             # one chip: phases 1-5
    python3 chip_smoke.py --chips 4   # four chips: the dp=2 x mp=2 phase

One chip:

1. the device is a TPU, or the run stops;
2. the reference: ``repro.train.train_loop.fit`` steps the in-tree model
   (float32, no remat) and gives the median step time;
3. a cold DistSim answer: a fresh ``MeasuredProvider`` profiles the same
   model on the chip and ``DistSim.simulate()`` predicts the step;
4. the mega-batch recurrence over a gpt2_345m search grid on the device
   (``backend="jax"``) ranks every candidate as numpy does;
5. the Pallas kernels, compiled, match ``repro.kernels.ref``.

``--chips 4`` runs only the in-tree train step sharded dp=2 x mp=2 over
a ("data", "model") mesh of four chips and DistSim's prediction of it.

The last line of standard output is one JSON object naming the device;
any failure raises and the exit code is not 0.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "gpt2_345m"
SEQ = 1024
#: global batch of the one-chip reference step: the largest whose
#: float32, no-remat step fits one v5e chip's 16 GB (see PERF.md)
BATCH = 1
STEPS = 8          # the first two include compilation and are dropped
WARMUP = 2


def _log(msg: str) -> None:
    print(msg, flush=True)


def _opts():
    import jax.numpy as jnp
    from repro.models.layers import ModelOptions
    # what MeasuredProvider profiles: float32 GEMMs, backward = 2x fwd
    return ModelOptions(dtype=jnp.float32, remat=False)


def reference_step(cfg, batch: int, seq: int, steps: int = STEPS) -> float:
    """Median measured step time of the in-tree model (seconds)."""
    import math
    from repro.train.train_loop import LoopConfig, fit
    res = fit(cfg, opts=_opts(),
              loop=LoopConfig(steps=steps, seq_len=seq, global_batch=batch,
                              log_every=10 ** 9),
              verbose=False)
    if not all(math.isfinite(x) for x in res.losses):
        raise RuntimeError(f"non-finite loss: {res.losses}")
    step = statistics.median(res.step_times[WARMUP:])
    _log(f"reference: {cfg.name} batch {batch} seq {seq}: median step "
         f"{step * 1e3:.3f} ms over {len(res.step_times) - WARMUP} steps; "
         f"losses {[round(x, 4) for x in res.losses]}")
    _log(f"reference: step times ms "
         f"{[round(t * 1e3, 3) for t in res.step_times]}")
    return step


def predict(cfg, strategy, batch: int, seq: int) -> float:
    """Cold DistSim answer from a fresh MeasuredProvider (seconds)."""
    from repro.core import DistSim, MeasuredProvider, V5E_POD
    provider = MeasuredProvider(V5E_POD)
    t0 = time.perf_counter()
    pred = DistSim(cfg, strategy, batch, seq, provider).simulate().batch_time
    wall = time.perf_counter() - t0
    snap = provider.cache_snapshot()
    n_compute = sum(1 for e in snap if e.kind == "compute")
    _log(f"distsim {strategy.label()}: predicted step {pred * 1e3:.3f} ms; "
         f"{len(snap)} unique events ({n_compute} compute, "
         f"{provider.n_groups} GEMM programs); compile "
         f"{provider.compile_seconds:.3f} s, timing "
         f"{provider.timing_seconds:.3f} s, answer {wall:.3f} s")
    return pred


def scan_check(cfg, seq: int, n_devices: int = 64,
               global_batch: int = 64) -> None:
    """Rank a search grid's candidates on numpy and on the device."""
    import numpy as np
    from repro.core import AnalyticalProvider, DistSim, MegaBatch, V5E_POD
    from repro.core.megabatch import RANK_RTOL, same_ranking
    from repro.search.space import enumerate_candidates
    provider = AnalyticalProvider(V5E_POD)
    cands = enumerate_candidates(n_devices, global_batch,
                                 schedules=("1f1b", "gpipe"))
    engines = [DistSim(cfg, c.strategy, global_batch, seq,
                       provider).engine() for c in cands]
    mb = MegaBatch(engines)
    ref = mb.predict("numpy").batch_times
    t0 = time.perf_counter()
    got = mb.predict("jax")
    wall = time.perf_counter() - t0
    if got.backend != "jax":
        raise RuntimeError(f"scan ran on {got.backend}, not jax")
    same = same_ranking(ref, got.batch_times)
    swaps = int(np.sum(np.argsort(ref, kind="stable")
                       != np.argsort(got.batch_times, kind="stable")))
    rel = float(np.max(np.abs(got.batch_times - ref) / ref))
    _log(f"scan: {mb.K} candidates, {mb.T} steps: rankings "
         f"{'identical' if same else 'DIFFER'} up to ties within "
         f"{RANK_RTOL:g} ({swaps} positions hold tied candidates in "
         f"another order); max relative difference {rel:.3e}; device "
         f"scan {wall:.3f} s (compile included)")
    if not same:
        raise RuntimeError("device scan ranks candidates differently")


def _match(label: str, got, want, tol: float) -> None:
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    _log(f"kernel: {label}: max abs error "
         f"{float(np.max(np.abs(got - want))):.3e}")
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def kernel_check(batch: int, seq: int, heads: int, head_dim: int,
                 d_model: int) -> None:
    """Compiled flash attention and rmsnorm against the references."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    def compiled(fn, *args):
        if "tpu_custom_call" not in fn.lower(*args).as_text():
            raise RuntimeError(f"{fn.__name__} is not a compiled kernel")
        return fn(*args)

    def bh(x):
        return x.transpose(0, 2, 1, 3).reshape(batch * heads, seq, head_dim)

    key = jax.random.PRNGKey(0)
    # float32 flash is held to the bf16 bound: against a "highest"
    # precision reference its dots err by ~1e-2 on v5e
    for dtype in (jnp.bfloat16, jnp.float32):
        q, k, v = (jax.random.normal(kk, (batch, seq, heads, head_dim),
                                     jnp.float32).astype(dtype)
                   for kk in jax.random.split(key, 3))
        out = compiled(ops.flash_attention, q, k, v)
        with jax.default_matmul_precision("highest"):
            want = ref.attention_ref(bh(q), bh(k), bh(v), causal=True)
        _match(f"flash_attention {jnp.dtype(dtype).name} "
               f"({batch * heads}, {seq}, {head_dim})", bh(out), want, 2e-2)

    for dtype, tol in ((jnp.bfloat16, 2e-2), (jnp.float32, 1e-4)):
        x = jax.random.normal(key, (batch * seq, d_model),
                              jnp.float32).astype(dtype)
        scale = jax.random.normal(jax.random.fold_in(key, 1), (d_model,))
        _match(f"rmsnorm {jnp.dtype(dtype).name} ({batch * seq}, {d_model})",
               compiled(ops.rmsnorm, x, scale), ref.rmsnorm_ref(x, scale),
               tol)


def sharded_step(cfg, mesh, batch: int, seq: int,
                 steps: int = STEPS) -> float:
    """Median step time of the in-tree train step sharded over ``mesh``
    (axes "data", "model"): Megatron TP over "model", batch over
    "data". The sharding is built as ``repro.launch.dryrun`` builds it,
    without that module, which forces host devices at import."""
    import math
    import jax
    from repro.data.pipeline import DataConfig, synth_batch
    from repro.models.api import build_model
    from repro.parallel import sharding
    from repro.train import optimizer as optlib
    from repro.train.step import TrainConfig, make_train_step

    opts = _opts()
    api = build_model(cfg, opts)
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(api.init, key)
    pspecs = sharding.param_specs(pshapes, mesh)
    ospecs = optlib.state_specs(pspecs)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch)
    host = synth_batch(dcfg, 0)
    bspecs = sharding.batch_specs(host, mesh, ("data",))
    # the same first loss, unsharded on one device: the check that the
    # sharded program computes the model
    ref_loss = float(jax.jit(lambda k, b: api.loss(api.init(k), b))(
        key, host))
    with jax.set_mesh(mesh):
        params = jax.jit(api.init, out_shardings=pspecs)(key)
        state = jax.jit(optlib.init, out_shardings=ospecs)(params)
        devs = {d for leaf in jax.tree.leaves(params)
                for d in leaf.sharding.device_set}
        split = sum(1 for leaf in jax.tree.leaves(params)
                    if leaf.sharding.shard_shape(leaf.shape) != leaf.shape)
        if len(devs) != mesh.devices.size or not split:
            raise RuntimeError(f"parameters on {len(devs)} devices, "
                               f"{split} leaves sharded")
        _log(f"sharded: parameters on {len(devs)} devices, {split} of "
             f"{len(jax.tree.leaves(params))} leaves split over the mesh")
        step_fn = jax.jit(
            make_train_step(cfg, opts, TrainConfig(), grad_specs=pspecs),
            in_shardings=(pspecs, ospecs, bspecs),
            out_shardings=(pspecs, ospecs, None), donate_argnums=(0, 1))
        times, losses = [], []
        for i in range(steps):
            b = jax.device_put(synth_batch(dcfg, i),
                               sharding.to_shardings(bspecs, mesh))
            t0 = time.perf_counter()
            params, state, metrics = step_fn(params, state, b)
            loss = float(metrics["loss"])
            times.append(time.perf_counter() - t0)
            losses.append(loss)
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    _log(f"sharded: first loss {losses[0]!r}, unsharded {ref_loss!r}")
    if not math.isclose(losses[0], ref_loss, rel_tol=1e-3):
        raise RuntimeError("sharded loss differs from the unsharded one")
    step = statistics.median(times[WARMUP:])
    _log(f"sharded: {cfg.name} dp={mesh.shape['data']} "
         f"mp={mesh.shape['model']} batch {batch} seq {seq}: median step "
         f"{step * 1e3:.3f} ms; losses {[round(x, 4) for x in losses]}")
    _log(f"sharded: step times ms {[round(t * 1e3, 3) for t in times]}")
    return step


def _entries(directory: Path) -> int:
    return len(list(directory.iterdir())) if directory.is_dir() else 0


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp=2 x mp=2 phase")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no {SRC / 'repro'}; run it from a "
                 f"checkout of the repository")
    sys.path.insert(0, str(SRC))

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke.py: JAX finds no TPU (platform "
                 f"{platform!r}); this run measures the chip and never "
                 f"falls back to the CPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but JAX finds "
                 f"{len(devices)} device(s)")
    kind = devices[0].device_kind
    _log(f"device: {platform} {kind} x{len(devices)}")

    from repro.launch.cache import setup_compile_cache
    cache_dir = Path(setup_compile_cache())
    _log(f"compile cache: {cache_dir} ({_entries(cache_dir)} entries at "
         f"start)")

    from repro.configs.base import get_config
    from repro.core import Strategy
    cfg = get_config(ARCH)

    if args.chips == 4:
        import numpy as np
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2),
                    ("data", "model"))
        batch = 2 * BATCH                    # BATCH per data replica
        measured = sharded_step(cfg, mesh, batch, SEQ)
        predicted = predict(cfg, Strategy(mp=2, dp=2), batch, SEQ)
    else:
        measured = reference_step(cfg, BATCH, SEQ)
        _log(f"reference: peak_bytes_in_use {_peak_bytes(devices[0])}")
        predicted = predict(cfg, Strategy(), BATCH, SEQ)
    _log(f"distsim vs measured: predicted {predicted * 1e3:.3f} ms, "
         f"measured {measured * 1e3:.3f} ms, ratio "
         f"{predicted / measured:.4f} ({args.chips} chip(s))")
    if args.chips == 1:
        scan_check(cfg, SEQ)
        kernel_check(BATCH, SEQ, cfg.n_heads, cfg.d_model // cfg.n_heads,
                     cfg.d_model)
    _log(f"compile cache: {_entries(cache_dir)} entries at end")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
