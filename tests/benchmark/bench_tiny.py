"""The chip benchmark's cells cut to a size the CPU holds, for its tests.

A dense decoder of the gpt2_345m family at tiny widths stands in for
every configuration; the plan and search mixes keep their kind, their
checks and their limits, at short sequences and few repetitions. The
cells are ``tiny.plan-1chip``, ``tiny.plan-4chip`` and
``tiny.search-pod``. :func:`install` points the harness at them.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/benchmark/bench_tiny.py --workload tiny.plan-4chip \\
        --seed 7 [--control] [--fault no_exchange]

drives one run through ``run.execute`` on the devices JAX finds,
skipping only the look for a chip, with the named fault planted, and
prints the result line. A test process may not hold the flag (the test
suite's ``conftest.py`` refuses it), so the four-device runs are child
processes.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Callable, Dict

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"

#: a dense decoder of the gpt2_345m family at a size the CPU holds
TINY_CONF = dict(name="tiny", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                 qkv_bias=True, mlp_gelu=True, tie_embeddings=True,
                 rope_theta=10000.0)

CELLS = {"tiny.plan-1chip": ("plan-1chip", 1),
         "tiny.plan-4chip": ("plan-4chip", 4),
         "tiny.search-pod": ("search-pod", 1)}


def plan_traffic(harness, name: str) -> Dict[str, Any]:
    tr = harness.load_traffic(name)
    tr.update(seq=32, reps=2, block_steps=2, pool_batches=4)
    return tr


def search_traffic(harness) -> Dict[str, Any]:
    tr = harness.load_traffic("search-pod")
    tr["questions"] = [
        {"chips": 8, "global_batch": 8, "seq": 128,
         "schedules": ["1f1b", "gpipe"], "T": 64, "K": 40},
        {"chips": 4, "global_batch": 8, "seq": 128,
         "schedules": ["1f1b"], "T": 32, "K": 10}]
    return tr


def manifest(harness) -> Dict[str, Any]:
    """``BENCHMARK.json`` with the tiny configuration and cells in place
    of the real ones; metric lists name the tiny cells."""
    out = dict(harness.load_manifest())
    out["configs"] = [{"name": "tiny", "source": "test", "file": "tiny.json",
                       "reduced": [], "why": "test"}]
    out["workloads"] = [{"name": name, "config": "tiny", "traffic": traffic,
                         "chips": chips, "why": "test"}
                        for name, (traffic, chips) in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        out[group] = [dict(m) for m in out[group]]
        for m in out[group]:
            if "workloads" in m:
                m["workloads"] = [w.replace("gpt2_345m.", "tiny.")
                                  .replace("gpt_145b.", "tiny.")
                                  for w in m["workloads"]]
    return out


def install(harness, patch: Callable[[Any, str, Any], None],
            traffic: Dict[str, Dict[str, Any]] = None) -> None:
    """Point ``harness`` at the tiny cells through ``patch`` (e.g.
    ``monkeypatch.setattr``); ``traffic`` replaces a mix by name; the
    compile cache setting is left alone."""
    tiny = manifest(harness)
    mixes = {"plan-1chip": plan_traffic(harness, "plan-1chip"),
             "plan-4chip": plan_traffic(harness, "plan-4chip"),
             "search-pod": search_traffic(harness)}
    mixes.update(traffic or {})
    patch(harness, "load_manifest", lambda *a: tiny)
    patch(harness, "load_config", lambda m, name: dict(TINY_CONF))
    patch(harness, "load_traffic", lambda name: mixes[name])
    patch(harness, "setup_compile_cache", lambda: None)
    patch(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


# ---- faults planted in the program, each where it is produced ----

def stale_step(patch) -> None:
    """A train step that returns its state unchanged."""
    from repro.train import step as steplib
    make = steplib.make_train_step

    def stale(*a, **kw):
        inner = make(*a, **kw)

        def step(params, state, batch):
            _, _, metrics = inner(params, state, batch)
            return params, state, metrics
        return step
    patch(steplib, "make_train_step", stale)


def half_step(patch) -> None:
    """A train step that leaves out the second half of its batch and
    takes the mean over the rest."""
    from repro.train import step as steplib
    make = steplib.make_train_step

    def half(*a, **kw):
        inner = make(*a, **kw)

        def step(params, state, batch):
            return inner(params, state, {k: x[: x.shape[0] // 2]
                                         for k, x in batch.items()})
        return step
    patch(steplib, "make_train_step", half)


def no_dp_exchange(patch) -> None:
    """A sharded train step whose data-parallel ranks exchange nothing:
    each steps on its own rows, with no gradient all-reduce over
    "data" (the tensor-parallel exchange over "model" stays)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.train import step as steplib
    make = steplib.make_train_step

    def local(cfg, opts, tcfg, grad_specs=None):
        if grad_specs is None:
            return make(cfg, opts, tcfg)
        mesh = jax.tree.leaves(grad_specs)[0].mesh
        return jax.shard_map(make(cfg, opts, tcfg), mesh=mesh,
                             in_specs=(P(), P(), P("data")),
                             out_specs=(P(), P(), P()),
                             axis_names={"data"}, check_vma=False)
    patch(steplib, "make_train_step", local)


def altered_answer(patch) -> None:
    """The predicted step altered where it is produced."""
    from repro.core import simulator
    prop = simulator.SimBatch.batch_time
    patch(simulator.SimBatch, "batch_time", property(
        lambda self: prop.fget(self) * (1 + 1e-6)))


def whole_batch(patch) -> None:
    """The prediction profiled at the whole batch on every data-parallel
    rank, not at its share."""
    from repro.core.events import Strategy
    patch(Strategy, "microbatch_size",
          lambda self, global_batch: max(1, global_batch
                                         // self.microbatches))


def no_exchange(patch) -> None:
    """The tensor-parallel all-reduce left out of the prediction."""
    from repro.core import events, hierarchy
    compose = events.layer_composed_events

    def without(*a, **kw):
        ce = compose(*a, **kw)
        return events.ComposedEvent(ce.name, [
            e for e in ce.events if e.kind != "collective"])
    patch(hierarchy, "layer_composed_events", without)


FAULTS = {"stale_step": stale_step, "half_step": half_step,
          "no_dp_exchange": no_dp_exchange,
          "altered_answer": altered_answer, "whole_batch": whole_batch,
          "no_exchange": no_exchange}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, choices=sorted(CELLS))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(BENCH.parents[1] / "src"))
    import jax

    import harness
    import run
    install(harness, setattr)
    if args.fault:
        import repro.core  # noqa: F401  (before repro.search)
        FAULTS[args.fault](setattr)
    return run.execute(args, devices=jax.devices())


if __name__ == "__main__":
    sys.exit(main())
