"""End-to-end behaviour tests for the full system.

1. DistSim models a strategy space and its ranking is consistent with
   the replay oracle (the paper's core claim, §6/Table 2).
2. A DistSim 1M1P1D prediction from MeasuredProvider profiles exactly
   the graph's unique compute events (the comparison with a measured
   step runs on the chip, in ``chip_smoke.py``).
3. Checkpoint/restart mid-run reproduces the uninterrupted loss curve.
"""
import tempfile

import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.core import (A40_CLUSTER, AnalyticalProvider, DistSim,
                        MeasuredProvider, Strategy, grid_search)
from repro.train.train_loop import LoopConfig, fit


def test_search_ranking_consistent_with_replay():
    cfg = get_config("bert_exlarge")
    provider = AnalyticalProvider(A40_CLUSTER)
    with pytest.warns(DeprecationWarning, match="grid_search"):
        entries = grid_search(cfg, 16, 16, 512, provider=provider)
    feasible = [e for e in entries if e.feasible]
    assert len(feasible) >= 10
    best, worst = feasible[0], feasible[-1]
    # paper Table 2: best/worst spread is large (7.37x there)
    assert worst.batch_time / best.batch_time > 3.0
    # replay agrees on the ordering of best vs worst
    rb = DistSim(cfg, best.strategy, 16, 512, provider).simulate(seeds=0).result()
    rw = DistSim(cfg, worst.strategy, 16, 512, provider).simulate(seeds=0).result()
    assert rb.batch_time < rw.batch_time


def test_measured_provider_predicts_real_step_time():
    """1M1P1D with MeasuredProvider: what a CPU run can show. The
    prediction is finite and positive, and the provider timed exactly
    the graph's unique compute events, each distinct GEMM group once.
    The comparison with a measured step needs the chip and lives in
    ``chip_smoke.py``."""
    import dataclasses
    from repro.core.events import stage_event_set
    cfg = dataclasses.replace(
        smoke_config(get_config("gpt2_345m")), d_model=512, d_ff=2048,
        n_layers=4, vocab=2048, n_heads=8, n_kv_heads=8)
    provider = MeasuredProvider(reps=1)
    sim = DistSim(cfg, Strategy(), global_batch=4, seq=256,
                  provider=provider)
    predicted = sim.simulate().batch_time
    assert np.isfinite(predicted) and predicted > 0

    graph = stage_event_set(sim.positions())
    compute = {e for e in graph if e.kind == "compute"}
    profiled = provider.cache_snapshot()
    assert {e for e in profiled if e.kind == "compute"} == compute
    assert provider.stats.evaluations == len(profiled)
    groups = {tuple((g.m, g.n, g.k) for g in e.gemms) for e in compute}
    assert provider.n_groups == len(groups - {()})
    assert all(profiled[e] > 0 for e in compute if e.gemms)


def test_checkpoint_restart_reproduces_run():
    cfg = smoke_config(get_config("qwen2_1_5b"))
    with tempfile.TemporaryDirectory() as d:
        full = fit(cfg, loop=LoopConfig(steps=12, seq_len=32,
                                        global_batch=2, save_every=100,
                                        ckpt_dir=None), verbose=False)
        part = fit(cfg, loop=LoopConfig(steps=6, seq_len=32,
                                        global_batch=2, save_every=6,
                                        ckpt_dir=d), verbose=False)
        rest = fit(cfg, loop=LoopConfig(steps=12, seq_len=32,
                                        global_batch=2, save_every=6,
                                        ckpt_dir=d), verbose=False)
        assert rest.resumed_from == 6
        np.testing.assert_allclose(rest.losses,
                                   full.losses[6:], rtol=1e-4, atol=1e-4)


def test_profiling_cheaper_than_direct():
    """Table 3: DistSim's profiling cost ≪ direct profiling."""
    cfg = get_config("bert_large")
    provider = AnalyticalProvider(A40_CLUSTER)
    sim = DistSim(cfg, Strategy(mp=2, pp=1, dp=8, microbatches=1),
                  16, 512, provider)
    rep = sim.profiling_report()
    assert rep["relative_scale"] < 0.5
