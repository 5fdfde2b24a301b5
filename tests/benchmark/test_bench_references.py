"""A plan cell's references come from its traffic file: a step
reference and a FLOP count of another name load by that name, with no
edit to the harness; and the composition across chips (``tp_dp``) is
the one-device composition at one rank and DistSim's prediction at
every mp x dp layout, at the published gpt2_345m widths."""
import argparse
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import bench_tiny
import harness
import run as bench_run
from reference import single_device, tp_dp

DATA = Path(__file__).parent / "data"
GPT2 = json.loads((harness.HERE / "configs" / "gpt2_345m.json").read_text())


def test_stand_in_references_load_by_name(tiny_bench, monkeypatch, capsys):
    monkeypatch.syspath_prepend(str(DATA))
    import standin_counts
    from reference import standin_step
    monkeypatch.setattr(standin_step, "CALLS", [])
    tr = bench_tiny.plan_traffic(harness, "plan-1chip")
    tr.update(step_reference="standin_step",
              step_flops="standin_counts:step_flops")
    bench_tiny.install(harness, monkeypatch.setattr,
                       traffic={"plan-1chip": tr})
    args = argparse.Namespace(workload="tiny.plan-1chip", seed=2 ** 31 + 5,
                              seconds=0.5, trace=1, control=False)
    assert bench_run.execute(args, devices=jax.devices()) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert {"make_weights", "token_pool", "run"} <= set(standin_step.CALLS)
    m = out["metrics"]
    flops = (m["ref_mfu"]["value"] / 100 * m["ref_step_ms"]["value"] / 1e3
             * 197e12)
    assert flops == pytest.approx(standin_counts.FLOPS, rel=1e-9)


def _table(conf, batch, seq, mp=1, dp=1, seed=0):
    rng = np.random.default_rng(seed)
    groups = tp_dp.layout_groups(conf, batch, seq, mp, dp).values()
    return {g: float(rng.uniform(1e-4, 1e-2)) for g in groups}


@pytest.mark.parametrize("dtype", [float, np.float32])
def test_tp_dp_at_one_rank_is_single_device(dtype):
    conf = dict(bench_tiny.TINY_CONF)
    table = _table(conf, 1, 32)
    assert (tp_dp.layout_groups(conf, 1, 32)
            == single_device.layout_groups(conf, 1, 32))
    assert (tp_dp.compose(conf, 1, 32, 819e9, table, dtype)["step"]
            == single_device.compose(conf, 1, 32, 819e9, table,
                                     dtype)["step"])
    assert (tp_dp.missing_groups(conf, 1, 32, list(table))
            == single_device.missing_groups(conf, 1, 32, list(table)) == 0)


@pytest.mark.parametrize("mp,dp", [(2, 2), (2, 1), (1, 2), (4, 1), (1, 4)])
def test_tp_dp_is_distsim_at_gpt2_345m(mp, dp):
    """DistSim with the analytical provider at the published widths:
    its compute table in, the same step out; the ring is the cluster's."""
    from repro.core import AnalyticalProvider, DistSim, Strategy, V5E_POD
    cfg = harness.arch_config(GPT2)
    batch, seq = 2 * dp, 1024
    provider = AnalyticalProvider(V5E_POD)
    pred = DistSim(cfg, Strategy(mp=mp, dp=dp), batch, seq,
                   provider).simulate().batch_time
    table = {tuple((g.m, g.n, g.k) for g in e.gemms): t
             for e, t in provider.cache_snapshot().items()
             if e.kind == "compute"}
    ring = {"ring_bytes_per_s": V5E_POD.intra_bw,
            "ring_hop_latency_s": V5E_POD.intra_latency}
    p = tp_dp.compose(GPT2, batch, seq, V5E_POD.chip.hbm_bw, table,
                      mp=mp, dp=dp, **ring)
    assert abs(pred - p["step"]) / p["step"] < 1e-12
    assert tp_dp.missing_groups(GPT2, batch, seq, list(table), mp=mp,
                                dp=dp) == 0
    assert (p["tp"] > 0) == (mp > 1) and (p["dp"] > 0) == (dp > 1)


@pytest.mark.parametrize("mp,dp", [(2, 1), (1, 2)])
def test_single_device_refuses_a_layout_across_chips(mp, dp):
    conf = dict(bench_tiny.TINY_CONF)
    table = _table(conf, 2, 32)
    with pytest.raises(ValueError):
        single_device.compose(conf, 2, 32, 819e9, table, mp=mp, dp=dp)
    with pytest.raises(ValueError):
        single_device.missing_groups(conf, 2, 32, list(table), mp=mp, dp=dp)


def test_plan_4chip_ring_is_its_cluster():
    from repro.core import V5E_POD
    tr = harness.load_traffic("plan-4chip")
    assert tr["prediction_args"] == {
        "ring_bytes_per_s": V5E_POD.intra_bw,
        "ring_hop_latency_s": V5E_POD.intra_latency}
    assert tr["hbm_bytes_per_s"] == V5E_POD.chip.hbm_bw
    assert harness.cluster_spec(tr["cluster"]) is V5E_POD
