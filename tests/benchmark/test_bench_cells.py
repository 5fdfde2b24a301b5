"""Each cell kind driven through a whole run on the CPU at a tiny size,
skipping only the look for a chip: sound runs come out correct; the
control (the plain reference, one precision down, in the program's
place) and the faults a one-chip cell can have come out not correct."""
import argparse
import json

import jax
import pytest

import bench_tiny
import run as bench_run


def _run(capsys, workload, trace=0, control=False, seconds=0.5):
    args = argparse.Namespace(workload=workload, seed=2 ** 31 + 17,
                              seconds=seconds, trace=trace,
                              control=control)
    assert bench_run.execute(args, devices=jax.devices()) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.parametrize("workload", ["tiny.plan-1chip", "tiny.search-pod"])
def test_sound_run_is_correct(tiny_bench, capsys, workload):
    out = _run(capsys, workload)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]
    assert len(out["metrics"]) >= 2
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("workload", ["tiny.plan-1chip", "tiny.search-pod"])
def test_traced_run_reports_per_layer(tiny_bench, capsys, workload):
    out = _run(capsys, workload, trace=1)
    assert out["correct"] is True, out["checks"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no TPU plane: device metrics are left out, never 0
    assert "idle_pct.plan" not in out["metrics"]
    assert "idle_pct.search" not in out["metrics"]
    assert out["metrics"], out


@pytest.mark.parametrize("workload,fails", [
    ("tiny.plan-1chip", {"pred_gap", "change_gap"}),
    ("tiny.search-pod", {"scan_gap", "ref_gap", "entry_gap"}),
])
def test_control_is_not_correct(tiny_bench, capsys, workload, fails):
    out = _run(capsys, workload, control=True)
    assert out["correct"] is False
    failed = {k for k, c in out["checks"].items()
              if not c["value"] <= c["limit"]}
    assert fails <= failed, out["checks"]


def _stale_step(monkeypatch):
    bench_tiny.stale_step(monkeypatch.setattr)


def _altered_answer(monkeypatch):
    bench_tiny.altered_answer(monkeypatch.setattr)


def _altered_scan(monkeypatch):
    """One lane's end times altered where the scan produces them."""
    from repro.kernels import megabatch_scan
    scan = megabatch_scan.scan_steps

    def altered(out, dep, delay, dur, n_slots):
        ends, starts = scan(out, dep, delay, dur, n_slots)
        ends = ends.copy()
        ends[out[:, 0]] *= 1 + 1e-3
        return ends, starts
    monkeypatch.setattr(megabatch_scan, "scan_steps", altered)


def _wrong_engine(monkeypatch):
    """The build cache hands out the engine of the layout with the other
    ZeRO-1 setting (the memory mask and the prune bound stay right)."""
    import dataclasses

    from repro.validate.build_cache import BuildCache
    get = BuildCache.engine_for_cfg

    def wrong(self, cfg, strat, *a, **kw):
        strat = dataclasses.replace(strat, zero1=not strat.zero1)
        return get(self, cfg, strat, *a, **kw)
    monkeypatch.setattr(BuildCache, "engine_for_cfg", wrong)


def _slow_lane(monkeypatch):
    """The mega-batch arrays of the first lane with every duration
    stretched: the scan computes them faithfully."""
    from repro.core import megabatch
    compile_one = megabatch.MegaBatch._compile_one

    def slow(self, k, eng, *a, **kw):
        out = compile_one(self, k, eng, *a, **kw)
        if k == 0:
            self._dur[:, 0] *= 1 + 1e-3
        return out
    monkeypatch.setattr(megabatch.MegaBatch, "_compile_one", slow)


@pytest.mark.parametrize("workload,fault,fails", [
    ("tiny.plan-1chip", _stale_step, "change_gap"),
    ("tiny.plan-1chip", _altered_answer, "pred_gap"),
    ("tiny.search-pod", _altered_scan, "scan_gap"),
    ("tiny.search-pod", _wrong_engine, "ref_gap"),
    ("tiny.search-pod", _slow_lane, "ref_gap"),
])
def test_fault_is_not_correct(tiny_bench, capsys, monkeypatch, workload,
                              fault, fails):
    fault(monkeypatch)
    out = _run(capsys, workload)
    assert out["correct"] is False
    c = out["checks"][fails]
    assert not c["value"] <= c["limit"], out["checks"]
