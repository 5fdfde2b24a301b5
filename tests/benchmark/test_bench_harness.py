"""The chip benchmark's harness on the CPU: manifest, files found by
name, work counts, trace reduction, the device check and the result
line. Nothing here measures anything."""
import json
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

import counts
import harness
import tracereduce

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
GPT2 = json.loads((harness.HERE / "configs" / "gpt2_345m.json").read_text())
GPT145 = json.loads((harness.HERE / "configs" / "gpt_145b.json").read_text())
SMALL_TRACE = Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_manifest_names_units_and_files(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    names += [c["name"] for c in manifest["configs"]]
    names += [w["name"] for w in manifest["workloads"]]
    assert len(set(names)) == len(names)
    for name in names:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in manifest["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in manifest["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_every_cell_reports_its_metrics(manifest, group):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        ms = harness.metrics_of(manifest, w["name"], group)
        assert ms, (w["name"], group)
        for m in ms:
            if group == "per_layer":
                moved = e2e[m["moves"]]
                assert ("workloads" not in moved
                        or w["name"] in moved["workloads"]), m["name"]


def test_files_are_found_by_name(manifest):
    for w in manifest["workloads"]:
        traffic = harness.load_traffic(w["traffic"])
        assert traffic["kind"] in ("plan", "search")
        conf = harness.load_config(manifest, w["config"])
        assert conf["name"] == w["config"]
        harness.arch_config(conf)
    for m in manifest["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    with pytest.raises(harness.BenchError):
        harness.load_traffic("no-such-mix")
    with pytest.raises(harness.BenchError):
        harness.load_reader("no_such_metric")


def test_search_questions_record_their_scan():
    for name in ("search-pod", "search-64"):
        for q in harness.load_traffic(name)["questions"]:
            assert q["T"] > 0 and q["K"] > 0
            assert q["probe"]["host_s"] < 15


def test_decoder_flops_at_gpt2_345m():
    t = 1024
    layer = (2 * t * 1024 * 4 * 1024 + 4 * 16 * 1024 * 1024 * 64
             + 4 * t * 1024 * 4096)
    fwd = 24 * layer + 2 * t * 1024 * 50257
    assert counts.decoder_forward_flops(GPT2, 1, 1024) == fwd
    assert counts.decoder_step_flops(GPT2, 1, 1024) == 3 * fwd
    assert counts.decoder_step_flops(GPT2, 1, 1024) == pytest.approx(
        2.48085e12, rel=1e-5)


def test_decoder_flops_at_gpt_145b():
    # 6 x parameters x tokens dominates at d 12288; attention adds 2*s/d
    step = counts.decoder_step_flops(GPT145, 1, 2048)
    params = 80 * 12 * 12288 ** 2 + 51200 * 12288
    assert step == pytest.approx(6 * params * 2048, rel=0.12)
    assert step > 6 * params * 2048


def test_recurrence_bytes():
    assert counts.recurrence_bytes(131072, 147) == 44 * 131072 * 147


def _trace():
    return {"window": [0.0, 10.0],
            "devices": {
                "/device:TPU:0": {
                    "ops": [["a", -1.0, 2.0], ["b", 2.0, 1.0],
                            ["a", 2.5, 1.0], ["c", 8.0, 3.0]],
                    "modules": [["jit_scan_program(1)", 2.0, 1.5],
                                ["jit_step", 8.0, 3.0]]},
                "/device:TPU:1": {"ops": [["a", 0.0, 5.0]],
                                  "modules": []}},
            "host": [["bench.window", 0.0, 10.0],
                     ["bench.answer", 4.0, 3.0]]}


def test_trace_reduction_on_a_small_trace():
    t = _trace()
    assert tracereduce.union([(2, 3), (0, 1), (2.5, 3.5)]) == [
        (0, 1), (2, 3.5)]
    # device 0: [0,1] [2,3.5] [8,10] = 4.5 s; device 1: 5 s
    assert tracereduce.busy_seconds(t) == pytest.approx(4.75)
    assert tracereduce.window_seconds(t) == 10.0
    assert tracereduce.idle_share(t) == pytest.approx(0.525)
    assert tracereduce.module_seconds(t, "scan_program") == 0.75
    assert tracereduce.top_ops(t, 2) == [["a", 8.0], ["c", 3.0]]
    gaps = dict(tracereduce.idle_gaps(t))
    assert gaps == {"bench.window": pytest.approx(1.0),
                    "bench.answer": pytest.approx(4.5)}


def test_trace_reduction_on_a_recorded_trace():
    """A trace recorded on a TPU v5e: a jitted matmul and a lax.scan
    under benchmark spans, reduced by tracereduce.load."""
    t = json.loads(SMALL_TRACE.read_text())
    busy = tracereduce.busy_seconds(t)
    assert 0 < busy < tracereduce.window_seconds(t)
    assert tracereduce.module_seconds(t, "scan_program") > 0
    names = {n for n, _ in tracereduce.idle_gaps(t)}
    assert names <= {"bench.window", "bench.answer", "bench.ref_block",
                     "no span"}


def _devices(platform, kind, n):
    return [SimpleNamespace(platform=platform, device_kind=kind)] * n


@pytest.mark.parametrize("platform,kind,n,chips", [
    ("cpu", "cpu", 1, 1),
    ("tpu", "TPU v9 imaginary", 1, 1),
    ("tpu", "TPU v5 lite", 1, 4),
])
def test_device_check_refuses(monkeypatch, platform, kind, n, chips):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: _devices(platform, kind, n))
    with pytest.raises(harness.BenchError):
        harness.check_devices(chips)


def test_device_check_accepts_v5e(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: _devices("tpu", "TPU v5 lite", 4))
    assert len(harness.check_devices(4)) == 4


@pytest.mark.parametrize("breakdown", [None, {"device_ops": [],
                                              "idle_gaps": []}])
def test_result_line_keys(breakdown):
    checks = [harness.Check("scan_gap", 1e-6, 1e-4)]
    line = json.loads(harness.result_line(
        True, 3, 0, {"setup_s": {"value": 1.0, "unit": "s"}},
        {"platform": "tpu"}, checks, breakdown))
    keys = list(line)
    want = list(harness.RESULT_KEYS)
    if breakdown is not None:
        want.append("breakdown")
    assert keys == want + ["checks"]
    assert line["checks"] == {"scan_gap": {"value": 1e-6, "limit": 1e-4}}
