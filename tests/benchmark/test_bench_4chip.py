"""The four-chip plan cell driven through whole runs on four virtual CPU
devices, each run a child process of its own (the flag that makes the
devices may not reach this process): a sound run and a traced one come
out correct; the control (the plain references one precision down in
the program's place) and each fault the cell can have come out not
correct, on the number that is to catch it."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

TINY = Path(__file__).with_name("bench_tiny.py")
SEED = 2 ** 31 + 29

RUNS = {
    "sound": (),
    "traced": ("--trace", "1"),
    "control": ("--control",),
    "whole_batch": ("--fault", "whole_batch"),
    "no_exchange": ("--fault", "no_exchange"),
    "stale_step": ("--fault", "stale_step"),
    "half_step": ("--fault", "half_step"),
    "no_dp_exchange": ("--fault", "no_dp_exchange"),
    "altered_answer": ("--fault", "altered_answer"),
}


@pytest.fixture(scope="module")
def results():
    """Every run started at once, each read when a test asks for it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip())
    procs = {name: subprocess.Popen(
        [sys.executable, str(TINY), "--workload", "tiny.plan-4chip",
         "--seed", str(SEED), *args], env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for name, args in RUNS.items()}
    out = {}
    try:
        for name, p in procs.items():
            stdout, stderr = p.communicate(timeout=600)
            out[name] = (p.returncode, stdout, stderr)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
    return out


def _line(results, name):
    rc, stdout, stderr = results[name]
    assert rc == 0, stderr[-4000:]
    line = json.loads(stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert list(line)[-1] == "checks"
    return line


def _failed(line):
    return {k for k, c in line["checks"].items()
            if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("name", ["sound", "traced"])
def test_sound_run_on_four_devices_is_correct(results, name):
    line = _line(results, name)
    assert line["correct"] is True and not _failed(line), line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    if name == "sound":
        assert {"answer_s", "setup_s"} <= set(line["metrics"])
    else:
        # the CPU has no TPU plane: device metrics are left out, never 0
        assert "ref_collective_pct" not in line["metrics"]
        assert "ref_step_ms" in line["metrics"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name,fails", [
    ("control", {"pred_gap", "change_gap"}),
    ("whole_batch", {"pred_gap"}),
    ("no_exchange", {"pred_gap"}),
    ("stale_step", {"change_gap"}),
    ("half_step", {"loss_gap", "grad_gap", "change_gap"}),
    ("no_dp_exchange", {"loss_gap", "grad_gap", "change_gap"}),
    ("altered_answer", {"pred_gap"}),
])
def test_control_and_faults_on_four_devices_are_not_correct(results, name,
                                                            fails):
    line = _line(results, name)
    assert line["correct"] is False
    assert fails <= _failed(line), line["checks"]
