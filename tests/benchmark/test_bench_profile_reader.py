"""``profile_host_pct.answer`` on small traces: it reads the device
modules of the profiler's GEMM programs (``jit_profile_group``) against
the provider's run seconds, and leaves the metric out where a trace has
no such module, no device or no answer."""
import pytest

import harness

READ = harness.load_reader("profile_host_pct.answer")


def _readings(modules, devices=True, answers=2, run_s=4.0):
    planes = {"/device:TPU:0": {"ops": [["dot", 0.0, 1.0]],
                                "modules": modules}} if devices else {}
    trace = {"window": [0.0, 10.0], "devices": planes,
             "host": [["bench.window", 0.0, 10.0]]}
    r = harness.Readings(cell="gpt2_345m.plan-1chip", trace=trace)
    r.values.update(answers=answers, run_s=run_s)
    return r


def test_share_of_the_run_phase_without_a_profiled_program():
    r = _readings([["jit_profile_group(3)", 1.0, 0.5],
                   ["jit_profile_group(4)", 2.0, 0.5],
                   ["jit_step", 3.0, 2.0]])
    # 1 s of the programs' modules in 4 s of run phase
    assert READ(r) == pytest.approx(75.0)


@pytest.mark.parametrize("modules,devices,answers", [
    ([["jit_run(3)", 1.0, 0.5]], True, 2),       # programs named otherwise
    ([], True, 2),
    ([["jit_profile_group(3)", 1.0, 0.5]], False, 2),
    ([["jit_profile_group(3)", 1.0, 0.5]], True, 0),
], ids=["other-name", "no-module", "no-device", "no-answer"])
def test_left_out_where_there_is_nothing_to_read(modules, devices, answers):
    assert READ(_readings(modules, devices, answers)) is None


def test_left_out_of_an_untraced_run():
    r = harness.Readings(cell="gpt2_345m.plan-1chip")
    r.values.update(answers=2, run_s=4.0)
    assert READ(r) is None


def test_programs_on_one_plane_of_several_are_summed():
    r = _readings([["jit_profile_group(3)", 1.0, 1.0]])
    r.trace["devices"]["/device:TPU:1"] = {"ops": [["dot", 0.0, 1.0]],
                                           "modules": []}
    # the profiler's 1 s lies on chip 0 alone: 1 s of 4 s of run phase
    assert READ(r) == pytest.approx(75.0)
