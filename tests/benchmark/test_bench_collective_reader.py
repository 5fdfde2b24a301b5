"""``ref_collective_pct`` on small traces: per device plane, the union
of the collective operations over the union of all operations that
start inside a reference block, averaged over the planes; nested
operations count once; the metric is left out where there is nothing
to read."""
import pytest

import harness

READ = harness.load_reader("ref_collective_pct")


def _readings(planes, blocks=((0.0, 4.0),)):
    host = [["bench.window", 0.0, 10.0]]
    host += [["bench.ref_block", s, e - s] for s, e in blocks]
    trace = {"window": [0.0, 10.0], "devices": planes, "host": host}
    return harness.Readings(cell="gpt2_345m.plan-4chip", trace=trace,
                            chips=len(planes))


def _plane(ops):
    return {"ops": ops, "modules": []}


def test_share_of_the_reference_blocks():
    r = _readings({
        # a loop holding its body: 2 s busy, 0.5 s of it all-reduces,
        # one of them an async start/done pair; the profiler's program
        # at 5 s lies outside every block
        "/device:TPU:0": _plane([["while.21", 0.0, 2.0],
                                 ["fusion.3", 0.1, 0.4],
                                 ["all-reduce.7", 0.5, 0.25],
                                 ["%all-reduce-start.2", 1.0, 0.05],
                                 ["all-reduce-done.2", 1.05, 0.2],
                                 ["jit_profile_group", 5.0, 1.0]]),
        # 1 s busy, a quarter in an all-gather
        "/device:TPU:1": _plane([["fusion.1", 0.0, 0.75],
                                 ["_all-gather.1", 0.75, 0.25]]),
    })
    assert READ(r) == pytest.approx((0.5 / 2.0 + 0.25 / 1.0) / 2 * 100)


@pytest.mark.parametrize("planes,blocks", [
    ({"/device:TPU:0": _plane([["fusion.1", 0.0, 1.0]])}, ((0.0, 4.0),)),
    ({"/device:TPU:0": _plane([["all-reduce.1", 5.0, 1.0]])},
     ((0.0, 4.0),)),
    ({"/device:TPU:0": _plane([["all-reduce.1", 0.0, 1.0]])}, ()),
    ({}, ((0.0, 4.0),)),
], ids=["no-collective", "outside-blocks", "no-block", "no-device"])
def test_left_out_where_there_is_nothing_to_read(planes, blocks):
    assert READ(_readings(planes, blocks)) is None


def test_left_out_of_an_untraced_run():
    assert READ(harness.Readings(cell="gpt2_345m.plan-4chip")) is None
