"""Optimizer, checkpoint, data pipeline, fault tolerance, compression."""
import os
import subprocess
import sys
import tempfile

import pytest

try:
    import hypothesis as hp
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dependency; spot-checks still run
    HAVE_HYPOTHESIS = False

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import DataConfig, DataLoader, synth_batch
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt
from repro.train.compression import (ErrorFeedback, compressed_psum,
                                     dequantize_int8, quantize_int8)
from repro.train.fault_tolerance import (ElasticPlan, HeartbeatMonitor,
                                         replan_mesh, run_with_recovery)


# ---------------------------- optimizer ----------------------------

def test_adamw_minimizes_quadratic():
    cfg = opt.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                          total_steps=200)
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt.update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_grad_clipping():
    cfg = opt.AdamWConfig(lr=0.0, grad_clip=1.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    _, _, metrics = opt.update(cfg, params, {"w": jnp.full(3, 100.0)},
                               state)
    assert float(metrics["grad_norm"]) > 100


def test_lr_schedule_shape():
    cfg = opt.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_frac=0.1)
    lrs = [float(opt.lr_schedule(cfg, jnp.array(s))) for s in range(100)]
    assert lrs[0] < lrs[9]                      # warmup rising
    assert max(lrs) <= 1.0 + 1e-6
    assert lrs[-1] >= 0.1 * 0.99                # floor respected
    assert lrs[50] > lrs[99]                    # decaying


# ---------------------------- checkpoint ----------------------------

def _tree(key):
    return {"a": jax.random.normal(key, (4, 8)),
            "nested": {"b": jnp.arange(6, dtype=jnp.int32)}}


def test_checkpoint_roundtrip():
    tree = _tree(jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 7, tree)
        restored, step = ckpt.restore(d, tree)
        assert step == 7
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest():
    tree = _tree(jax.random.PRNGKey(1))
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4, 5):
            ckpt.save(d, s, tree, keep=2)
        assert ckpt.all_steps(d) == [4, 5]
        assert ckpt.latest_step(d) == 5


def test_checkpoint_keep_zero_retains_nothing():
    """Regression: ``steps[:-0]`` is the empty slice, so keep=0 used to
    silently retain EVERY checkpoint — the opposite of its meaning."""
    tree = _tree(jax.random.PRNGKey(3))
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3):
            ckpt.save(d, s, tree, keep=0)
        assert ckpt.all_steps(d) == []
        with pytest.raises(ValueError):
            ckpt.save(d, 4, tree, keep=-1)


def test_checkpoint_shape_mismatch_fails_loudly():
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, {"a": jnp.zeros((2, 2))})
        with pytest.raises(ValueError):
            ckpt.restore(d, {"a": jnp.zeros((3, 3))})


def test_checkpoint_dtype_mismatch_fails_loudly():
    """Regression: restore used to silently astype, hiding config drift
    (e.g. fp32 optimizer moments quietly rounded into a bf16 slot)."""
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, {"a": jnp.zeros((2, 2), dtype=jnp.float32)})
        with pytest.raises(ValueError, match="dtype mismatch"):
            ckpt.restore(d, {"a": jnp.zeros((2, 2), dtype=jnp.int32)})


def test_checkpoint_atomicity_tmp_never_latest():
    """A stale .tmp dir (simulated crash) must be invisible to restore."""
    tree = _tree(jax.random.PRNGKey(2))
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, tree)
        os.makedirs(os.path.join(d, "step_00000002.tmp"))
        assert ckpt.latest_step(d) == 1


def test_checkpoint_manifest_helpers_are_numpy_only():
    """The manifest helpers feed engine-side restore sizing
    (``repro.core.perturb``); importing the module must not drag jax
    in — checked in a fresh interpreter so this process's imports
    can't mask it."""
    m = ckpt.synthetic_manifest(4, {"pos0/params": 1000.0,
                                    "pos1/params": 24.0})
    assert m["step"] == 4
    assert [e["shape"] for e in m["leaves"]] == [[250], [6]]
    assert ckpt.manifest_nbytes(m) == 250 * 4 + 6 * 4
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    code = ("import sys\n"
            "import repro.train.checkpoint as c\n"
            "m = c.synthetic_manifest(0, {'pos0/params': 64.0})\n"
            "assert c.manifest_nbytes(m) == 64.0\n"
            "assert 'jax' not in sys.modules, 'checkpoint imported jax'\n")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         capture_output=True,
                         env={**os.environ, "PYTHONPATH": src})
    assert out.returncode == 0, out.stderr


# ---------------------------- data ----------------------------

def test_data_deterministic_and_resumable():
    cfg = DataConfig(seed=5, vocab=100, seq_len=16, global_batch=4)
    b1 = synth_batch(cfg, 3)
    b2 = synth_batch(cfg, 3)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # loader starting at step 3 produces the same batch
    loader = DataLoader(cfg, start_step=3)
    step, batch = next(loader)
    loader.close()
    assert step == 3
    np.testing.assert_array_equal(batch["tokens"], b1["tokens"])


def test_data_shards_disjoint():
    c0 = DataConfig(seed=1, vocab=50, seq_len=8, global_batch=8,
                    shard_index=0, shard_count=2)
    c1 = DataConfig(seed=1, vocab=50, seq_len=8, global_batch=8,
                    shard_index=1, shard_count=2)
    b0, b1 = synth_batch(c0, 0), synth_batch(c1, 0)
    assert b0["tokens"].shape == (4, 8)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_labels_shifted():
    cfg = DataConfig(seed=2, vocab=100, seq_len=16, global_batch=2)
    b = synth_batch(cfg, 0)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["labels"][:, -1] == -1).all()


# ---------------------------- fault tolerance ----------------------------

def test_straggler_detection():
    mon = HeartbeatMonitor(4, straggler_factor=1.5)
    for step in range(8):
        for w in range(4):
            mon.heartbeat(w, 1.0 if w != 2 else 2.5, now=float(step))
    assert mon.stragglers() == [2]


def test_dead_worker_detection_is_pure_query():
    """Regression: ``dead()`` used to flip ``alive`` as a side effect,
    so a second poller (or a repeated poll) saw an empty dead set and
    never triggered recovery. Detection and transition are now split."""
    mon = HeartbeatMonitor(3, dead_after_s=10)
    for w in range(3):
        mon.heartbeat(w, 1.0, now=0.0)
    mon.heartbeat(0, 1.0, now=20.0)
    mon.heartbeat(1, 1.0, now=20.0)
    assert mon.dead(now=25.0) == [2]
    assert mon.dead(now=25.0) == [2]            # still visible
    assert mon.alive_count() == 3               # no mutation yet
    assert mon.mark_dead(now=25.0) == [2]
    assert mon.alive_count() == 2
    assert mon.dead(now=25.0) == []             # transitioned
    assert mon.mark_dead([2]) == []             # already dead: no-op


def test_dead_worker_rejoins_on_heartbeat():
    """Elastic rescheduling brings a node back: its heartbeat re-joins
    it and drops the stale step-time history (so the revived worker is
    not instantly flagged a straggler on pre-death data)."""
    mon = HeartbeatMonitor(2, dead_after_s=10)
    mon.heartbeat(0, 1.0, now=0.0)
    mon.heartbeat(1, 9.0, now=0.0)
    mon.mark_dead(now=20.0)
    assert mon.alive_count() == 0
    mon.heartbeat(1, 1.0, now=21.0)
    assert mon.alive_count() == 1
    assert mon.workers[1].step_times == [1.0]   # stale history dropped


def test_replan_mesh_boundaries():
    with pytest.raises(ValueError):
        replan_mesh(0, 4)
    with pytest.raises(ValueError):
        replan_mesh(-3, 1)
    assert replan_mesh(1, 1) == ElasticPlan(data=1, model=1)
    # survivors < model group: mp halves until it fits
    assert replan_mesh(3, 8) == ElasticPlan(data=1, model=2)
    assert replan_mesh(1, 8) == ElasticPlan(data=1, model=1)
    # model group kept intact when it fits; data is power-of-two
    assert replan_mesh(7, 4) == ElasticPlan(data=1, model=4)
    assert replan_mesh(8, 4) == ElasticPlan(data=2, model=4)
    assert replan_mesh(513, 4) == ElasticPlan(data=128, model=4)


if HAVE_HYPOTHESIS:
    @hp.given(survivors=st.integers(1, 512),
              mp=st.sampled_from([1, 2, 4, 8, 16]))
    @hp.settings(max_examples=50, deadline=None)
    def test_replan_mesh_feasible(survivors, mp):
        plan = replan_mesh(survivors, mp)
        assert plan.devices <= survivors
        assert plan.devices >= max(1, survivors // 4)   # wastes <75%
        assert plan.model <= mp


@pytest.mark.parametrize("survivors,mp", [
    (1, 1), (3, 2), (5, 4), (9, 8), (31, 16), (512, 16),
])
def test_replan_mesh_spot_checks(survivors, mp):
    plan = replan_mesh(survivors, mp)
    assert plan.devices <= survivors
    assert plan.devices >= max(1, survivors // 4)
    assert plan.model <= mp


def test_run_with_recovery_loses_bounded_steps():
    saved = {"step": 0}
    done = []

    def step_fn(s):
        done.append(s)

    def save_fn(s):
        saved["step"] = s

    def restore_fn():
        return saved["step"]

    steps, recoveries = run_with_recovery(
        50, step_fn, save_fn, restore_fn, save_every=10, failure_at=25)
    assert steps == 50
    assert recoveries == 1
    # lost work bounded by save_every: checkpoint at 20 ⇒ steps 20-24
    # re-execute once, 19 and earlier never re-run
    assert done.count(19) == 1 and done.count(20) == 2


def test_run_with_recovery_budget_stops_persistent_failure():
    """Regression: a step that deterministically raises used to loop
    forever (restore rewinds to the same step, which fails again).
    The recovery budget re-raises with the original failure chained."""
    attempts = []

    def step_fn(s):
        if s == 3:
            attempts.append(s)
            raise RuntimeError("bad node")

    with pytest.raises(RuntimeError, match="recovery budget") as ei:
        run_with_recovery(10, step_fn, lambda s: None, lambda: 0,
                          save_every=100, max_recoveries=4)
    assert len(attempts) == 5                   # initial try + 4 retries
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert "bad node" in str(ei.value.__cause__)


# ---------------------------- compression ----------------------------

def _quantize_error_bound(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (256,)) * 3.0
    q, scale = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, scale) - x)
    assert float(err.max()) <= float(scale) / 2 + 1e-6


if HAVE_HYPOTHESIS:
    @hp.given(seed=st.integers(0, 10))
    @hp.settings(max_examples=10, deadline=None)
    def test_quantize_error_bound(seed):
        _quantize_error_bound(seed)


@pytest.mark.parametrize("seed", [0, 3, 7])
def test_quantize_error_bound_spot_checks(seed):
    _quantize_error_bound(seed)


def test_error_feedback_unbiased_over_time():
    """Accumulated sent updates converge to accumulated true gradient."""
    key = jax.random.PRNGKey(0)
    g_true = jax.random.normal(key, (64,))
    resid = ErrorFeedback.init({"g": g_true})
    total_sent = jnp.zeros(64)
    for i in range(50):
        sent, resid = ErrorFeedback.apply({"g": g_true}, resid)
        total_sent = total_sent + sent["g"]
    np.testing.assert_allclose(np.asarray(total_sent / 50),
                               np.asarray(g_true), atol=0.02)


def test_compressed_psum_single_axis():
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P
    g = {"w": jnp.linspace(-1, 1, 32)}
    f = jax.shard_map(lambda t: compressed_psum(t, "data"), mesh=mesh,
                  in_specs=(P(),), out_specs=P())
    out = f(g)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=0.02)
