"""Sweep-scale subsystem: shared build cache, parallel executor, and
the engine/provider caching fixes it exposed.

Acceptance pins (ISSUE 5):
* cached-build sweeps are bit-identical to uncached ones (same
  ``dump()`` JSON, goldens untouched);
* a parallel ``jobs=4`` sweep reproduces the serial report exactly and
  its merged ``ProviderStats.evaluations`` equals the serial sweep's
  unique-event count;
* ``DistSim.engine(positions)`` keys on structural content, not list
  identity;
* ``Provider.clear_cache()`` invalidates engines holding baked-in
  means;
* the >8-way ring extrapolation shares its constants with
  ``costmodel.collective_time`` and stays continuous at the 8->9
  boundary;
* a schedule's task structure is built once per cache and shared by
  every engine of its key, with bit-identical compiled arrays.
"""
import copy

import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import (A40_CLUSTER, AnalyticalProvider, DistSim,
                        EngineBuild, Event, EventFlowEngine, Strategy,
                        collective_time, ring_hops, ring_volume_factor)
from repro.core.engine import TaskStructure
from repro.core.events import ComposedEvent
from repro.core.megabatch import MegaBatch
from repro.core.modelgraph import GEMM
from repro.core.hierarchy import build_positions
from repro.core.scenario import TRAIN, Decode, Prefill
from repro.validate import (BuildCache, ValidationCell, full_matrix,
                            run_sweep, smoke_matrix)
from repro.validate.report import dump, dumps, load
from repro.validate.sweep import _cell

SEEDS = (0, 1)
MATRIX = smoke_matrix()
SCHEDULES = ("gpipe", "1f1b", "interleaved", "pipedream")


def _family(arch="gpt2_345m", mp=1, pp=2, dp=2, m=4, gb=16, seq=128):
    """One (model, strategy) pair under all four schedules — the
    recurrence the build cache dedups."""
    return [_cell(arch, mp, pp, dp, m, s,
                  vpp=2 if s == "interleaved" else 1, gb=gb, seq=seq)
            for s in SCHEDULES]


# --------------------------------------------------------------------------
# build cache: bit-identity + reuse accounting
# --------------------------------------------------------------------------

def test_cached_sweep_bit_identical_to_uncached():
    a = run_sweep(MATRIX, cluster=A40_CLUSTER, seeds=SEEDS, cache=False)
    b = run_sweep(MATRIX, cluster=A40_CLUSTER, seeds=SEEDS, cache=True)
    assert dumps(a) == dumps(b)
    assert load(dump(a)) == b             # round-trip across the modes


def test_shared_build_engines_bit_identical_per_schedule():
    """The cache's core claim: a schedule only reorders tasks — every
    schedule's engine built from ONE shared (schedule-independent)
    EngineBuild reproduces the from-scratch engine exactly."""
    provider = AnalyticalProvider(A40_CLUSTER)
    cfg = get_config("gpt2_345m")
    for schedule in SCHEDULES:
        vpp = 2 if schedule == "interleaved" else 1
        strat = Strategy(mp=1, pp=2, dp=2, microbatches=4,
                         schedule=schedule, vpp=vpp)
        pos = build_positions(cfg, strat, 2, 128, provider.cluster)
        shared = EngineBuild(pos, strat, provider, with_dp_sync=None)
        cached = EventFlowEngine(pos, strat, provider, build=shared)
        fresh = EventFlowEngine(pos, strat, provider)
        assert cached.run().batch_time == fresh.run().batch_time
        ca = cached.run_batched(SEEDS, jitter_sigma=0.025)
        fr = fresh.run_batched(SEEDS, jitter_sigma=0.025)
        assert list(ca.batch_times) == list(fr.batch_times), schedule


def test_build_cache_shares_across_schedules():
    provider = AnalyticalProvider(A40_CLUSTER)
    cache = BuildCache(provider)
    for cell in _family():
        cache.engine_for(cell)
    # 4 schedules -> 2 positions/builds (vpp=1 shared by three schedules,
    # vpp=2 for interleaved), one engine per schedule
    assert cache.stats.engine_misses == 4
    assert cache.stats.build_misses == 2
    assert cache.stats.build_hits == 2
    assert cache.stats.positions_misses == 2


def test_warm_cache_serves_engines_and_stays_identical():
    provider = AnalyticalProvider(A40_CLUSTER)
    cache = BuildCache(provider)
    a = run_sweep(MATRIX, provider=provider, seeds=SEEDS, cache=cache)
    misses = cache.stats.engine_misses
    b = run_sweep(MATRIX, provider=provider, seeds=SEEDS, cache=cache)
    assert dumps(a) == dumps(b)
    assert cache.stats.engine_misses == misses        # no rebuilds
    assert cache.stats.engine_hits >= len(MATRIX)


def test_build_cache_rejects_foreign_provider():
    cache = BuildCache(AnalyticalProvider(A40_CLUSTER))
    with pytest.raises(ValueError, match="different provider"):
        run_sweep(MATRIX[:1], provider=AnalyticalProvider(A40_CLUSTER),
                  seeds=(0,), cache=cache)


def test_run_batched_memoized_per_seed_set():
    cell = MATRIX[0]
    provider = AnalyticalProvider(A40_CLUSTER)
    cache = BuildCache(provider)
    eng = cache.engine_for(cell)
    assert eng.run_batched(SEEDS, jitter_sigma=0.025) \
        is eng.run_batched(SEEDS, jitter_sigma=0.025)
    # different seeds / sigmas are distinct entries, not collisions
    other = eng.run_batched((2,), jitter_sigma=0.025)
    assert other is not eng.run_batched(SEEDS, jitter_sigma=0.025)


def test_batch_memo_is_bounded():
    """Long-lived cached engines must not pin one TimelineBatch per
    seed set ever requested."""
    provider = AnalyticalProvider(A40_CLUSTER)
    cache = BuildCache(provider)
    eng = cache.engine_for(MATRIX[0])
    for s in range(3 * eng._BATCH_MEMO_MAX):
        eng.run_batched((s,), jitter_sigma=0.025)
    assert len(eng._batch_memo) <= eng._BATCH_MEMO_MAX


def test_engine_rejects_mismatched_build():
    """A build precomputed for other stages must raise, not silently
    simulate the wrong model."""
    provider = AnalyticalProvider(A40_CLUSTER)
    cfg = get_config("gpt2_345m")
    strat = Strategy(mp=1, pp=2, dp=2, microbatches=4)
    pos_a = build_positions(cfg, strat, 2, 128, provider.cluster)
    pos_b = build_positions(cfg, strat, 2, 256, provider.cluster)
    build_b = EngineBuild(pos_b, strat, provider)
    with pytest.raises(ValueError, match="different stages"):
        EventFlowEngine(pos_a, strat, provider, build=build_b)


def test_full_matrix_extended_with_predict_scale_cells():
    cells = full_matrix()
    big = {c.arch for c in cells if c.global_batch == 64}
    assert big == {"gpt_145b", "dbrx_132b", "jamba_v0_1_52b",
                   "qwen2_vl_72b"}
    for c in cells:
        assert c.global_batch % (c.strategy.dp
                                 * c.strategy.microbatches) == 0


# --------------------------------------------------------------------------
# build cache: shared task structure
# --------------------------------------------------------------------------

CFG = get_config("gpt2_345m")
_LISTS = ("task_isf", "task_pos", "task_micro", "task_name",
          "task_p2p_name")


def _shares(a, b) -> bool:
    """Every per-device inner sequence of ``a`` is ``b``'s object."""
    return all(x is y for name in _LISTS
               for x, y in zip(getattr(a, name), getattr(b, name)))


def _pair(cache, **kw):
    """Engines of one (pp=2, m=4) structure: ZeRO-1 off and on, and an
    (mp, dp) pair of the same pp and m."""
    base = dict(mp=1, pp=2, dp=2, microbatches=4)
    return [cache.engine_for_cfg(CFG, Strategy(**{**base, **over}), 16,
                                 128, **kw)
            for over in ({}, {"zero1": True}, {"mp": 2, "dp": 1})]


def _direct(strat, seq=128, gb=16):
    provider = AnalyticalProvider(A40_CLUSTER)
    micro = strat.microbatch_size(gb)
    return build_positions(CFG, strat, micro, seq, A40_CLUSTER), strat, \
        provider


def test_structure_shared_across_zero1_and_mp_dp():
    cache = BuildCache(AnalyticalProvider(A40_CLUSTER))
    a, z, md = _pair(cache)
    for other in (z, md):
        assert _shares(a, other)
        assert other.structure is a.structure
        assert other.topo_order() is a.topo_order()
        # the outer per-device lists stay each engine's own
        assert all(getattr(a, n) is not getattr(other, n) for n in _LISTS)
    # a different m, schedule or vpp, or a serving scenario, does not
    for strat, scen in (
            (Strategy(mp=1, pp=2, dp=2, microbatches=8), None),
            (Strategy(mp=1, pp=2, dp=2, microbatches=4,
                      schedule="gpipe"), None),
            (Strategy(mp=1, pp=2, dp=2, microbatches=4,
                      schedule="interleaved", vpp=2), None),
            (Strategy(mp=1, pp=2, dp=2, microbatches=4), Prefill()),
            (Strategy(mp=1, pp=2, dp=2, microbatches=4), Decode(steps=4))):
        kw = {} if scen is None else {"scenario": scen}
        e = cache.engine_for_cfg(CFG, strat, 16, 128, **kw)
        assert e.structure is not a.structure
        assert not any(x is y for x, y in zip(e.task_isf, a.task_isf))
    # prefill and decode of one (pp, m) differ only in the decode flag
    pre = cache.engine_for_cfg(CFG, Strategy(mp=1, pp=2, dp=1,
                                             microbatches=4), 8, 128,
                               scenario=Prefill())
    dec = cache.engine_for_cfg(CFG, Strategy(mp=1, pp=2, dp=1,
                                             microbatches=4), 8, 128,
                               scenario=Decode(steps=4))
    assert pre.structure.key[:5] == dec.structure.key[:5]
    assert pre.structure is not dec.structure


def test_structure_counters_and_fresh_cache():
    cache = BuildCache(AnalyticalProvider(A40_CLUSTER))
    snap = cache.snapshot()
    assert snap["structure_entries"] == 0
    assert snap["structure_hits"] == snap["structure_misses"] == 0
    _pair(cache)
    assert (cache.stats.structure_misses, cache.stats.structure_hits) \
        == (1, 2)
    _pair(cache)                          # engine hits: no structure lookup
    assert (cache.stats.structure_misses, cache.stats.structure_hits) \
        == (1, 2)
    cache.engine_for_cfg(CFG, Strategy(mp=1, pp=2, dp=2, microbatches=8),
                         16, 128)
    assert (cache.stats.structure_misses, cache.stats.structure_hits) \
        == (2, 2)
    assert cache.snapshot()["structure_entries"] == 2
    assert cache.stats.hits == (cache.stats.positions_hits
                                + cache.stats.build_hits
                                + cache.stats.engine_hits + 2)
    # a fresh cache starts with no structure of its own
    fresh = BuildCache(cache.provider)
    e = _pair(fresh)[0]
    assert fresh.stats.structure_misses == 1
    assert e.structure is not _pair(cache)[0].structure


def test_structure_counts_on_small_search_grid():
    """A search session builds each distinct structure once: the 4-chip
    grid below asks 64 engines of 18 (schedule, pp, vpp, m) keys."""
    from repro.search.engine import SearchEngine
    from repro.search.space import enumerate_candidates
    args = (4, 8, None, ("1f1b", "gpipe"), (False, True))
    keys = {(s.schedule, s.pp, s.vpp, s.microbatches)
            for s in (c.strategy for c in enumerate_candidates(*args))}
    se = SearchEngine(CFG, A40_CLUSTER, megabatch=True,
                      megabatch_backend="numpy")
    se.search(4, 8, 128, schedules=args[3], zero1_options=args[4])
    st = se.cache.build_cache(A40_CLUSTER).stats
    assert st.engine_misses == st.structure_misses + st.structure_hits
    assert st.structure_misses == len(keys)
    assert (st.structure_misses, st.structure_hits) == (18, 46)


def test_structure_inner_sequences_are_tuples():
    for eng in _pair(BuildCache(AnalyticalProvider(A40_CLUSTER))) + [
            EventFlowEngine(*_direct(Strategy(mp=1, pp=2, dp=2,
                                              microbatches=4)))]:
        for name in _LISTS:
            outer = getattr(eng, name)
            assert isinstance(outer, list)
            assert all(isinstance(seq, tuple) for seq in outer)
        assert isinstance(eng.topo_order(), tuple)


_MB_STRATS = [
    Strategy(mp=1, pp=2, dp=2, microbatches=4),
    Strategy(mp=1, pp=2, dp=2, microbatches=4, zero1=True),
    Strategy(mp=2, pp=2, dp=1, microbatches=4),
    Strategy(mp=1, pp=4, dp=1, microbatches=4, schedule="gpipe"),
    Strategy(mp=1, pp=4, dp=1, microbatches=4, schedule="gpipe",
             zero1=True),
    Strategy(mp=1, pp=2, dp=2, microbatches=4, schedule="interleaved",
             vpp=2),
    Strategy(mp=1, pp=2, dp=1, microbatches=8, schedule="pipedream"),
]


def test_megabatch_arrays_identical_with_shared_structure():
    cache = BuildCache(AnalyticalProvider(A40_CLUSTER))
    shared = [cache.engine_for_cfg(CFG, s, 16, 128) for s in _MB_STRATS]
    assert cache.stats.structure_hits >= 2
    own = [EventFlowEngine(*_direct(s)) for s in _MB_STRATS]
    a, b = MegaBatch(shared), MegaBatch(own)
    for name in ("_out", "_dep0", "_dep1", "_dep2", "_del1", "_del2",
                 "_dur", "_seg", "_send"):
        assert np.array_equal(getattr(a, name), getattr(b, name)), name
    assert list(a.predict_times("numpy")) == list(b.predict_times("numpy"))
    for x, y in zip(shared, own):
        assert x.run().batch_time == y.run().batch_time
        assert list(x.run_batched(SEEDS, jitter_sigma=0.025).batch_times) \
            == list(y.run_batched(SEEDS, jitter_sigma=0.025).batch_times)
        assert [(t.name, t.start, t.end) for t in x.run().activities] \
            == [(t.name, t.start, t.end) for t in y.run().activities]


def test_planted_fault_stays_in_its_engine():
    """Replacing one sharing engine's device entries deadlocks that
    engine alone: its sibling keeps the structure's order and time."""
    cache = BuildCache(AnalyticalProvider(A40_CLUSTER))
    eng, sib, _ = _pair(cache)
    order, bt = sib.topo_order(), sib.run().batch_time
    assert eng.topo_order() is order
    for name in _LISTS:
        lst = getattr(eng, name)
        lst[1] = lst[1][::-1]
    eng._topo = None
    assert not eng.structure.holds(eng)
    with pytest.raises(RuntimeError, match="deadlock"):
        eng.run()
    with pytest.raises(RuntimeError, match="deadlock"):
        eng.topo_order()
    assert sib.structure.holds(sib)
    assert sib.topo_order() is order
    assert eng.structure.topo_order() is order
    assert sib.run().batch_time == bt


def test_engine_rejects_mismatched_structure():
    pos, strat, provider = _direct(Strategy(mp=1, pp=2, dp=2,
                                            microbatches=4))
    other = TaskStructure(
        Strategy(mp=1, pp=2, dp=2, microbatches=8), TRAIN, len(pos))
    with pytest.raises(ValueError, match="task structure"):
        EventFlowEngine(pos, strat, provider, structure=other)


# --------------------------------------------------------------------------
# parallel executor: report + stats merge
# --------------------------------------------------------------------------

def test_parallel_jobs4_report_equals_serial():
    serial = run_sweep(MATRIX, cluster=A40_CLUSTER, seeds=SEEDS,
                       cache=False)
    par = run_sweep(MATRIX, cluster=A40_CLUSTER, seeds=SEEDS, jobs=4)
    assert dumps(serial) == dumps(par)


def test_parallel_provider_merge_matches_serial_unique_events():
    """Merged shard caches must count each unique event ONCE — the
    paper's Table 3 accounting — no matter how many workers profiled
    it."""
    sp = AnalyticalProvider(A40_CLUSTER)
    run_sweep(MATRIX, provider=sp, seeds=SEEDS)
    pp_ = AnalyticalProvider(A40_CLUSTER)
    run_sweep(MATRIX, provider=pp_, seeds=SEEDS, jobs=4)
    serial_unique = len(sp.cache_snapshot())
    assert sp.stats.evaluations == serial_unique
    assert pp_.stats.evaluations == serial_unique
    assert set(pp_.cache_snapshot()) == set(sp.cache_snapshot())


def test_parallel_accumulates_shard_cache_stats():
    provider = AnalyticalProvider(A40_CLUSTER)
    cache = BuildCache(provider)
    run_sweep(MATRIX, provider=provider, seeds=(0,), cache=cache, jobs=2)
    assert cache.stats.engine_misses >= len(MATRIX) // 2


def test_parallel_refuses_provider_that_measures_on_device():
    """A chip belongs to one process: worker processes must not each
    profile on it, so jobs > 1 with a MeasuredProvider raises before
    any worker starts (and before any profiling)."""
    from repro.core import MeasuredProvider
    from repro.validate.executor import run_parallel
    provider = MeasuredProvider(A40_CLUSTER)
    with pytest.raises(ValueError, match="device"):
        run_sweep(MATRIX[:2], provider=provider, seeds=(0,), jobs=2)
    with pytest.raises(ValueError, match="device"):
        run_parallel(MATRIX[:2], provider, seeds=(0,), jobs=2)
    assert provider.stats.evaluations == 0


# --------------------------------------------------------------------------
# satellite: DistSim.engine(positions) structural identity
# --------------------------------------------------------------------------

def _sim(provider=None):
    return DistSim(get_config("gpt2_345m"),
                   Strategy(mp=1, pp=2, dp=2, microbatches=4),
                   16, 128, provider or AnalyticalProvider(A40_CLUSTER))


def test_engine_reused_for_equal_content_positions():
    sim = _sim()
    pos = sim.positions()
    eng = sim.engine(pos)
    # a fresh, equal-content list must NOT rebuild
    assert sim.engine(copy.deepcopy(pos)) is eng
    assert sim.engine(sim.positions()) is eng


def test_engine_rebuilt_for_mutated_positions():
    """Regression: identity keying returned a stale engine when the
    caller mutated the positions list in place."""
    sim = _sim()
    pos = sim.positions()
    bt = sim.simulate(positions=pos).batch_time
    extra = Event(kind="compute", name="injected",
                  gemms=(GEMM(4096, 4096, 4096),))
    pos[0].fwd = ComposedEvent(pos[0].fwd.name,
                               pos[0].fwd.events + [extra])
    bt_mut = sim.simulate(positions=pos).batch_time
    assert bt_mut != bt                   # not the stale engine
    assert bt_mut > bt                    # stage-0 fwd grew


# --------------------------------------------------------------------------
# satellite: Provider.clear_cache() invalidates engines
# --------------------------------------------------------------------------

class _ScaledProvider(AnalyticalProvider):
    """Times change when ``scale`` changes — only a cache clear may
    expose the new values."""

    def __init__(self, cluster):
        super().__init__(cluster)
        self.scale = 1.0

    def _time(self, e: Event) -> float:
        return self.scale * super()._time(e)


def test_clear_cache_invalidates_default_engine():
    provider = _ScaledProvider(A40_CLUSTER)
    sim = _sim(provider)
    bt = sim.simulate().batch_time
    provider.scale = 2.0
    # without a clear, profiled times (and the engine) legitimately stay
    assert sim.simulate().batch_time == bt
    provider.clear_cache()
    # regression: the engine used to keep its baked-in (stale) means.
    # Exact 2x is NOT expected — optimizer time bypasses the provider.
    bt2 = sim.simulate().batch_time
    assert bt2 != bt
    assert bt < bt2 < 2.0 * bt + 1e-12


def test_clear_cache_invalidates_positions_engine():
    provider = _ScaledProvider(A40_CLUSTER)
    sim = _sim(provider)
    pos = sim.positions()
    bt = sim.simulate(positions=pos).batch_time
    provider.scale = 3.0
    provider.clear_cache()
    bt2 = sim.simulate(positions=pos).batch_time
    assert bt2 != bt
    assert bt < bt2 < 3.0 * bt + 1e-12


def test_clear_cache_invalidates_build_cache():
    provider = _ScaledProvider(A40_CLUSTER)
    cache = BuildCache(provider)
    cell = ValidationCell("gpt2_345m",
                          Strategy(mp=1, pp=2, dp=2, microbatches=4),
                          global_batch=16, seq=128)
    e1 = cache.engine_for(cell)
    provider.scale = 2.0
    provider.clear_cache()
    e2 = cache.engine_for(cell)
    assert e2 is not e1
    assert cache.stats.invalidations == 1
    assert e2.fwd_base[0] == pytest.approx(2.0 * e1.fwd_base[0])


# --------------------------------------------------------------------------
# satellite: profiling_report shares DistSim.microbatch()
# --------------------------------------------------------------------------

def test_profiling_report_uses_microbatch_floor():
    """gb=0 is the degenerate case where the inline recomputation
    (gb // (dp*m) == 0) used to diverge from microbatch()'s max(1, ...)
    floor; both paths must see the same per-microbatch GEMM dims."""
    cfg = get_config("gpt2_345m")
    strat = Strategy(mp=1, pp=2, dp=2, microbatches=4)
    provider = AnalyticalProvider(A40_CLUSTER)
    floor = DistSim(cfg, strat, 0, 128, provider)
    ref = DistSim(cfg, strat, 8, 128, provider)    # micro == 1 exactly
    assert floor.microbatch() == ref.microbatch() == 1
    a, b = floor.profiling_report(), ref.profiling_report()
    assert a["unique_events"] == b["unique_events"]
    assert a["profile_time_s"] == pytest.approx(b["profile_time_s"])


# --------------------------------------------------------------------------
# satellite: ring extrapolation helpers + continuity
# --------------------------------------------------------------------------

RING_OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all")


def test_ring_helpers_reject_unknown_op():
    with pytest.raises(ValueError):
        ring_hops("broadcast", 8)
    with pytest.raises(ValueError):
        ring_volume_factor("broadcast", 8)


@pytest.mark.parametrize("op", RING_OPS)
@pytest.mark.parametrize("scope", ("intra", "inter"))
def test_extrapolation_matches_direct_formula(op, scope):
    """With the hop-latency term removed/re-added via the shared
    helpers, the >8-way extrapolation is exact, not just <2% off."""
    provider = AnalyticalProvider(A40_CLUSTER)
    for n in (9, 12, 16, 64):
        e = Event(kind="collective", name=f"{op}:{n}", coll_op=op,
                  nbytes=4e6, n_dev=n, scope=scope)
        assert provider.time(e) == pytest.approx(
            collective_time(op, 4e6, n, A40_CLUSTER, scope), rel=1e-12)


@pytest.mark.parametrize("op", RING_OPS)
def test_extrapolation_continuous_at_nine(op):
    """Continuity: the first extrapolated point (n=9) follows the
    direct formula's trend at n=8 — no jump at the profile boundary."""
    provider = AnalyticalProvider(A40_CLUSTER)

    def t(n):
        return provider.time(Event(kind="collective", name=f"c:{n}",
                                   coll_op=op, nbytes=4e6, n_dev=n))
    step_78 = t(8) - t(7)
    step_89 = t(9) - t(8)
    assert t(9) > t(8)
    # the ring's per-device volume increments shrink with n, so the
    # 8->9 step must stay within the 7->8 trend
    assert step_89 <= step_78 + 1e-12
