"""Mega-batch predict: differential oracle against per-engine run(),
backend agreement, and edge cases (PR: vectorized strategy scoring)."""
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.core import (A40_CLUSTER, AnalyticalProvider, DistSim, Strategy,
                        MegaBatch, megabatch_predict)
from repro.core.megabatch import same_ranking

PROVIDER = AnalyticalProvider(A40_CLUSTER)
CFG = get_config("gpt2_345m")

# fully heterogeneous: schedules, pp depth, vpp, microbatches, zero1,
# grad compression — and ragged task counts (2 .. hundreds of tasks)
STRATS = [
    Strategy(mp=1, pp=1, dp=1, microbatches=1),
    Strategy(mp=1, pp=2, dp=2, microbatches=4),
    Strategy(mp=1, pp=4, dp=1, microbatches=8, schedule="gpipe"),
    Strategy(mp=2, pp=2, dp=1, microbatches=4, schedule="interleaved",
             vpp=2),
    Strategy(mp=1, pp=2, dp=2, microbatches=4, schedule="pipedream"),
    Strategy(mp=2, pp=2, dp=2, microbatches=4, zero1=True),
    Strategy(mp=1, pp=4, dp=2, microbatches=16, schedule="interleaved",
             vpp=3),
    Strategy(mp=1, pp=2, dp=2, microbatches=4, grad_compress=0.25),
    Strategy(mp=1, pp=8, dp=1, microbatches=8),
]


def _engines(cfg=CFG, strats=STRATS, seq=128):
    engines = []
    for strat in strats:
        gb = strat.dp * strat.microbatches * 2
        engines.append(DistSim(cfg, strat, gb, seq, PROVIDER).engine())
    return engines


def test_megabatch_bit_identical_to_per_engine_run():
    """The tentpole gate: batch times bit-identical PER CANDIDATE to
    engine.run(), across heterogeneous ragged candidates."""
    engines = _engines()
    sizes = {e.total_tasks for e in engines}
    assert len(sizes) > 3            # genuinely ragged program
    pred = megabatch_predict(engines, backend="numpy")
    assert pred.backend == "numpy"
    assert pred.n_candidates == len(engines)
    for i, eng in enumerate(engines):
        tl = eng.run()
        assert float(pred.batch_times[i]) == tl.batch_time, \
            eng.strat.label()
        assert float(pred.bubble_fractions[i]) == pytest.approx(
            tl.bubble_fraction(), abs=1e-12)


def test_megabatch_includes_empty_stage_candidates():
    """pp > layer count: candidates whose trailing devices own no
    tasks still score bit-identically."""
    cfg = smoke_config(get_config("gpt2_345m"))      # 2 layers
    strats = [Strategy(pp=4, microbatches=4),
              Strategy(pp=2, microbatches=2),
              Strategy(pp=8, microbatches=8, schedule="gpipe")]
    engines = _engines(cfg, strats, seq=64)
    pred = megabatch_predict(engines, backend="numpy")
    for i, eng in enumerate(engines):
        assert float(pred.batch_times[i]) == eng.run().batch_time


def test_megabatch_empty_and_single():
    empty = MegaBatch([]).predict()
    assert empty.n_candidates == 0 and len(empty.batch_times) == 0
    engines = _engines(strats=STRATS[:1])
    pred = MegaBatch(engines).predict("numpy")
    assert float(pred.batch_times[0]) == engines[0].run().batch_time


def test_megabatch_compile_once_predict_many():
    engines = _engines(strats=STRATS[:4])
    mb = MegaBatch(engines)
    a = mb.predict("numpy").batch_times
    b = mb.predict("numpy").batch_times
    assert np.array_equal(a, b)
    assert np.array_equal(a, mb.predict_times("numpy"))


def test_megabatch_unknown_backend_raises():
    mb = MegaBatch(_engines(strats=STRATS[:1]))
    with pytest.raises(ValueError, match="backend"):
        mb.predict("cuda")


def test_megabatch_auto_backend_numpy_without_accelerator():
    """'auto' must not import jax on a CPU box (numpy-only CI jobs)."""
    mb = MegaBatch(_engines(strats=STRATS[:1]))
    assert mb.resolve_backend("auto") in ("numpy", "jax")


@pytest.mark.parametrize("backend", ["jax"])
def test_megabatch_accelerator_backends_match_numpy(backend):
    """jax runs the same recurrence; float32 accumulation bounds the
    deviation (numpy stays the bit-identical reference)."""
    jax = pytest.importorskip("jax")
    del jax
    engines = _engines(strats=STRATS[:5])
    mb = MegaBatch(engines)
    ref = mb.predict("numpy").batch_times
    got = mb.predict(backend)
    assert got.backend == backend
    np.testing.assert_allclose(got.batch_times, ref, rtol=1e-5)


def test_same_ranking_allows_only_ties_to_swap():
    ref = np.array([3.0, 1.0, 2.0, 2.0 * (1 + 1e-16), 5.0])
    assert same_ranking(ref, ref)
    assert same_ranking(ref, np.array([3.0, 1.0, 2.0 + 1e-9, 2.0, 5.0]))
    assert not same_ranking(ref, np.array([3.0, 1.0, 2.0, 3.5, 2.5]))
    assert not same_ranking(ref, np.array([0.5, 1.0, 2.0, 2.0, 5.0]))


def test_megabatch_jax_ranks_search_grid_as_numpy():
    """The float32 device path keeps the numpy ranking of a search grid
    with exact and last-bit ties (gpipe == 1f1b at pp=1); float32
    resolves those ties either way, so only they may swap."""
    pytest.importorskip("jax")
    from repro.core import V5E_POD
    from repro.search.space import enumerate_candidates
    provider = AnalyticalProvider(V5E_POD)
    cands = enumerate_candidates(16, 32, schedules=("1f1b", "gpipe"))
    mb = MegaBatch([DistSim(CFG, c.strategy, 32, 128, provider).engine()
                    for c in cands])
    ref = mb.predict("numpy").batch_times
    got = mb.predict("jax").batch_times
    assert mb.K >= 100
    assert same_ranking(ref, got)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
