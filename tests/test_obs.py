"""Program spans (``repro.obs``): off, a span is one shared null context
and importing the module imports no jax; on, a profiler trace holds
each phase of an answer and of a search, nested where the work happens;
on or off, every prediction is the same."""
import glob
import os
import subprocess
import sys

import pytest

from repro import obs
from repro.configs.base import get_config, smoke_config
from repro.core import (A40_CLUSTER, AnalyticalProvider, DistSim,
                        MeasuredProvider, Strategy)
from repro.search import SearchEngine

CFG = smoke_config(get_config("gpt2_345m"))
SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

#: each span and the innermost program span it must sit in
PARENT = {
    "distsim.positions": "distsim.simulate",
    "distsim.engine": "distsim.simulate",
    "distsim.run": "distsim.simulate",
    "distsim.profile.inputs": "distsim.engine",
    "distsim.profile.lower": "distsim.engine",
    "distsim.profile.compile": "distsim.engine",
    "distsim.profile.warmup": "distsim.engine",
    "distsim.profile.reps": "distsim.engine",
    "distsim.search.engines": "distsim.search",
    "distsim.build.engine": "distsim.search.engines",
    "distsim.build.engine_build": "distsim.build.engine",
    "distsim.build.positions": "distsim.build.engine_build",
    "distsim.build.structure": "distsim.build.engine",
    "distsim.megabatch.compile": "distsim.search",
    "distsim.megabatch.predict": "distsim.search",
    "distsim.scan.stack": "distsim.megabatch.predict",
    "distsim.scan.put": "distsim.megabatch.predict",
    "distsim.scan.run": "distsim.megabatch.predict",
    "distsim.scan.fetch": "distsim.megabatch.predict",
    "distsim.megabatch.epilogue": "distsim.megabatch.predict",
    "distsim.search.replay": "distsim.search",
    "distsim.search.rank": "distsim.search",
}


def _search():
    return SearchEngine(CFG, clusters=A40_CLUSTER,
                        megabatch_backend="jax").search(
        8, 8, 64, schedules=("1f1b", "gpipe"), zero1_options=(False, True))


def _simulate(provider, **kw):
    return DistSim(CFG, Strategy(), global_batch=2, seq=64,
                   provider=provider).simulate(**kw)


@pytest.fixture
def spans_on():
    obs.enable()
    try:
        yield
    finally:
        obs.disable()


def test_off_is_one_null_context_and_imports_no_jax():
    assert not obs.enabled()
    a, b = obs.span("distsim.a"), obs.span("distsim.b")
    assert a is b
    with a, b:                      # reusable and reentrant
        pass
    code = ("import sys; from repro import obs; "
            "assert not obs.enabled(); "
            "assert obs.span('distsim.x') is obs.span('distsim.y'); "
            "assert 'jax' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=SRC), timeout=60)


def _host_events(logdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _host_spans(logdir):
    return [s for s in _host_events(logdir) if s[0].startswith("distsim.")]


def test_profiled_programs_carry_their_name(tmp_path):
    """The profiler's GEMM programs are ``jit(profile_group)``, so their
    device modules read ``jit_profile_group`` whether spans are on or
    off."""
    import jax
    with jax.profiler.trace(str(tmp_path)):
        _simulate(MeasuredProvider(reps=2))
    names = {n for n, _, _ in _host_events(str(tmp_path))}
    assert "PjitFunction(jit(profile_group))" in names
    assert not any(n.startswith("distsim.") for n in names)


def test_spans_nest_where_the_work_happens(tmp_path, spans_on):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        _simulate(MeasuredProvider(reps=2))
        _search()
    spans = _host_spans(str(tmp_path))
    names = {n for n, _, _ in spans}
    assert names == set(PARENT) | {"distsim.simulate", "distsim.search"}
    for i, (name, s, e) in enumerate(spans):
        if name not in PARENT:
            continue
        holders = [(b - a, n) for j, (n, a, b) in enumerate(spans)
                   if j != i and a <= s and e <= b]
        assert holders, name
        assert min(holders)[1] == PARENT[name], name


@pytest.mark.parametrize("seeds", [None, (0, 1)])
def test_predictions_are_the_same_with_spans_on(seeds):
    off_sim = _simulate(AnalyticalProvider(A40_CLUSTER), seeds=seeds)
    off_search = _search()
    obs.enable()
    try:
        on_sim = _simulate(AnalyticalProvider(A40_CLUSTER), seeds=seeds)
        on_search = _search()
    finally:
        obs.disable()
    assert on_sim.batch_times.tobytes() == off_sim.batch_times.tobytes()
    assert on_search.entries == off_search.entries
    assert on_search.pareto == off_search.pareto
