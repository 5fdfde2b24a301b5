"""Search cells: DistSim's strategy search scored on the device.

A question is (chips, global batch, sequence, schedules); every
question passes both ZeRO-1 options. A pass stands for one planner at
work: a fresh ``SearchEngine`` with ``megabatch_backend="jax"`` asks every
question of the traffic file once, in an order drawn from the seed, so
every seed does the same work. Set-up makes one uncounted pass, which
compiles the device scan for every shape the questions make. The
window runs whole passes until ``--seconds`` have passed.

Spans: the benchmark times ``MegaBatch(engines)`` and
``MegaBatch.predict`` from outside, and records the device scan's
inputs and outputs for the questions the check samples. After the
window the plain float64 recurrence runs over those inputs, the plain
step composition (``reference/pipeline.py``) over every layout those
questions answered, and a fresh engine asks the same questions on the
program's per-candidate path.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List

import numpy as np

from harness import Cell, Check, Readings, log


def control_scan(out, dep, delay, dur, n_slots):
    """The plain recurrence in bfloat16, in the device scan's place."""
    import ml_dtypes

    from reference import recurrence
    return recurrence.scan(np.asarray(out), np.asarray(dep),
                           np.asarray(delay), np.asarray(dur), n_slots,
                           ml_dtypes.bfloat16)


@contextlib.contextmanager
def instrumented(spans, record: Dict[str, Any], control: bool = False):
    """Time the mega-batch compile and predict of each question, and
    keep the scan's inputs and outputs while ``record["on"]`` is set.
    ``control`` puts the bfloat16 reference in the device scan's
    place."""
    from repro.core import megabatch
    from repro.kernels import megabatch_scan

    base, program_scan = megabatch.MegaBatch, megabatch_scan.scan_steps
    scan = control_scan if control else program_scan

    class Timed(base):
        def __init__(self, *a, **kw):
            with spans.span("bench.megabatch_compile"):
                super().__init__(*a, **kw)

        def predict(self, *a, **kw):
            with spans.span("bench.scan"):
                return super().predict(*a, **kw)

    def recorded(out, dep, delay, dur, n_slots):
        ends, starts = scan(out, dep, delay, dur, n_slots)
        if record.get("on"):
            record["scans"].append({
                "out": out, "dep": dep, "delay": delay, "dur": dur,
                "n_slots": n_slots, "ends": np.array(ends)})
        return ends, starts

    megabatch.MegaBatch, megabatch_scan.scan_steps = Timed, recorded
    try:
        yield
    finally:
        megabatch.MegaBatch, megabatch_scan.scan_steps = (base,
                                                          program_scan)


def _engine(cfg, cluster, megabatch=True):
    from repro.search.engine import SearchEngine
    return SearchEngine(cfg, cluster, megabatch=megabatch,
                        megabatch_backend="jax")


def _ask(engine, q):
    return engine.search(q["chips"], q["global_batch"], q["seq"],
                         schedules=tuple(q["schedules"]),
                         zero1_options=(False, True))


def run(cell: Cell, control: bool = False) -> Dict[str, Any]:
    from harness import arch_config, cluster_spec
    cfg = arch_config(cell.conf)
    tr = cell.traffic
    cluster = cluster_spec(tr["cluster"])
    questions = tr["questions"]
    rng = np.random.default_rng([cell.seed, 11])
    record: Dict[str, Any] = {"on": False, "scans": []}
    # sampled for the check: the question with the longest scan, and
    # one more drawn from the seed
    longest = max(range(len(questions)),
                  key=lambda i: questions[i]["T"] * questions[i]["K"])
    others = [i for i in range(len(questions)) if i != longest]
    sampled = {longest} | ({int(rng.choice(others))} if others else set())

    with instrumented(cell.spans, record, control):
        warm = _engine(cfg, cluster)
        for q in questions:
            _ask(warm, q)
        del warm
        setup_s = time.perf_counter() - cell.t_start
        log(f"search: set-up {setup_s:.3f} s")

        asked: List[Dict[str, Any]] = []
        first_results = {}
        cell.spans.spans.clear()
        passes = 0
        with cell.window():
            t0 = time.perf_counter()
            while passes == 0 or time.perf_counter() - t0 < cell.seconds:
                engine = _engine(cfg, cluster)
                for i in map(int, rng.permutation(len(questions))):
                    keep = passes == 0 and i in sampled
                    record["on"] = keep
                    n_scans = len(record["scans"])
                    with cell.spans.span("bench.question"):
                        q0 = time.perf_counter()
                        res = _ask(engine, questions[i])
                        wall = time.perf_counter() - q0
                    record["on"] = False
                    asked.append({"q": i, "wall": wall,
                                  "cands": res.stats.candidates})
                    if keep:
                        first_results[i] = (res,
                                            record["scans"][n_scans:])
                passes += 1
            window_s = time.perf_counter() - t0
    return {"setup_s": setup_s, "window_s": window_s, "asked": asked,
            "passes": passes, "sampled": first_results, "cfg": cfg}


def _entry_times(res) -> Dict[Any, tuple]:
    return {(e.cluster, e.strategy): (e.batch_time, e.pruned, e.feasible)
            for e in res.entries}


def composed_times(cfg, cluster, q, strategies) -> List[float]:
    """Each layout's step as ``reference/pipeline.py`` composes it from
    the program's stage events, timed by a fresh provider."""
    from repro.core import DistSim
    from repro.core.events import Event
    from repro.core.profiler import AnalyticalProvider

    from reference import pipeline
    provider = AnalyticalProvider(cluster)

    def cost(kind, nbytes, scope, op="", n_dev=1):
        return provider.time(Event(kind=kind, name="bench", coll_op=op,
                                   nbytes=nbytes, n_dev=n_dev, scope=scope))
    out = []
    for s in strategies:
        stages = DistSim(cfg, s, q["global_batch"], q["seq"],
                         provider).positions()
        out.append(pipeline.step_time(
            {"mp": s.mp, "pp": s.pp, "dp": s.dp, "m": s.microbatches,
             "schedule": s.schedule, "zero1": s.zero1,
             "grad_compress": s.grad_compress},
            [{"fwd": [provider.time(e) for e in st.fwd.events],
              "bwd": [provider.time(e) for e in st.bwd.events],
              "boundary_bytes": st.boundary_act_bytes,
              "param_bytes": st.param_bytes} for st in stages],
            cost, cluster.devices_per_island, cluster.chip.hbm_bw))
    return out


def check(cell: Cell, got: Dict[str, Any], control: bool = False
          ) -> List[Check]:
    """The sampled questions' device scans against the plain recurrence
    over the same inputs; their answered layouts against the plain step
    composition; and their entries and prune decisions against the same
    questions asked of a fresh engine on the program's per-candidate
    path (no build cache, no mega-batch). (The control ran in the scan's
    place in :func:`run`.)"""
    from harness import cluster_spec
    from reference import recurrence
    limits = cell.traffic["limits"]
    questions = cell.traffic["questions"]
    cluster = cluster_spec(cell.traffic["cluster"])
    scan_gap, ref_gap, entry_gap = 0.0, 0.0, 0.0
    breaks, prunes = 0, 0
    witness = _engine(got["cfg"], cluster, megabatch=False)
    for i, (res, scans) in sorted(got["sampled"].items()):
        if not scans:
            scan_gap = float("inf")
        for s in scans:
            trash = s["n_slots"] - 1
            want = recurrence.scan_ends(s["out"], s["dep"], s["delay"],
                                        s["dur"], s["n_slots"])
            scan_gap = max(scan_gap, recurrence.rel_gap(
                recurrence.lane_finish(s["out"], s["ends"], trash),
                recurrence.lane_finish(s["out"], want, trash)))
        dev = _entry_times(res)
        keys = [k for k in dev if not dev[k][1] and dev[k][2]]
        ref_gap = max(ref_gap, recurrence.rel_gap(
            [dev[k][0] for k in keys],
            composed_times(got["cfg"], cluster, questions[i],
                           [k[1] for k in keys])))
        ref = _entry_times(_ask(witness, questions[i]))
        if set(ref) != set(dev):
            prunes += len(set(ref) ^ set(dev))
            continue
        prunes += sum(ref[k][1:] != dev[k][1:] for k in ref)
        keys = [k for k in ref if not ref[k][1] and ref[k][2]]
        r = np.array([ref[k][0] for k in keys])
        d = np.array([dev[k][0] for k in keys])
        breaks += recurrence.ranking_breaks(r, d)
        entry_gap = max(entry_gap, recurrence.rel_gap(d, r))
    return [
        Check("scan_gap", scan_gap, limits["scan_gap"]),
        Check("ref_gap", ref_gap, limits["ref_gap"]),
        Check("entry_gap", entry_gap, limits["entry_gap"]),
        Check("prune_mismatch", float(prunes), 0.0),
        Check("rank_breaks", float(breaks), 0.0),
    ]


def readings(cell: Cell, got: Dict[str, Any], r: Readings) -> None:
    from counts import recurrence_bytes
    asked = got["asked"]
    questions = cell.traffic["questions"]
    cands = sum(a["cands"] for a in asked)
    wall = sum(a["wall"] for a in asked)
    spans = cell.spans
    r.values.update({
        "setup_s": got["setup_s"],
        "search_cands_per_s": cands / got["window_s"],
        "questions": len(asked),
        "candidates": cands,
        "question_wall_s": wall,
        "megabatch_compile_s": spans.total("bench.megabatch_compile"),
        "scan_wall_s": spans.total("bench.scan"),
        "scan_bytes": float(sum(recurrence_bytes(
            questions[a["q"]]["T"], questions[a["q"]]["K"])
            for a in asked)),
        "window_s": got["window_s"],
    })
    log(f"search: {got['passes']} passes, {len(asked)} questions, "
        f"{cands} candidates in {got['window_s']:.3f} s")


def free(got: Dict[str, Any]) -> None:
    pass


def failed(got: Dict[str, Any]) -> int:
    return 0


def attempted(got: Dict[str, Any]) -> int:
    return sum(a["cands"] for a in got["asked"])
