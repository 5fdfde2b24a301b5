"""Plain reference of the mega-batch step recurrence and its ranking bar.

Given the arrays that the device scan is handed (``out`` (T, K) slot
written at each step, ``dep`` (T, K, 3) slots read, ``delay`` (T, K, 3),
``dur`` (T, K)), every step j does, for every lane k:

    start = max over i of (ends[dep[j, k, i]] + delay[j, k, i])
    ends[out[j, k]] = start + dur[j, k]

starting from ``ends`` all zero. Written from that statement, in numpy,
one step at a time, in the dtype asked for (float64 for the reference,
bfloat16 for the control).
"""
from __future__ import annotations

import numpy as np


def scan(out, dep, delay, dur, n_slots: int, dtype=np.float64):
    """Slot end times after all T steps, and the start of the task each
    slot holds; both float64 (upcast from ``dtype``)."""
    ends = np.zeros(n_slots, dtype=dtype)
    starts = np.zeros(n_slots, dtype=dtype)
    for j in range(out.shape[0]):
        start = np.max(ends[dep[j]] + delay[j].astype(dtype), axis=-1)
        ends[out[j]] = start + dur[j].astype(dtype)
        starts[out[j]] = start
    return ends.astype(np.float64), starts.astype(np.float64)


def scan_ends(out, dep, delay, dur, n_slots: int, dtype=np.float64
              ) -> np.ndarray:
    """Slot end times after all T steps."""
    return scan(out, dep, delay, dur, n_slots, dtype)[0]


def lane_finish(out, ends, trash: int) -> np.ndarray:
    """(K,) latest end over the slots each lane writes (its padding
    steps write the trash slot, which is left out)."""
    ends = np.asarray(ends, dtype=np.float64)
    vals = np.where(out == trash, -np.inf, ends[out])
    return vals.max(axis=0)


def rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if not len(want):
        return 0.0
    return float(np.max(np.abs(got - want) / np.abs(want)))


#: relative gap under which two reference times count as tied
RANK_RTOL = 1e-5


def ranking_breaks(reference, times) -> int:
    """Adjacent pairs of ``times``' order that contradict the order of
    ``reference``, where ties (groups of reference times within
    :data:`RANK_RTOL` of their neighbour) may come in any order."""
    reference = np.asarray(reference, np.float64)
    times = np.asarray(times, np.float64)
    if reference.shape != times.shape:
        return len(reference) + len(times)
    order = np.argsort(reference, kind="stable")
    ref = reference[order]
    new_group = np.ones(len(ref), dtype=bool)
    new_group[1:] = ref[1:] - ref[:-1] > RANK_RTOL * np.abs(ref[1:])
    group = np.empty(len(ref), dtype=np.int64)
    group[order] = np.cumsum(new_group)
    ranked = group[np.argsort(times, kind="stable")]
    return int(np.sum(np.diff(ranked) < 0))
