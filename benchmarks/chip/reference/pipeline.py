"""Plain reference of one training step of a hybrid layout (mp x pp x dp,
m microbatches, one stage per pipeline device), composed from the times
of its events.

- Device d holds pipeline stage d and runs its tasks in a fixed order:
  GPipe runs the m forwards, then the m backwards in reverse; 1F1B runs
  min(m, pp - 1 - d) forwards, then alternates a forward and a backward,
  then the remaining backwards. Interleaved 1F1B with one chunk per
  device is 1F1B.
- A task starts once its device is free and its input has arrived. The
  forward of microbatch i on stage s > 0 needs stage s - 1's forward of
  i plus one send of the boundary activation; the backward of i on stage
  s needs stage s's own forward of i and, below the last stage, stage
  s + 1's backward of i plus one send back.
- After its last task a device synchronises the gradients of its
  parameters over the dp replicas (one all-reduce, or a reduce-scatter
  then an all-gather under ZeRO-1), where dp > 1, then runs AdamW, which
  streams six copies of its parameter bytes twice at the chip's HBM
  bandwidth (its dp-th share under ZeRO-1).
- The step ends when the last device's last work ends. All replicas of a
  layout do the same work, so one replica stands for all.

Every event's time comes from ``cost``: ``cost("p2p", nbytes=, scope=)``
and ``cost("collective", op=, nbytes=, n_dev=, scope=)``; the stages'
compute and collective times come summed per task in ``fwd``/``bwd``.
A payload crosses islands ("inter") when the ranks it spans do not fit
on one island of the cluster.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

Task = Tuple[str, int]


def task_order(schedule: str, pp: int, m: int, d: int) -> List[Task]:
    if schedule == "gpipe":
        return ([("F", i) for i in range(m)]
                + [("B", i) for i in reversed(range(m))])
    if schedule not in ("1f1b", "interleaved"):
        raise ValueError(f"no plain schedule for {schedule!r}")
    w = min(m, pp - 1 - d)
    order = [("F", i) for i in range(w)]
    for j in range(m - w):
        order += [("F", w + j), ("B", j)]
    return order + [("B", i) for i in range(m - w, m)]


def _scope(span: int, per_island: int) -> str:
    return "intra" if span <= per_island else "inter"


def step_time(layout: Dict, stages: Sequence[Dict], cost: Callable,
              per_island: int, hbm_bw: float) -> float:
    """``layout``: mp, pp, dp, m, schedule, zero1 and grad_compress;
    ``stages``: per stage ``fwd`` and ``bwd`` (lists of event seconds),
    ``boundary_bytes`` and ``param_bytes``."""
    mp, pp, dp, m = (layout[k] for k in ("mp", "pp", "dp", "m"))
    if len(stages) != pp:
        raise ValueError("one stage per pipeline device")
    fwd = [sum(s["fwd"]) for s in stages]
    bwd = [sum(s["bwd"]) for s in stages]
    send = [cost("p2p", nbytes=s["boundary_bytes"],
                 scope=_scope(mp + 1, per_island)) for s in stages]

    f_end: Dict[Tuple[int, int], float] = {}
    b_end: Dict[Tuple[int, int], float] = {}
    orders = [task_order(layout["schedule"], pp, m, d) for d in range(pp)]
    nxt, free, last = [0] * pp, [0.0] * pp, [0.0] * pp
    left = sum(len(o) for o in orders)
    while left:
        moved = False
        for d in range(pp):
            while nxt[d] < len(orders[d]):
                phase, i = orders[d][nxt[d]]
                if phase == "F":
                    if d and (d - 1, i) not in f_end:
                        break
                    ready = f_end[d - 1, i] + send[d - 1] if d else 0.0
                    end = max(free[d], ready) + fwd[d]
                    f_end[d, i] = end
                    arrive = end + send[d] if d < pp - 1 else end
                else:
                    if d < pp - 1 and (d + 1, i) not in b_end:
                        break
                    ready = f_end[d, i]
                    if d < pp - 1:
                        ready = max(ready, b_end[d + 1, i] + send[d])
                    end = max(free[d], ready) + bwd[d]
                    b_end[d, i] = end
                    arrive = end + send[d - 1] if d else end
                free[d] = end
                last[d] = max(last[d], arrive)
                nxt[d] += 1
                left -= 1
                moved = True
        if not moved:
            raise RuntimeError("the schedule deadlocks")

    step = 0.0
    for d in range(pp):
        pbytes = stages[d]["param_bytes"] / mp * layout["grad_compress"]
        t = free[d]
        if dp > 1:
            scope = _scope(dp * pp * mp, per_island)
            if layout["zero1"]:
                t += (cost("collective", op="reduce_scatter", nbytes=pbytes,
                           n_dev=dp, scope=scope)
                      + cost("collective", op="all_gather", nbytes=pbytes,
                             n_dev=dp, scope=scope))
            else:
                t += cost("collective", op="all_reduce", nbytes=pbytes,
                          n_dev=dp, scope=scope)
        opt_bytes = pbytes / dp if layout["zero1"] else pbytes
        t += 6.0 * opt_bytes * 2 / hbm_bw
        step = max(step, last[d], t)
    return step
