"""Share of the reference step's device time spent in collectives: on
each device plane, the union of the collective operations' intervals
(all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, their start and done halves included) over the union of all
operations' intervals, both taken over the operations that start inside
a ``bench.ref_block`` span, so that the profiler's own programs on one
chip do not dilute it; averaged over the planes that ran any. Operations
nest (a loop holds its body), hence unions and not sums. A trace with no
reference block or no collective leaves the metric out."""
import bisect
import re

COLLECTIVE = re.compile(r"^[%_]*(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def read(r):
    if r.trace is None or not r.trace["devices"]:
        return None
    import tracereduce
    blocks = sorted((s, s + d) for name, s, d in r.trace["host"]
                    if name == "bench.ref_block")
    starts = [a for a, _ in blocks]

    def in_block(t):
        i = bisect.bisect_right(starts, t) - 1
        return i >= 0 and t < blocks[i][1]

    shares, found = [], False
    for plane in r.trace["devices"].values():
        ops = [(name, s, s + d) for name, s, d in plane["ops"]
               if in_block(s)]
        busy = sum(e - s for s, e in tracereduce.union(
            [(s, e) for _, s, e in ops]))
        coll = sum(e - s for s, e in tracereduce.union(
            [(s, e) for name, s, e in ops if COLLECTIVE.match(name)]))
        found = found or coll > 0
        if busy > 0:
            shares.append(coll / busy)
    if not found:
        return None
    return sum(shares) / len(shares) * 100.0
