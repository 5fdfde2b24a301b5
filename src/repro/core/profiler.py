"""Event profiling (paper §4.2).

Each unique event is profiled ONCE:

* ``AnalyticalProvider`` — TPU v5e operator-level roofline (the
  "Habitat-style predictor" pathway the paper offers for users without
  profiling hardware). Used for full-size configs and the target cluster.

* ``MeasuredProvider`` — actually executes each compute event's GEMMs with
  jit'd JAX on the default device and times them (the analogue of the
  paper's 2-node profiling; here one TPU v5e chip, and the CPU backend
  in the tests, where the times mean nothing). Communication events
  still use the ring model — one chip has no link to measure, the same
  situation the paper solves by extrapolating ≤8-way profiles (§4.2:
  error contribution <2%).

Times are cached per event — repeated strategies re-use profiles, as the
paper notes ("events' time can be stored and reused").
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable

from repro.core.costmodel import (ClusterSpec, V5E_POD, collective_time,
                                  compute_time, hbm_time, p2p_time,
                                  ring_hops, ring_volume_factor)
from repro.core.events import Event
from repro.obs import span


@dataclasses.dataclass
class ProviderStats:
    """Profiling-cost accounting for the search engine.

    ``evaluations`` counts real cost-model evaluations (cache misses) —
    the quantity the paper's unique-event dedup minimizes; ``hits``
    counts reuses of an already-profiled event.
    """
    evaluations: int = 0
    hits: int = 0

    @property
    def lookups(self) -> int:
        return self.evaluations + self.hits

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def reset(self) -> None:
        self.evaluations = 0
        self.hits = 0


class Provider:
    def __init__(self, cluster: ClusterSpec = V5E_POD):
        self.cluster = cluster
        self._cache: Dict[Event, float] = {}
        self.stats = ProviderStats()
        #: bumped on every cache clear; consumers that bake cached times
        #: into derived structures (EventFlowEngine, validate.BuildCache)
        #: stamp themselves with this and rebuild on mismatch.
        self.cache_version = 0

    def time(self, e: Event) -> float:
        if e not in self._cache:
            self._cache[e] = self._time(e)
            self.stats.evaluations += 1
        else:
            self.stats.hits += 1
        return self._cache[e]

    def cached_time(self, e: Event) -> float:
        """Profiled time of an already-cached event, without touching
        the hit/miss accounting (bookkeeping reads, e.g. the search
        engine's per-candidate profiling-cost sum)."""
        return self._cache[e]

    def clear_cache(self) -> None:
        """Drop profiled event times (stats are kept; reset separately).
        Bumps :attr:`cache_version` so engines holding baked-in means
        from the old cache are invalidated, not silently reused, and
        clears any subclass-derived caches (:meth:`_clear_derived`) so
        re-profiling can't serve measurements from before the clear."""
        self._cache.clear()
        self._clear_derived()
        self.cache_version += 1

    def _clear_derived(self) -> None:
        """Hook for subclasses holding caches derived from profiling
        (e.g. ``MeasuredProvider._group_cache``): called by
        :meth:`clear_cache` so a clear drops EVERYTHING, not just the
        event-time dict."""

    @property
    def cache_size(self) -> int:
        """Number of unique events currently profiled — the public
        accessor for accounting surfaces (``ProfileCache``, stores)
        that previously reached into ``_cache``."""
        return len(self._cache)

    def bare(self) -> "Provider":
        """Copy of this provider with EMPTY event/derived caches and
        fresh stats (same cluster, config and ``cache_version``) — what
        the parallel executor ships to worker processes when a disk
        :class:`repro.store.ProfileStore` carries the warm events
        instead of the pickled parent cache."""
        import copy
        p = copy.copy(self)
        p._cache = {}
        p.stats = ProviderStats()
        return p

    # ---- parallel-sweep shard support (repro.validate.executor) ----
    def cache_snapshot(self) -> Dict[Event, float]:
        """Copy of the profiled-event cache (picklable: Events are
        frozen dataclasses) — what a worker shard sends back."""
        return dict(self._cache)

    def merge_cache(self, entries: Dict[Event, float]) -> int:
        """Merge a shard's profiled events; existing entries win (values
        are identical for a deterministic provider — keeping the
        incumbent makes the merge order-independent). Returns how many
        events were new. Stats are NOT touched: the executor
        reconstructs serial-equivalent accounting from shard lookups."""
        fresh = 0
        for e, t in entries.items():
            if e not in self._cache:
                self._cache[e] = t
                fresh += 1
        return fresh

    def _time(self, e: Event) -> float:
        if e.kind == "compute":
            return self._compute_time(e)
        if e.kind == "collective":
            n = e.n_dev
            if n > 8:
                # paper §4.2: profile 8-way, extrapolate by ring volume.
                # We additionally remove/re-add the per-hop latency term
                # (known from the cluster spec) so the extrapolation is
                # exact — the paper bounds the residual effect at <2%.
                lat = (self.cluster.intra_latency if e.scope == "intra"
                       else self.cluster.inter_latency)
                t8 = (collective_time(e.coll_op, e.nbytes, 8, self.cluster,
                                      e.scope)
                      - ring_hops(e.coll_op, 8) * lat)
                v8 = ring_volume_factor(e.coll_op, 8)
                vn = ring_volume_factor(e.coll_op, n)
                return t8 * vn / v8 + ring_hops(e.coll_op, n) * lat
            return collective_time(e.coll_op, e.nbytes, n, self.cluster,
                                   e.scope)
        if e.kind == "p2p":
            # dPRO's min(SEND, RECV) rule: our model times the transmission
            # itself, which is that minimum by construction.
            return p2p_time(e.nbytes, self.cluster, e.scope)
        if e.kind == "hbm":
            # decode KV-cache / SSM-state read: pure HBM-bandwidth-bound
            return hbm_time(e.nbytes, self.cluster)
        raise ValueError(e.kind)

    def _compute_time(self, e: Event) -> float:
        raise NotImplementedError


class AnalyticalProvider(Provider):
    def _compute_time(self, e: Event) -> float:
        return compute_time(e.gemms, self.cluster.chip)


class MeasuredProvider(Provider):
    """Times real jit'd op groups on the default JAX device.

    An event's GEMMs are executed inside ONE jitted function — the
    operator-level granularity the paper profiles (per-op dispatch
    overheads amortize exactly as in a real fused program). A per-GEMM
    elementwise epilogue approximates the activation/softmax traffic
    between the GEMMs. Inputs are float32, so the profile matches a
    float32 step. :attr:`compile_seconds` and :attr:`timing_seconds`
    split the profiling cost between compiling and running.
    """

    def __init__(self, cluster: ClusterSpec = V5E_POD, reps: int = 3):
        super().__init__(cluster)
        self.reps = reps
        self._group_cache: Dict[tuple, float] = {}
        self.compile_seconds = 0.0
        self.timing_seconds = 0.0

    @property
    def n_groups(self) -> int:
        """Distinct GEMM groups compiled and timed."""
        return len(self._group_cache)

    def _clear_derived(self) -> None:
        # without this, a clear_cache() followed by re-profiling would
        # silently reuse jit timings measured before the clear
        self._group_cache.clear()

    def bare(self) -> "MeasuredProvider":
        p = super().bare()
        p._group_cache = {}
        p.compile_seconds = p.timing_seconds = 0.0
        return p

    def _time_group(self, dims: tuple) -> float:
        if dims in self._group_cache:
            return self._group_cache[dims]
        import jax
        import jax.numpy as jnp

        with span("distsim.profile.inputs"):
            inputs = [(jnp.ones((m, k), jnp.float32),
                       jnp.ones((k, n), jnp.float32)) for m, n, k in dims]

        # the name reaches the device: its modules read jit_profile_group
        def profile_group(args):
            acc = jnp.zeros((), jnp.float32)
            for a, b in args:
                y = a @ b
                y = jax.nn.silu(y)            # epilogue stand-in
                acc = acc + y.sum()
            return acc

        start = time.perf_counter()
        with span("distsim.profile.lower"):
            lowered = jax.jit(profile_group).lower(inputs)
        with span("distsim.profile.compile"):
            f = lowered.compile()
        compiled = time.perf_counter()
        self.compile_seconds += compiled - start
        with span("distsim.profile.warmup"):
            f(inputs).block_until_ready()
        best = float("inf")
        # no span inside a timed repetition: the spans leave the
        # profiled times as they are
        with span("distsim.profile.reps"):
            for _ in range(self.reps):
                t0 = time.perf_counter()
                f(inputs).block_until_ready()
                best = min(best, time.perf_counter() - t0)
        self.timing_seconds += time.perf_counter() - compiled
        self._group_cache[dims] = best
        return best

    def _compute_time(self, e: Event) -> float:
        dims = tuple((g.m, g.n, g.k) for g in e.gemms)
        return self._time_group(dims) if dims else 0.0


def profile_events(events: Iterable[Event], provider: Provider
                   ) -> Dict[Event, float]:
    return {e: provider.time(e) for e in events}


def profiling_cost(counts: Dict[Event, int], profile: Dict[Event, float]
                   ) -> Dict[str, float]:
    """Table 3: DistSim profiles each unique event once vs direct running
    profiling every instance on every device."""
    unique_t = sum(profile[e] for e in counts)
    direct_t = sum(profile[e] * c for e, c in counts.items())
    return {
        "unique_events": len(counts),
        "total_instances": int(sum(counts.values())),
        "profile_time_s": unique_t,
        "direct_time_s": direct_t,
        "relative_scale": unique_t / direct_t if direct_t else 1.0,
    }
