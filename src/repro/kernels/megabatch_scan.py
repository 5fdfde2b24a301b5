"""Device backend for the mega-batch predict recurrence.

:class:`repro.core.megabatch.MegaBatch` compiles K candidate engines
into ``(T, K)`` step arrays; this module evaluates the step recurrence

    start[j] = max over 3 deps of (ends[dep[j]] + delay[j])
    ends[out[j]] = start[j] + dur[j]

on jax as a ``lax.scan`` over the T steps (the dependency chain is
inherently sequential; each step is a (K, 3) gather + add + row-max).

This path runs in whatever precision jax is configured for (float32
by default); the numpy path in :mod:`repro.core.megabatch` remains the
bit-identical reference and the default on CPU. The device path is held
to the numpy path's ranking, not its bits. Import of jax is deferred to
call time so numpy-only environments can import this module's callers
freely.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.obs import span

try:                              # deferred everywhere else; this flag
    import jax                    # only gates backend availability
    HAS_JAX = True
except ImportError:               # pragma: no cover - numpy-only CI env
    jax = None
    HAS_JAX = False


def accelerator_backend() -> Optional[str]:
    """'gpu' / 'tpu' when jax sees an accelerator, else None — the
    signal ``backend='auto'`` uses to leave CPU runs on numpy."""
    if not HAS_JAX:
        return None
    b = jax.default_backend()
    return b if b in ("gpu", "tpu") else None


def scan_steps(out: np.ndarray, dep: np.ndarray, delay: np.ndarray,
               dur: np.ndarray, n_slots: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Evaluate the step recurrence; returns float64 numpy
    ``(ends, starts)`` vectors of length ``n_slots`` (upcast from the
    jax dtype in use)."""
    if not HAS_JAX:
        raise RuntimeError(
            "megabatch backend 'jax' requires jax; this environment has "
            "numpy only — use backend='numpy'")
    import jax.numpy as jnp

    dtype = jnp.result_type(float)      # honors jax_enable_x64
    with span("distsim.scan.put"):
        ends0 = jnp.zeros((n_slots,), dtype=dtype)
        xs = (jnp.asarray(out), jnp.asarray(dep),
              jnp.asarray(delay, dtype=dtype),
              jnp.asarray(dur, dtype=dtype))
    with span("distsim.scan.run"):
        ends, step_starts = _scan_jit(ends0, xs)
    with span("distsim.scan.fetch"):
        ends = np.asarray(ends, dtype=np.float64)
        # scatter per-step start rows back to slot order (trash-slot
        # rows overwrite each other; their value is never read)
        starts = np.zeros(n_slots)
        starts[np.asarray(out)] = np.asarray(step_starts,
                                             dtype=np.float64)
    return ends, starts


def _step(ends, xs):
    o, dp_, dl, du = xs
    start = (ends[dp_] + dl).max(axis=-1)
    return ends.at[o].set(start + du), start


def scan_program(ends0, xs):
    """The device program: ``(ends, per-step starts)`` after T steps.
    Module-level so that jit caches one executable per shape."""
    return jax.lax.scan(_step, ends0, xs)


_scan_jit = jax.jit(scan_program) if HAS_JAX else None
