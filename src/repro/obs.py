"""Program spans on the profiler's clock.

    from repro import obs
    obs.enable()                      # imports jax; spans on
    with jax.profiler.trace(logdir):  # records host spans and device ops
        sim.simulate()

``span(name)`` marks one phase of the program. Off (the default) it
returns one shared null context: no clock read, no allocation, and no
jax import, so numpy-only callers keep working. On, it returns
``jax.profiler.TraceAnnotation(name)``, which lands in the same
``.xplane.pb`` as the device's operations while a trace is recorded.
Spans never feed a value back into the program. Every name starts with
``distsim.``.
"""
from __future__ import annotations

import contextlib
from typing import Any, ContextManager, Optional

_NULL: ContextManager[None] = contextlib.nullcontext()
#: ``jax.profiler.TraceAnnotation`` while spans are on, else None
_annotation: Optional[Any] = None


def enable() -> None:
    """Turn spans on (imports jax)."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation


def disable() -> None:
    """Turn spans off again."""
    global _annotation
    _annotation = None


def enabled() -> bool:
    return _annotation is not None


def span(name: str) -> ContextManager[Any]:
    """A context manager bounding the phase ``name``."""
    if _annotation is None:
        return _NULL
    return _annotation(name)
