"""What every cell of the chip benchmark shares.

The manifest is ``BENCHMARK.json`` at the root of the checkout. A cell
(an entry of ``workloads``) names a configuration, whose sizes live in
``configs/<name>.json``, and a traffic mix, whose parameters live in
``traffic/<name>.json``. The traffic file's ``kind`` picks the module
that runs the cell (``plan`` or ``search``). A plan traffic file also
names the cell's references: ``step_reference`` (a module of
``reference/``: the plain step), ``step_flops``
(``"<module>:<function>"``: a step's FLOPs) and ``prediction_reference``
(a module of ``reference/``: the plain composition of DistSim's
prediction, given the strategy's ``mp`` and ``dp`` and any
``prediction_args`` as keywords), and ``limits``, one for each number
that ``correct`` compares, so a new architecture brings these as files
of its own. Each
per-layer metric is a reader of its own in ``metrics/<name>.py``.
Nothing here knows a cell by name.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
MANIFEST = ROOT / "BENCHMARK.json"
#: fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class BenchError(RuntimeError):
    """The run cannot measure what the cell asks for; no result."""


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: Path = MANIFEST) -> Dict[str, Any]:
    if not path.is_file():
        raise BenchError(f"no {path.name} at {path.parent}")
    return load_json(path)


def find_cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in {MANIFEST.name}")


def load_config(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in manifest["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise BenchError(f"no configuration {name!r} in {MANIFEST.name}")


def load_traffic(name: str) -> Dict[str, Any]:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no traffic file {path.relative_to(ROOT)}")
    return load_json(path)


def metrics_of(manifest: Dict[str, Any], cell: str, group: str
               ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str) -> Callable[["Readings"], Optional[float]]:
    """``metrics/<name>.py``'s ``read``: returns the metric, or None
    where the run has nothing to read it from."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def arch_config(conf: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {k: v for k, v in conf.items() if k in fields}
    if "shapes" in kw:
        kw["shapes"] = tuple(kw["shapes"])
    return ArchConfig(**kw)


def cluster_spec(name: str):
    """The program's cluster (a ``ClusterSpec`` of ``repro.core.costmodel``)
    named in a traffic file."""
    from repro.core import costmodel
    return getattr(costmodel, name)


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json; "
                         f"known: {sorted(table)}")
    return table[kind]


def check_devices(chips: int):
    """The TPU devices of this run; raises where JAX finds no TPU or
    fewer chips than the cell asks for, or a kind without peaks."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform {platform!r}); the "
                         f"benchmark measures the chip and never falls "
                         f"back to the CPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def setup_compile_cache() -> None:
    """JAX's persistent compilation cache in the checkout, for every
    program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: it needs an access-time file beside every entry
    jax.config.update("jax_compilation_cache_max_size", -1)


def seed_key(seed: int):
    """A JAX key from a seed of any size (two 32-bit halves)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class CompileCounter:
    """Counts XLA compiles: programs built by the backend, leaving out
    those read back from the persistent compilation cache."""

    def __init__(self):
        from jax import monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self._built = 0
        self._hits = 0

        def on_duration(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self._built += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self._hits += 1
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    @property
    def count(self) -> int:
        return self._built - self._hits


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float


class Spans:
    """Host spans from the benchmark's own files, kept in memory; in a
    traced run each is also a profiler annotation of the same name."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.spans: List[Span] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append(Span(name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(s.end - s.start for s in self.spans if s.name == name)


@dataclasses.dataclass
class Readings:
    """What a run hands the metric readers: host-clock totals, program
    counters, and, in a traced run, the reduced device trace."""
    cell: str
    values: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Optional[Dict[str, Any]] = None
    peaks: Dict[str, float] = dataclasses.field(default_factory=dict)
    chips: int = 1


@dataclasses.dataclass
class Check:
    """One number compared with its limit (larger is worse)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def peak_memory(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                checks: List[Check],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The last line of standard output; the compared numbers come
    last, each beside its limit."""
    out: Dict[str, Any] = dict(zip(RESULT_KEYS, (
        correct, attempted, failed, metrics, device)))
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)


def print_checks(checks: List[Check]) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    """What a cell's module needs to run one cell."""
    name: str
    conf: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    devices: List[Any]
    spans: Spans
    t_start: float
    #: where the profiler writes the traced window; None: not traced
    trace_dir: Optional[str] = None
    counter: Optional[CompileCounter] = None
    #: XLA compiles inside the window
    window_compiles: int = 0

    @contextlib.contextmanager
    def window(self):
        """The measured window: a ``bench.window`` span, traced where
        asked; XLA compiles inside it are counted."""
        import jax
        before = self.counter.count if self.counter else 0
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
        try:
            with self.spans.span("bench.window"):
                yield
        finally:
            if self.trace_dir:
                jax.profiler.stop_trace()
            if self.counter:
                self.window_compiles = self.counter.count - before
