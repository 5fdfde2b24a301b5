"""DistSim's benchmark on the chip: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs the cell named in ``BENCHMARK.json`` on the TPU devices of this
machine and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last the numbers
that decided ``correct``, each beside its limit. It exits non-zero and
prints no result where JAX finds no TPU or fewer chips than the cell
asks for.

    python3 benchmarks/chip/run.py --control ...

puts the plain reference, in the precision below the configuration's,
in the program's place and prints the same line: the readings that set
each limit's upper end. The benchmark's own runs never do this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def execute(args, devices=None) -> int:
    """One run of ``args.workload``; ``devices`` given skips the look
    for a chip (the tests drive the rest of a run on the CPU)."""
    manifest = harness.load_manifest()
    spec = harness.find_cell(manifest, args.workload)
    conf = harness.load_config(manifest, spec["config"])
    traffic = harness.load_traffic(spec["traffic"])
    src = harness.ROOT / "src"
    if not (src / "repro").is_dir():
        raise harness.BenchError(f"no program at {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    if devices is None:
        devices = harness.check_devices(spec["chips"])
    harness.setup_compile_cache()
    import repro.core  # noqa: F401  (before repro.search: import order)

    kind_mod = importlib.import_module(traffic["kind"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    cell = harness.Cell(
        name=args.workload, conf=conf, traffic=traffic, seed=args.seed,
        seconds=float(args.seconds), devices=devices,
        spans=harness.Spans(traced=bool(args.trace)), t_start=T_START,
        trace_dir=trace_dir, counter=harness.CompileCounter())
    got = kind_mod.run(cell, control=args.control)
    harness.log(f"window: {cell.window_compiles} XLA compiles")
    kind = devices[0].device_kind
    readings = harness.Readings(cell=cell.name,
                                peaks=harness.peaks_for(kind),
                                chips=len(devices))
    kind_mod.readings(cell, got, readings)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": harness.peak_memory(devices)}
    breakdown = None
    if trace_dir:
        import tracereduce
        try:
            readings.trace = tracereduce.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tracereduce.busy_seconds(readings.trace)
        device["window_s"] = tracereduce.window_seconds(readings.trace)
        breakdown = {"device_ops": tracereduce.top_ops(readings.trace),
                     "idle_gaps": tracereduce.idle_gaps(readings.trace)}
    kind_mod.free(got)
    checks = kind_mod.check(cell, got, control=args.control)

    metrics = {}
    group = "per_layer" if args.trace else "end_to_end"
    for m in harness.metrics_of(manifest, cell.name, group):
        if group == "end_to_end":
            value = readings.values[m["name"]]
        else:
            value = harness.load_reader(m["name"])(readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    harness.print_checks(checks)
    correct = all(c.ok for c in checks)
    print(harness.result_line(correct, kind_mod.attempted(got),
                              kind_mod.failed(got), metrics, device, checks,
                              breakdown), flush=True)
    return 0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="the reference below the stated precision in "
                         "the program's place")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        return execute(args)
    except harness.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
