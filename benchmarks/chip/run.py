"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is a configuration (``configs/<config>.json``: the registered
Problem, its sizes and the generator of its inputs) under a traffic mix
(``traffic/<traffic>.json``: the driver of the window, the solver
settings it adds and what the check compares).  One run:

1. finds the chips the cell asks for, and starts the compile clock;
2. hands the cell to its driver, ``drivers/<driver>.py``, whose
   ``drive(run)`` makes the inputs on the device from ``--seed``,
   drives the program, opens the measured window (``run.window``) once
   every shape the window uses has run, and closes it after
   ``--seconds``.  Everything before the window opens is set-up
   (``setup_s``).  ``drive`` frees the program's state and returns a
   dict: ``values`` (the end-to-end metrics it measures, by name),
   ``attempted`` and ``failed`` (the units of work in the window, and
   those that failed), ``units`` (what per-layer readers divide by),
   ``log`` (bookkeeping to print) and ``check`` (a function that runs
   the plain reference and returns each compared number by name);
3. reads the device memory peak;
4. with ``--trace 1``, reads the cell's per-layer metrics from the
   profiler's trace of the window (``metrics/<name>.py``);
5. calls ``check`` and holds each number to the traffic mix's limit.

Earlier lines of standard output carry the window's bookkeeping (the
compilations inside the window, which should be 0, the compile seconds
within set-up, the memory peak); the last line is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
last, ``checks``: each compared number with its limit, also printed as
the last lines of standard error.

The run refuses, with a non-zero exit and no result, when JAX finds no
TPU or another number of devices than the cell's ``chips``.
``--rehearse N`` is for rehearsals and tests only: it runs on the CPU
(four virtual devices for a four-chip cell) with N records.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import cells  # noqa: E402

_STARTS = [T_START]       # process start; later in-process runs (tests)


def fail(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr)
    raise SystemExit(1)


class CompileClock:
    """Count and seconds of XLA compilations (persistent-cache loads
    excluded), from JAX's own monitoring events."""

    def __init__(self, jax):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


class Window:
    """The measured window.  The cell's driver opens it and closes it at
    points of its own choosing (the solve driver: chunk boundaries);
    with a trace directory, the profiler records it between two
    markers.  Compilations and garbage-collection pauses inside it are
    counted."""

    def __init__(self, jax, seconds: float, clock, trace_dir=None):
        self.jax, self.seconds = jax, seconds
        self.clock, self.trace_dir = clock, trace_dir
        self.opened = self.closed = None        # (time, units done)
        self.compiles = None
        self.compile_s = 0.0                    # within set-up
        self.gc_pauses = []                     # inside the window
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if self.opened is None or self.closed is not None:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)
            self._gc_t0 = None

    def open(self, done: int) -> None:
        self.compiles = self.clock.count
        self.compile_s = self.clock.seconds
        if self.trace_dir is not None:
            from devtrace import OPEN_MARK
            self.jax.profiler.start_trace(self.trace_dir)
            with self.jax.profiler.TraceAnnotation(OPEN_MARK):
                pass
        self.opened = (time.perf_counter(), done)

    def due(self) -> bool:
        return time.perf_counter() - self.opened[0] >= self.seconds

    def close(self, done: int) -> None:
        self.closed = (time.perf_counter(), done)
        self.compiles = self.clock.count - self.compiles
        gc.callbacks.remove(self._on_gc)
        if self.trace_dir is not None:
            from devtrace import CLOSE_MARK
            with self.jax.profiler.TraceAnnotation(CLOSE_MARK):
                pass

    @property
    def done(self) -> int:
        return self.closed[1] - self.opened[1]

    @property
    def seconds_open(self) -> float:
        return self.closed[0] - self.opened[0]


@dataclasses.dataclass
class Run:
    """What a driver gets: the cell, the seed, the devices (a mesh over
    them for a cell of several chips), the window, and ``fail``."""
    jax: object
    cell: cells.Cell
    seed: int
    devices: list
    mesh: object
    window: Window

    @staticmethod
    def fail(msg: str) -> None:
        fail(msg)


class Reading:
    """What a per-layer metric reader gets."""

    def __init__(self, trace, cell, device_kind, iters):
        self.trace, self.cell = trace, cell
        self.device_kind, self.iters = device_kind, iters


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N",
                    help="CPU rehearsal with N records (tests only)")
    return ap.parse_args(argv)


def chip_devices(jax, cell, rehearse: bool):
    devs = jax.devices()
    if rehearse:
        if len(devs) < cell.chips:
            fail(f"rehearsal needs {cell.chips} devices, found {len(devs)}")
        return devs[:cell.chips]
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) != cell.chips:
        fail(f"{cell.name} runs on {cell.chips} chips; JAX found "
             f"{len(devs)}")
    return devs


def trace_readings(cell, trace_dir, device_kind, iters):
    import devtrace
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if not path:
        fail("the profiler wrote no trace")
    tr = devtrace.load(path[0])
    lo, hi = tr.window
    reading = Reading(tr, cell, device_kind, iters)
    metrics = {}
    for m in cell.per_layer:
        v = cells.component("metrics", m["name"]).read(reading)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    devs = tr.devices
    busy = (sum(devtrace.busy_ns(d, lo, hi) for d in devs) / len(devs)
            if devs else 0.0)
    gaps = []
    if devs:
        gaps = devtrace.name_gaps(devtrace.chunk_gaps(devs[0], lo, hi),
                                  tr.host)
    breakdown = {
        "device_ops": [[n, s] for n, s in devtrace.top_ops(devs, lo, hi)],
        "idle_gaps": [[n, (e - s) * 1e-9] for n, s, e in
                      sorted(gaps, key=lambda g: g[1] - g[2])[:10]],
    }
    return metrics, busy * 1e-9, (hi - lo) * 1e-9, breakdown


def main(argv=None) -> dict:
    t_start = _STARTS.pop() if _STARTS else time.perf_counter()
    args = parse(argv)
    if args.seconds <= 0:
        fail("--seconds must be positive")
    try:
        bench = cells.load_benchmark(ROOT)
        cell = cells.resolve(bench, args.workload, args.rehearse)
    except (OSError, KeyError, ValueError) as e:
        fail(f"cannot resolve the cell: {e}")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1 and "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={cell.chips}")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is missing: no {ROOT / 'src' / 'repro'}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    if not args.rehearse:
        # fixed, inside the checkout: the path is part of the cache key
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = chip_devices(jax, cell, bool(args.rehearse))
    t_devices = time.perf_counter()
    clock = CompileClock(jax)
    mesh = None
    if cell.chips > 1:
        mesh = jax.make_mesh(
            (cell.chips, 1), ("data", "model"), devices=devs,
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_") if args.trace else None
    window = Window(jax, args.seconds, clock, trace_dir)
    driver = cells.component("drivers", cell.traffic["driver"])
    driven = driver.drive(Run(jax, cell, args.seed, devs, mesh, window))
    if args.trace:
        jax.profiler.stop_trace()
    setup_s = window.opened[0] - t_start
    units = driven["units"]

    stats = [d.memory_stats() or {} for d in devs]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    gc.collect()

    result = {"correct": False, "attempted": driven["attempted"],
              "failed": driven["failed"]}
    if args.trace:
        layer, busy_s, window_s, breakdown = trace_readings(
            cell, trace_dir, devs[0].device_kind, units)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = layer
    else:
        values = dict(driven["values"], setup_s=setup_s)
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in values]
        if missing:
            fail(f"driver {cell.traffic['driver']!r} reports no {missing}")
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if args.trace:
        device.update(busy_s=busy_s, window_s=window_s)
    result["device"] = device
    if args.trace:
        result["breakdown"] = breakdown
    print(json.dumps({"window": {
        "seed": args.seed, **driven["values"], **driven["log"],
        "setup_s": setup_s, "compiles_in_window": window.compiles,
        "compile_s_in_setup": window.compile_s,
        "gc_in_window": {"count": len(window.gc_pauses),
                         "seconds": sum(window.gc_pauses),
                         "longest_s": max(window.gc_pauses, default=0.0)},
        "start_to_devices_s": t_devices - t_start,
        "peak_bytes_in_use": peak}}), flush=True)

    t_ref = time.perf_counter()
    numbers = driven["check"]()
    limits = cell.traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in numbers.items()}
    result["correct"] = bool(driven["failed"] == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()))
    result["checks"] = checks
    print(json.dumps({"reference_s": time.perf_counter() - t_ref}),
          flush=True)
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
