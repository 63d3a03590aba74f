#!/usr/bin/env python3
"""Benchmark harness: runs one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/repro``).
Everything a cell names is found by name, from files (``spec.py``): its
configuration (``bench/configs/``), whose ``graph.generator`` names a
generator in ``bench/generators/``; its traffic mix (``bench/traffic/``,
data), whose ``loop`` names the loop in ``bench/loops/`` that drives the
program; and a reader in ``bench/metrics/`` for each metric but
``setup_s``.  Nothing here names a cell, a loop or a metric.

A loop module has a ``Loop(cell, seed, clock, log)`` class with
``setup()``, ``window(seconds)``, ``check()`` and the counts
``attempted`` and ``failed``; readers get the ``Run`` below and its
``loop``.  A run:

1. Set-up (``setup_s``, from process start to the end of
   ``loop.setup()``): JAX and the chip, the persistent compile cache,
   then the loop's inputs from ``--seed`` and a warm-up of every shape
   the window will use.
2. The window, ``loop.window(seconds)``, inside the host span
   ``bench.window``; with ``--trace 1`` under the JAX profiler.
3. After the window: peak device memory, then ``loop.check()``, the
   comparison with the plain reference that decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics and ``--trace 1``
its per-layer metrics, with the device's busy and window seconds and a
breakdown.  The last line of standard output is the result, one JSON
object; the numbers compared with their limits are its last key, and the
last lines of standard error.  With no TPU, with fewer chips than the
cell asks for, or with a device missing from ``peaks.json``, the harness
exits non-zero and prints no result.

The compile cache is the program's (``repro.serve.cache``):
``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import tracing  # noqa: E402
from peaks import UnknownDevice, peaks_for  # noqa: E402
from spec import CellError, load_cell  # noqa: E402

# lowering and XLA compile (or persistent-cache load): the seconds taken
# out of a route span.  Each program the process did not hold yet brings
# one lowering and one backend-compile event, whether XLA compiles it or
# it loads from the persistent cache
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (LOWERING, BACKEND_COMPILE)


class NoChip(Exception):
    """No TPU, or fewer chips than the cell asks for."""


class CompileClock:
    """Compile seconds, lowerings and backend compiles, from jax's own
    events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.lowerings = 0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == LOWERING:
            self.lowerings += 1
        if event == BACKEND_COMPILE:
            self.backend_compiles += 1


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""

    loop: Any
    setup_s: float
    window_start: float
    compiles: int            # backend compiles inside the window
    peak_bytes: Optional[int]
    peaks: Dict[str, Any]
    trace: Optional[tracing.Trace] = None
    trace_busy_s: float = 0.0
    trace_window_s: float = 0.0

    def trace_busy_in(self, span: str) -> float:
        """Device-busy nanoseconds inside the named harness spans."""
        return tracing.busy_inside(
            self.trace, tracing.span_intervals(self.trace, span))


def log(msg: str) -> None:
    print(msg, flush=True)


def device_or_raise(cell):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {devices[0].platform} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, jax found "
                     f"{len(devices)}")
    return devices


def _read(cell, metrics, r: Run) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in metrics:
        v = r.setup_s if m["name"] == "setup_s" else \
            cell.readers[m["name"]](r)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell, seed: int, seconds: float, traced: bool, *,
        require_tpu: bool = True) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object."""
    import jax
    if require_tpu:
        devices = device_or_raise(cell)
        peaks = peaks_for(devices[0].platform, devices[0].device_kind)
    else:
        devices = jax.devices()
        peaks = {}
    devices = devices[:cell.chips]
    from repro.serve.cache import init_persistent_cache
    cache_dir = init_persistent_cache()
    clock = CompileClock()
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; compile cache: {cache_dir}")

    loop = cell.loop.Loop(cell, seed, clock, log)
    loop.setup()
    setup_s = time.perf_counter() - PROCESS_START

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        # host spans are the harness's TraceAnnotations; the Python
        # tracer would add an event per Python call
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles0 = clock.backend_compiles
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        loop.window(seconds)
    t_end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    compiles = clock.backend_compiles - compiles0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices) if require_tpu else None
    log(f"window: attempted={loop.attempted} failed={loop.failed} "
        f"window_s={t_end - t0:.4f} compiles={compiles} "
        f"peak_bytes_in_use={peak} setup_s={setup_s:.4f}")

    # correctness, once the window has closed and the peak is read
    checks = loop.check()
    correct = loop.failed == 0 and loop.attempted > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    r = Run(loop=loop, setup_s=setup_s, window_start=t0, compiles=compiles,
            peak_bytes=peak, peaks=peaks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": loop.attempted,
                              "failed": loop.failed}
    if traced:
        tr = tracing.load(tracing.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        win = tracing.span_intervals(tr, "bench.window")
        lo, hi = (win[0] if win else (min(s for s, _, _ in tr.spans),
                                      max(e for _, e, _ in tr.spans)))
        busy = tracing.device_busy(tr, lo, hi)
        r.trace = tr
        r.trace_busy_s = (sum(busy.values()) / max(len(busy), 1)) / 1e9
        r.trace_window_s = (hi - lo) / 1e9
        device["busy_s"] = r.trace_busy_s
        device["window_s"] = r.trace_window_s
        metrics = _read(cell, cell.per_layer, r)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tracing.top_ops(tr, lo, hi)],
            "idle_gaps": [[n, s] for n, s in
                          tracing.idle_by_host_span(tr, lo, hi)]}
    else:
        metrics = _read(cell, cell.end_to_end, r)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        cell = load_cell(root, args.workload)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program (src/repro) is not in {root}",
              file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     require_tpu=require_tpu)
    except (NoChip, UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
