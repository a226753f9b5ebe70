"""Run one cell of the benchmark once and print its result line.

A run: find the chips, warm up (every program the cell's calls use is
compiled, or loaded from the persistent cache, here), then call the system
under test for ``--seconds`` seconds of wall time, starting no call after
that, and report the work of the calls finished over the window's length.
With ``--trace 1`` the window runs under the profiler, and the result
carries the cell's per-layer metrics instead of its end-to-end ones. After
the window, with the device's peak memory read, the reference recomputes a
seeded sample of the window's outputs, and ``correct`` says whether every
gap stayed within its limit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

from bench import drivers

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(entries, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def plan(workload: str, root: str = ROOT) -> dict:
    """Everything one cell's run needs, found by name from BENCHMARK.json:
    the cell, its configuration and traffic files, and its metrics."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, configs[cell["config"]]
                                          ["file"])),
        "traffic": load_json(os.path.join(BENCH, "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": _for_cell(spec["end_to_end"], workload),
        "per_layer": _for_cell(spec["per_layer"], workload),
    }


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it:
    ``$JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``
    (``repro.launch.cache``)."""
    from repro.launch.cache import enable_compile_cache as enable

    enable()


class CompileClock:
    """Counts XLA/Mosaic backend compilations (JAX's own monitoring
    event), so that a compile inside the window shows."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0

        def listener(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listener)


def find_devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader (``bench/layers/<metric>.py``) reads."""

    trace: object            # trace_reduce.TraceSummary of the window
    config: dict             # the configuration with the traffic's run
    traffic: dict
    device_kind: str
    calls: int               # timed calls finished in the traced window
    slots_per_call: int      # scan slots each call runs (per run)
    runs_per_call: int

    @property
    def slots(self) -> int:
        return self.calls * self.slots_per_call

    @property
    def run_slots(self) -> int:
        return self.slots * self.runs_per_call


def read_layers(metrics: list, ctx: LayerContext) -> dict:
    out = {}
    for m in metrics:
        path = os.path.join(BENCH, "layers", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_layer_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(p: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True) -> dict:
    """One run of the cell ``p`` (see :func:`plan`); returns the result
    line. Raises :class:`NoChip` before any work where the chips are
    missing."""
    import jax

    devs = find_devices(p["cell"]["chips"], require_tpu)
    t_devs = time.perf_counter() - t_start
    enable_compile_cache()
    clock = CompileClock()
    cfg, traffic = p["config"], p["traffic"]
    drv = drivers.make(cfg, traffic, seed)
    t_warm = time.perf_counter()
    drv.warm()
    setup_s = time.perf_counter() - t_start
    _note(f"setup {setup_s:.3f} s: chips found at {t_devs:.3f} s, warm-up "
          f"call {time.perf_counter() - t_warm:.3f} s ({clock.count} "
          f"compiles, {clock.seconds:.3f} s)")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    compiles0 = clock.count
    attempted = failed = 0
    work = 0.0
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while attempted == 0 or time.perf_counter() - t0 < seconds:
                attempted += 1
                try:
                    with jax.profiler.TraceAnnotation("bench.call"):
                        work += drv.call(attempted - 1)
                except Exception:  # noqa: BLE001 - a failed call is counted
                    traceback.print_exc()
                    failed += 1
            window_s = time.perf_counter() - t0
    finally:
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            _note(f"trace stopped in {time.perf_counter() - t_stop:.3f} s")
    in_window = clock.count - compiles0
    _note(f"window {window_s:.3f} s, {attempted} calls, {failed} failed, "
          f"{in_window} compiles inside")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        from bench import trace_reduce

        t_read = time.perf_counter()
        try:
            summary = trace_reduce.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        _note(f"trace read in {time.perf_counter() - t_read:.3f} s")
        ctx = LayerContext(
            trace=summary, config=drv.cfg, traffic=traffic,
            device_kind=devs[0].device_kind, calls=len(drv.done),
            slots_per_call=drv.cfg["n_slots"],
            runs_per_call=drv.runs_per_call)
        result["metrics"] = read_layers(p["per_layer"], ctx)
        device.update(busy_s=summary.mean_busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    else:
        values = {"setup_s": setup_s,
                  traffic["metric"]: work / window_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in p["end_to_end"]}
    result["device"] = device

    t_ref = time.perf_counter()
    gaps = drv.check() if drv.done else {}
    _note(f"reference check {time.perf_counter() - t_ref:.3f} s over "
          f"{len(drv.done)} calls")
    limits = traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}
    result["correct"] = (failed == 0 and bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["checks"] = checks
    for k, c in checks.items():
        _note(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result
