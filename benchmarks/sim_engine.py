"""Simulation-engine throughput: serial per-run loop vs the sweep runner.

Runs the same (scenarios x seeds) sweep three ways:

* ``serial``  — the pre-refactor pattern: one ``simulate`` call per point
  (jit-cached after the first, so this measures dispatch + per-run device
  work, not recompilation);
* ``batched`` — one ``repro.sim.sweep`` trace-mode sweep (the
  ``simulate_batch`` path): a single compiled program over the planned
  device mesh, full per-sample traces shipped to the host;
* ``batched_reduced`` — the fleet path: the same sweep with the on-device
  ``mean`` reduction (and ``--chunk-size N`` streaming chunks when
  given), so only per-run statistics ever cross the device/host boundary.

Timing is honest: the batched rows are timed end to end until the results
are *numpy arrays on the host* (so trace-mode pays for its transfer
volume and the reduced mode gets credit for avoiding it), and the serial
row ends with ``jax.block_until_ready`` on the raw device outputs.

Each row also reports the per-run ``lax.scan`` carry bytes (the quantity
bit-packing shrinks), the bytes shipped to the host
(``host_transfer_bytes`` — the quantity on-device reduction shrinks), and
the process peak RSS (where the platform has ``resource``). Results are
written to ``reports/bench/sim_engine.csv`` and, as JSON,
``reports/bench/sim_engine.json`` — compare against the checked-in
``BENCH_sim_engine.json`` baseline (``scripts/ci.sh --bench-smoke`` gates
on >30% throughput regression and on the transfer-bytes reduction).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

try:  # not available on every platform (e.g. Windows)
    import resource
except ImportError:  # pragma: no cover
    resource = None

import jax
import jax.numpy as jnp

from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, sweep
from repro.sim.engine import (
    _check_params, _run_single, dynamic_params, scan_carry_bytes,
)

from benchmarks.common import emit


def _peak_rss_mb() -> float | None:
    if resource is None:
        return None
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # ru_maxrss is kilobytes on Linux but bytes on macOS
    return rss / (1024.0 * 1024.0) if sys.platform == "darwin" else rss / 1024.0


def _carry_bytes_legacy(cfg: SimConfig, M: int) -> int:
    """Scan-carry bytes of the PR-1 layout (boolean masks, int32 queues)
    for the same config — the 'before' of the bit-packing optimization.

    Queue deltas come from the *actual* packed dtypes
    (``repro.sim.state.queue_dtypes``), not a hardcoded width."""
    from repro.sim.state import queue_dtypes

    n, k, qt, qm = cfg.n_nodes, cfg.k_obs, cfg.q_train, cfg.q_merge
    kw, nw = (k + 31) // 32, (n + 31) // 32
    id_dt, slot_dt = queue_dtypes(M, k)
    id_nbytes = jnp.dtype(id_dt).itemsize
    slot_nbytes = jnp.dtype(slot_dt).itemsize
    packed = scan_carry_bytes(cfg, M)
    return (
        packed
        + 2 * (n * M * k - n * M * kw * 4)   # inc, snap: bool -> words
        + (n * n - n * nw * 4)               # prev_close: bool -> words
        + (n * k - n * kw * 4)               # serv_mask:  bool -> words
        + (4 - id_nbytes) * n * (qt + qm)    # tq_model / mq_model
        + (4 - slot_nbytes) * n * qt         # tq_slot
    )


def run(quick: bool = False, chunk_size: int | None = None) -> list[dict]:
    lams = (0.02, 0.05, 0.1, 0.2) if quick else (
        0.02, 0.03, 0.05, 0.08, 0.1, 0.15, 0.2, 0.3,
    )
    seeds = tuple(range(4 if quick else 16))
    cfg = SimConfig(n_nodes=120, n_slots=600 if quick else 800,
                    sample_every=16)
    ps = [paper_params(lam=lam, M=1) for lam in lams]
    M = _check_params(ps)
    n_runs = len(ps) * len(seeds)
    total_slots = n_runs * cfg.n_slots
    carry_b = scan_carry_bytes(cfg, M)
    carry_legacy = _carry_bytes_legacy(cfg, M)

    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(list(seeds), jnp.uint32))
    p_dyns = [dynamic_params(p) for p in ps]

    def row(mode, wall, compile_s, host_bytes, devices_used):
        return dict(
            mode=mode, runs=n_runs, wall_s=round(wall, 3),
            slots_runs_per_s=round(total_slots / wall),
            compile_s=round(compile_s, 2),
            host_transfer_bytes=host_bytes,
            carry_bytes_per_run=carry_b,
            carry_bytes_legacy_layout=carry_legacy,
            n_devices=len(jax.devices()), devices_used=devices_used,
            peak_rss_mb=(None if (rss := _peak_rss_mb()) is None
                         else round(rss, 1)),
        )

    reps = 2 if quick else 4  # best-of-N: the timed region is short and
    #                           2-core hosts are noisy neighbors to their
    #                           own measurement

    # ---- serial loop (per-point jit-cached calls) ----
    t0 = time.time()
    jax.block_until_ready(_run_single(keys[0], p_dyns[0], cfg, M))  # compile
    serial_compile = time.time() - t0
    serial_s = float("inf")
    for _ in range(reps):  # same best-of-N sampling as the batched rows:
        #                    the CI gate compares their ratio
        t0 = time.time()
        for p_dyn in p_dyns:
            for k in keys:
                out = _run_single(k, p_dyn, cfg, M)
        jax.block_until_ready(out)
        serial_s = min(serial_s, time.time() - t0)

    # ---- sweep runner, full traces (the simulate_batch path) ----
    t0 = time.time()
    batch = sweep.run(ps, cfg, seeds, reduce="trace")   # compile
    trace_compile = time.time() - t0
    trace_s = float("inf")
    for _ in range(reps):
        t0 = time.time()
        batch = sweep.run(ps, cfg, seeds, reduce="trace")
        trace_s = min(trace_s, time.time() - t0)

    # ---- sweep runner, on-device mean reduction (+ optional chunks) ----
    t0 = time.time()
    red = sweep.run(ps, cfg, seeds, reduce="mean", chunk_size=chunk_size)
    red_compile = time.time() - t0
    red_s = float("inf")
    for _ in range(reps):
        t0 = time.time()
        red = sweep.run(ps, cfg, seeds, reduce="mean", chunk_size=chunk_size)
        red_s = min(red_s, time.time() - t0)

    return [
        row("serial", serial_s, serial_compile, None, 1),
        row("batched", trace_s, trace_compile, batch.host_bytes,
            batch.devices_used),
        row("batched_reduced", red_s, red_compile, red.host_bytes,
            red.devices_used),
    ]


def dispatch_rows(quick: bool = False,
                  chunk_size: int | None = None) -> list[dict]:
    """Dispatcher overhead: the reduced sweep through the lease-based
    multi-process queue (``workers=``) vs the in-process path.

    Workers are fresh processes, so without care the measurement is all
    XLA compilation: every run shares the persistent compile cache
    (``repro.launch.cache``) and a warm-up dispatch populates it first —
    after that a worker loads the compiled chunk program from the cache in
    well under a second, and the row measures queue + process overhead,
    which is the number the acceptance target bounds (workers=1 within 10%
    of in-process; workers=2 faster — *when the host has 2+ cores*; the
    rows record ``n_cores`` so the CI gate can tell). The dispatched runs
    come first: their workers need the device, which this process holds
    once its own in-process sweep has run.

    The workload is deliberately bigger than the throughput bench's: a
    dispatched study pays a fixed per-run cost (worker spawn + jax
    import, ~2.5 s) that only a study lasting tens of seconds — the kind
    worth dispatching at all — can amortize below the 10% target.
    ``quick`` trims repetitions, not the workload.
    """
    import shutil
    import tempfile

    lams = (0.02, 0.03, 0.05, 0.08, 0.1, 0.15, 0.2, 0.3)
    seeds = tuple(range(64))
    cfg = SimConfig(n_nodes=120, n_slots=1600, sample_every=16)
    ps = [paper_params(lam=lam, M=1) for lam in lams]
    cs = chunk_size if chunk_size is not None else max(len(ps) // 2, 1)
    kw = dict(reduce="mean", chunk_size=cs)
    reps = 1 if quick else 2
    n_runs = len(ps) * len(seeds)
    total_slots = n_runs * cfg.n_slots

    dispatched = {}
    work_root = tempfile.mkdtemp(prefix="fg-bench-dispatch-")
    try:
        for workers in (1, 2):
            best = float("inf")
            for rep in range(reps + 1):  # rep 0 warms the compile cache
                qd = os.path.join(work_root, f"q{workers}_{rep}")
                t0 = time.time()
                sweep.run(ps, cfg, seeds, **kw, workers=workers,
                          queue_dir=qd)
                wall = time.time() - t0
                if rep > 0:
                    best = min(best, wall)
                shutil.rmtree(qd, ignore_errors=True)
            dispatched[workers] = best
    finally:
        shutil.rmtree(work_root, ignore_errors=True)

    sweep.run(ps, cfg, seeds, **kw)  # compile the in-process program
    inproc_s = float("inf")
    for _ in range(reps):
        t0 = time.time()
        sweep.run(ps, cfg, seeds, **kw)
        inproc_s = min(inproc_s, time.time() - t0)

    n_devices = len(jax.devices())
    rows = [dict(mode="dispatch_inproc", workers=0, wall_s=round(inproc_s, 3),
                 slots_runs_per_s=round(total_slots / inproc_s),
                 overhead_pct=0.0, n_cores=os.cpu_count(),
                 n_devices=n_devices)]
    for workers, best in dispatched.items():
        rows.append(dict(
            mode=f"dispatch_workers_{workers}", workers=workers,
            wall_s=round(best, 3),
            slots_runs_per_s=round(total_slots / best),
            overhead_pct=round(100.0 * (best / inproc_s - 1.0), 1),
            n_cores=os.cpu_count(), n_devices=n_devices,
        ))
    return rows


def scaling(ns: list[int], n_slots: int = 48, reps: int = 2) -> list[dict]:
    """Per-slot step throughput vs N, dense vs cells backend, at fixed
    density (the paper geometry scaled so area grows as sqrt(N)).

    One row per (N, backend) with slots/s and the implied per-slot cost;
    the cells rows carry the dense speedup where both ran. The dense
    backend is skipped above ``_DENSE_MAX_N`` (its d² context alone is
    O(N²) floats — 1 GB at N = 16384). Written to
    ``reports/bench/sim_scaling.json``; ``scripts/ci.sh --bench-smoke``
    gates the N=4096 speedup, and the checked-in pr5 rows in
    ``BENCH_sim_engine.json`` come from ``--scaling`` on the reference
    host.
    """
    import dataclasses
    import math

    from repro.configs.fg_paper import DENSITY
    from repro.sim.engine import check_overflow
    from repro.sim.faults import FaultConfig

    _DENSE_MAX_N = 8192
    p = paper_params(lam=0.05, M=1)
    pd = dynamic_params(p)
    n_overhead = max(ns)  # zero-rate fault overhead probe at the top row
    rows = []
    for n in ns:
        area = math.sqrt(n / DENSITY)
        per_backend = {}
        for backend in ("dense", "cells"):
            if backend == "dense" and n > _DENSE_MAX_N:
                continue
            cfg = SimConfig(
                n_nodes=n, area_side=area, rz_radius=area / 2.0,
                n_slots=n_slots, sample_every=n_slots,
                contact_backend=backend,
            )
            key = jax.random.PRNGKey(0)
            t0 = time.time()
            out = jax.block_until_ready(_run_single(key, pd, cfg, 1))
            compile_s = time.time() - t0
            best = float("inf")
            for _ in range(reps):
                t0 = time.time()
                out = jax.block_until_ready(_run_single(key, pd, cfg, 1))
                best = min(best, time.time() - t0)
            ovf = out.get("nbr_overflow")
            max_ovf = None if ovf is None else int(ovf[-1])
            # degradation telemetry: a saturated neighbor list silently
            # drops contacts — surface it the same way simulate() does
            check_overflow(cfg, max_ovf,
                           context=f"scaling N={n} backend={backend}")
            overhead_pct = None
            if n == n_overhead and backend == "cells":
                # an all-zero-rates FaultConfig must trace the identical
                # program: the gate is Python-level, so the only cost
                # allowed is jit-cache noise (< 5%, CI-gated)
                cfg_f = dataclasses.replace(cfg, faults=FaultConfig())
                jax.block_until_ready(_run_single(key, pd, cfg_f, 1))
                best_f = float("inf")
                for _ in range(reps):
                    t0 = time.time()
                    jax.block_until_ready(_run_single(key, pd, cfg_f, 1))
                    best_f = min(best_f, time.time() - t0)
                overhead_pct = round(100.0 * (best_f / best - 1.0), 1)
            per_backend[backend] = n_slots / best
            rows.append(dict(
                n_nodes=n, backend=backend,
                slots_per_s=round(n_slots / best, 1),
                ms_per_slot=round(1e3 * best / n_slots, 2),
                compile_s=round(compile_s, 1),
                nbr_overflow=max_ovf,
                zero_fault_overhead_pct=overhead_pct,
                speedup_x=None,
            ))
        if "dense" in per_backend and "cells" in per_backend:
            rows[-1]["speedup_x"] = round(
                per_backend["cells"] / per_backend["dense"], 2
            )
    return rows


def main(quick: bool = False, chunk_size: int | None = None) -> None:
    t0 = time.time()
    rows = run(quick, chunk_size=chunk_size)
    serial = next(r for r in rows if r["mode"] == "serial")
    batched = next(r for r in rows if r["mode"] == "batched")
    reduced = next(r for r in rows if r["mode"] == "batched_reduced")
    speedup = serial["wall_s"] / reduced["wall_s"]
    transfer_x = batched["host_transfer_bytes"] / reduced["host_transfer_bytes"]
    emit("sim_engine", rows, t0,
         f"batched_speedup_x={speedup:.1f} transfer_reduction_x={transfer_x:.0f}")
    # carry reduction at figure scale: the masks grow with M, the queues
    # don't — fig. 4's M=25 is where packing pays the advertised >= 4x
    fig4_cfg = SimConfig(n_nodes=120, sample_every=16)
    mem = dict(
        bench_M1=dict(packed=rows[0]["carry_bytes_per_run"],
                      legacy=rows[0]["carry_bytes_legacy_layout"]),
        fig4_M25=dict(packed=scan_carry_bytes(fig4_cfg, 25),
                      legacy=_carry_bytes_legacy(fig4_cfg, 25)),
    )
    for entry in mem.values():
        entry["reduction_x"] = round(entry["legacy"] / entry["packed"], 2)
    transfer = dict(
        trace_bytes=batched["host_transfer_bytes"],
        reduced_bytes=reduced["host_transfer_bytes"],
        reduction_x=round(transfer_x, 1),
    )
    report_dir = os.path.join(os.path.dirname(__file__), "..", "reports",
                              "bench")
    os.makedirs(report_dir, exist_ok=True)
    with open(os.path.join(report_dir, "sim_engine.json"), "w") as f:
        json.dump(dict(quick=quick, chunk_size=chunk_size, rows=rows,
                       carry_bytes=mem, host_transfer=transfer), f, indent=2)


def main_dispatch(quick: bool = False,
                  chunk_size: int | None = None) -> None:
    t0 = time.time()
    rows = dispatch_rows(quick, chunk_size=chunk_size)
    w1 = next(r for r in rows if r["workers"] == 1)
    w2 = next(r for r in rows if r["workers"] == 2)
    emit("sim_dispatch", rows, t0,
         f"w1_overhead_pct={w1['overhead_pct']} "
         f"w2_overhead_pct={w2['overhead_pct']} cores={w1['n_cores']}")
    report_dir = os.path.join(os.path.dirname(__file__), "..", "reports",
                              "bench")
    os.makedirs(report_dir, exist_ok=True)
    with open(os.path.join(report_dir, "sim_dispatch.json"), "w") as f:
        json.dump(dict(quick=quick, rows=rows), f, indent=2)


def main_scaling(ns: list[int]) -> None:
    t0 = time.time()
    rows = scaling(ns)
    by_n = {}
    for r in rows:
        if r["speedup_x"] is not None:
            by_n[r["n_nodes"]] = r["speedup_x"]
    emit("sim_scaling", rows, t0,
         " ".join(f"N{n}_cells_over_dense={x}x" for n, x in by_n.items()))
    report_dir = os.path.join(os.path.dirname(__file__), "..", "reports",
                              "bench")
    os.makedirs(report_dir, exist_ok=True)
    with open(os.path.join(report_dir, "sim_scaling.json"), "w") as f:
        json.dump(dict(rows=rows, n_devices=len(jax.devices())), f, indent=2)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="scenarios per dispatched chunk (streaming path)")
    ap.add_argument("--scaling", default=None,
                    help="comma-separated N list: time the dense vs cells "
                         "contact backends at fixed density instead of "
                         "running the sweep benchmark")
    ap.add_argument("--dispatch", action="store_true",
                    help="time the multi-process dispatcher (workers=1, 2) "
                         "against the in-process reduced sweep instead of "
                         "running the sweep benchmark")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.scaling:
        main_scaling([int(x) for x in args.scaling.split(",")])
    elif args.dispatch:
        main_dispatch(quick=args.quick, chunk_size=args.chunk_size)
    else:
        main(quick=args.quick, chunk_size=args.chunk_size)
