"""Fleet-scale sweep execution for the Monte-Carlo engine.

This module replaces the ad-hoc scenario-axis SPMD that used to live in
``engine.simulate_batch`` with a sweep-execution subsystem built from
three pieces:

**Flattened (scenario x seed) work axis.** A sweep is a grid of
``n_scenarios x n_seeds`` independent work items. The planner
(:func:`plan_sweep`) factorizes the visible device count over *both* grid
axes — picking the factorization that minimizes padded work — so uneven
grids (``n_scenarios % n_devices != 0``) and seed-heavy sweeps (many
seeds, few scenarios) parallelize instead of silently falling back to one
device. Both axes are padded with repeats of their last row (work items
are independent SPMD rows, so pad items change nothing and are sliced
off) and sharded over a 2-D device mesh (``jax.make_mesh``, Auto axes);
partition specs come from the ``sweep_scenario`` / ``sweep_seed`` logical
axes in ``repro.sharding.logical.SWEEP_RULES``. The (scenario, seed)
*structure* of each device block is deliberately preserved rather than
physically flattened to one axis: everything in the per-slot program that depends
only on the per-seed PRNG chain — mobility, RZ membership, the O(N²)
distance matrix, observer scores — is computed once per seed and
broadcast across the scenario axis by ``vmap``; a physically flattened
axis re-computes all of it per work item (measured ~25% slower at paper
scale).

**Streaming chunked execution.** Large grids run as a stream of
fixed-shape chunks along the scenario axis. Chunk inputs are donated
(``jit(..., donate_argnums=...)``), letting XLA reuse their buffers for
the scan carry and outputs of the same dispatch, and the runner is
double-buffered: chunk ``k+1`` is dispatched before chunk ``k``'s outputs
are materialized on the host, so host transfers and result assembly
overlap device compute. Device memory stays flat in the grid size —
only one chunk's traces (plus the in-flight chunk) ever exist on device.

**On-device sweep reductions.** For figure-sized parameter studies the
full per-slot trace is rarely wanted — its host transfer dominates the
sweep at scale. ``reduce="mean" | "final" | "quantiles"`` reduce each
run's trace over the (post-warmup) sample axis *inside* the compiled
program and ship only the reduced statistics (a few scalars per run
instead of the whole ``(runs, samples, ...)`` trace, >100x fewer bytes at
paper scale); the per-observation traces (``obs_birth``/``obs_holders``,
needed only by the o(τ) estimator) are skipped entirely on this path.
``reduce="trace"`` returns the full :class:`~repro.sim.engine.
BatchSimOutputs` and is **bitwise identical** to the historical
``simulate_batch`` — pinned by ``tests/test_sim_sweep.py`` against the
unsharded nested-vmap reference, chunked or not, sharded or not.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import time
import warnings
from functools import lru_cache
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.meanfield import FGParams
from repro.sharding.logical import SWEEP_RULES, spec_for
from repro.sim.compute import take_paths
from repro.sim.engine import (
    BatchSimOutputs, SimConfig, _check_params, _run, _sample_times,
    stack_dynamic_params, take_reads,
)

__all__ = ["SweepPlan", "SweepSummary", "plan_sweep", "run", "REDUCERS"]

#: Valid ``reduce=`` modes: "trace" ships the full per-sample trace
#: (bitwise the historical ``simulate_batch``); the others reduce on
#: device over the post-warmup sample axis and ship only statistics —
#: "o_tau" accumulates the o(τ) holder-fraction age histograms
#: (``observations.o_tau_histograms``) so the one consumer that used to
#: need the full per-observation trace on the host no longer does.
REDUCERS = ("trace", "mean", "final", "quantiles", "o_tau")

#: Quantities present in the light (reduced) trace, reduced per run over
#: the sample axis. The ``*_z`` entries are the per-zone traces (trailing
#: zone axis — K_zones = 1 for the legacy single-RZ geometry); reductions
#: apply over the sample axis only, so every reduced statistic keeps its
#: zone axis.
_LIGHT_KEYS = ("availability", "busy_frac", "stored", "model_holders",
               "n_in_rz", "availability_z", "stored_z", "n_in_rz_z")

#: Fault-layer degradation telemetry (present only when ``cfg.faults`` is
#: an enabled FaultConfig; trailing class axis C). Reduced like the light
#: keys; the cumulative ``fault_events`` counter rides every reduction as
#: its final sample, like ``nbr_overflow``.
_FAULT_KEYS = ("availability_c", "on_frac_c", "n_in_rz_c")

#: Gossip-learning telemetry (present only when ``cfg.learn`` is an
#: enabled LearnConfig; per-sample scalars except the per-class
#: contamination split — trailing class axis — which, with
#: ``poisoned_frac``, is present only under an adversarial FaultConfig).
#: Reduced like the light keys on every reduction mode; the cumulative
#: ``merge_stats`` screen counters ride every reduction as their final
#: sample, like ``fault_events``.
_LEARN_KEYS = ("test_acc", "test_acc_holders", "learn_obs", "theta_var",
               "poisoned_frac", "poisoned_frac_c")


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """Placement of a (scenarios x seeds) grid onto a device mesh.

    ``mesh_shape = (d_scen, d_seed)`` multiplies to the device count; the
    grid axes are padded to ``pad_scenarios`` / ``pad_seeds`` (multiples
    of the respective mesh axis) and the scenario axis streams in
    ``n_chunks`` dispatches of ``chunk_scenarios`` each."""

    n_scenarios: int
    n_seeds: int
    n_devices: int
    mesh_shape: tuple[int, int]
    pad_scenarios: int
    pad_seeds: int
    chunk_scenarios: int

    @property
    def n_chunks(self) -> int:
        return self.pad_scenarios // self.chunk_scenarios

    @property
    def padded_runs(self) -> int:
        return self.pad_scenarios * self.pad_seeds

    @property
    def utilization(self) -> float:
        """Real work items / padded work items (1.0 = no padding waste)."""
        return self.n_scenarios * self.n_seeds / self.padded_runs


def plan_sweep(
    n_scenarios: int,
    n_seeds: int,
    n_devices: int | None = None,
    chunk_size: int | None = None,
) -> SweepPlan:
    """Factorize the device count over the (scenario, seed) grid.

    Every divisor pair ``(d_scen, d_seed)`` of ``n_devices`` is scored by
    the padded work it implies (each grid axis rounds up to a multiple of
    its mesh axis); the minimum wins, ties preferring scenario-axis
    sharding (the historical layout, and the axis chunking streams along).
    A 3x5 grid on 2 devices therefore shards the *seed* axis (15 -> 18
    padded runs) instead of the scenario axis (-> 20) — and instead of not
    sharding at all, as the pre-sweep engine did when the scenario count
    did not divide the device count.

    ``chunk_size`` is the number of *scenarios* per dispatched chunk
    (rounded up to a multiple of ``d_scen``); ``None`` means a single
    dispatch. The scenario axis additionally pads up to a multiple of the
    chunk so every dispatch shares one compiled shape.
    """
    if n_devices is None:
        n_devices = len(jax.devices())
    if n_scenarios < 1 or n_seeds < 1:
        raise ValueError("empty sweep grid")

    best = None
    for d_scen in range(n_devices, 0, -1):
        if n_devices % d_scen:
            continue
        d_seed = n_devices // d_scen
        pad_p = -(-n_scenarios // d_scen) * d_scen
        pad_r = -(-n_seeds // d_seed) * d_seed
        cost = pad_p * pad_r
        # strict < keeps the largest d_scen (first seen) on ties
        if best is None or cost < best[0]:
            best = (cost, d_scen, d_seed, pad_p, pad_r)
    _, d_scen, d_seed, pad_p, pad_r = best

    if chunk_size is None:
        chunk_p = pad_p
    else:
        chunk_p = max(1, min(chunk_size, pad_p))
        chunk_p = -(-chunk_p // d_scen) * d_scen
        pad_p = -(-pad_p // chunk_p) * chunk_p
    return SweepPlan(
        n_scenarios=n_scenarios, n_seeds=n_seeds, n_devices=n_devices,
        mesh_shape=(d_scen, d_seed), pad_scenarios=pad_p, pad_seeds=pad_r,
        chunk_scenarios=chunk_p,
    )


@dataclasses.dataclass
class SweepSummary:
    """On-device-reduced sweep result.

    ``stats`` maps each light-trace quantity to an array with leading
    (scenario, seed) axes: time-means (+ ``*_std``) for ``reduce="mean"``,
    the last sample for ``"final"``, and a trailing quantile axis for
    ``"quantiles"`` (scalar quantities: ``(scen, seed, Q)``; per-model
    quantities: ``(scen, seed, M, Q)``). ``host_bytes`` counts the bytes
    actually materialized from device — padded chunk outputs included —
    the number the transfer-reduction benchmark column tracks.

    Partial completion is *labeled*, never silent: ``coverage`` is an
    (n_scenarios,) bool mask — ``True`` where the scenario's chunk actually
    computed, ``False`` where its rows are NaN/zero fill (chunks are slices
    of the scenario axis, so the chunk → row mapping is exact). The
    uncovered chunk indices are in ``failed_chunks`` (exhausted their
    :class:`~repro.sim.dispatch.RetryPolicy` attempts) and, for dispatched
    sweeps, ``quarantined`` carries the poison chunks whose quarantine
    records (worker tracebacks, attempt history) live in
    ``telemetry["quarantine"]``. ``telemetry`` also holds per-chunk
    attempt/latency/requeue counters (see
    :func:`repro.sim.dispatch.run_dispatched`).
    """

    reduce: str
    t: np.ndarray
    warmup_samples: int
    stats: dict[str, np.ndarray]
    plan: SweepPlan
    devices_used: int
    host_bytes: int
    quantiles: tuple[float, ...] | None = None
    failed_chunks: tuple[int, ...] = ()   # chunk indices that exhausted
                                          # their retries (NaN/zero-filled)
    coverage: np.ndarray | None = None    # (n_scenarios,) bool completion
    quarantined: tuple[int, ...] = ()     # poison chunks (dispatch path)
    telemetry: dict | None = None         # attempts/latency/requeue records


def _reduce_outs(outs: dict, reduce: str, s0: int, qs, tau, t) -> dict:
    """Per-run on-device reduction over the sample axis (axis 2)."""
    keys = _LIGHT_KEYS + tuple(
        k for k in _FAULT_KEYS + _LEARN_KEYS if k in outs
    )
    if reduce == "o_tau":
        from repro.sim.observations import o_tau_histograms

        n_tau, dtau = tau
        num, den = o_tau_histograms(
            t=t[s0:],
            obs_birth=outs["obs_birth"][:, :, s0:],
            obs_holders=outs["obs_holders"][:, :, s0:].astype(jnp.float32),
            model_holders=outs["model_holders"][:, :, s0:].astype(
                jnp.float32),
            n_tau=n_tau, dtau=dtau,
        )
        red = {"o_tau_num": num, "o_tau_den": den}
        # the fault telemetry rides the o_tau reduction as final samples
        for k in keys[len(_LIGHT_KEYS):]:
            red[k] = outs[k][:, :, -1]
    elif reduce == "mean":
        red = {}
        for k in keys:
            v = outs[k][:, :, s0:]
            red[k] = jnp.mean(v, axis=2)
            red[k + "_std"] = jnp.std(v, axis=2)
    elif reduce == "final":
        red = {k: outs[k][:, :, -1] for k in keys}
    elif reduce == "quantiles":
        q = jnp.asarray(qs, jnp.float32)
        # quantile levels land on the TRAILING axis for every quantity,
        # scalar (scen, seed, Q) and vector (scen, seed, M, Q) alike
        red = {
            k: jnp.moveaxis(
                jnp.quantile(outs[k][:, :, s0:], q, axis=2), 0, -1
            )
            for k in keys
        }
    else:
        raise ValueError(f"unknown reduce mode {reduce!r}; known: {REDUCERS}")
    if "nbr_overflow" in outs:
        # cells contact backend: the running overflow max — its final
        # sample is the whole-run diagnostic — rides every reduction
        red["nbr_overflow"] = outs["nbr_overflow"][:, :, -1]
    if "fault_events" in outs:
        # cumulative abort/link-fail/crash counters: final sample = run
        red["fault_events"] = outs["fault_events"][:, :, -1]
    if "merge_stats" in outs:
        # cumulative merge-screen counters (learning layer): same rule
        red["merge_stats"] = outs["merge_stats"][:, :, -1]
    return red


def _worker_fn(cfg: SimConfig, M: int, reduce: str, s0: int, qs: tuple,
               tau: tuple):
    """The pure (uncompiled) per-chunk program — also what
    ``_SweepSetup.expected_shapes`` abstract-evals, so the result schema
    is a property of the sweep definition, not of a compiled executable."""
    # o_tau consumes the per-observation traces, so it runs the full
    # engine trace — but reduces it on device like the light modes
    trace = "full" if reduce in ("trace", "o_tau") else "light"
    t_const = jnp.asarray(_sample_times(cfg), jnp.float32)

    def worker(keys, p_chunk):
        over_seeds = jax.vmap(
            lambda k, pd: _run(k, pd, cfg, M, trace=trace),
            in_axes=(0, None),
        )
        outs = jax.vmap(over_seeds, in_axes=(None, 0))(keys, p_chunk)
        if reduce == "trace":
            return outs
        with jax.named_scope("fg.reduce"):
            return _reduce_outs(outs, reduce, s0, qs, tau, t_const)

    return worker


@lru_cache(maxsize=None)
def _chunk_worker(cfg: SimConfig, M: int, plan: SweepPlan, reduce: str,
                  s0: int, qs: tuple, tau: tuple, p_keys: tuple):
    """Compiled per-chunk runner, cached per (config, plan, reduction).

    Inputs are sharded over the plan's 2-D mesh via the ``sweep_scenario``
    / ``sweep_seed`` logical axes and the per-chunk parameter buffers are
    donated — each chunk's arrays are dead after its dispatch, so XLA may
    reuse their memory for the scan carry and outputs of the same step.
    The program runs under ``shard_map``: every (scenario, seed) run is
    independent, so each device runs the per-run program on its own block
    of the grid — and the Pallas kernels inside it, which the compiler
    cannot partition, see device-local shapes.
    """
    mesh = jax.make_mesh(plan.mesh_shape, ("sweep_scenario", "sweep_seed"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    chunk_p, pad_r = plan.chunk_scenarios, plan.pad_seeds
    scen_spec = spec_for(mesh, ("sweep_scenario",), (chunk_p,), SWEEP_RULES)
    seed_spec = spec_for(mesh, ("sweep_seed", None), (pad_r, 2), SWEEP_RULES)
    # every output leads with the (scenario, seed) grid axes
    out_spec = jax.sharding.PartitionSpec(scen_spec[0], seed_spec[0])
    body = jax.shard_map(
        _worker_fn(cfg, M, reduce, s0, qs, tau), mesh=mesh,
        in_specs=(seed_spec, {k: scen_spec for k in p_keys}),
        out_specs=out_spec, check_vma=False,
    )

    return jax.jit(
        body,
        in_shardings=(
            jax.sharding.NamedSharding(mesh, seed_spec),
            {k: jax.sharding.NamedSharding(mesh, scen_spec) for k in p_keys},
        ),
        donate_argnums=(1,),
    )


def _pad_rows(arr: np.ndarray, to: int) -> np.ndarray:
    pad = to - arr.shape[0]
    if pad == 0:
        return arr
    return np.concatenate([arr, np.repeat(arr[-1:], pad, axis=0)])


def _sweep_fingerprint(cfg, M, plan, reduce, s0, qs, tau, seeds,
                       p_stack) -> str:
    """Content hash of everything that determines a sweep's results.

    A checkpoint chunk is only reusable when the whole (config, grid,
    plan, reduction, seeds, parameter stack) quintuple matches — the hash
    covers the static reprs plus the exact parameter bytes."""
    h = hashlib.sha256()
    h.update(repr(
        (cfg, M, plan, reduce, s0, qs, tau, tuple(int(s) for s in seeds))
    ).encode())
    for k in sorted(p_stack):
        h.update(k.encode())
        h.update(np.asarray(p_stack[k]).tobytes())
    return h.hexdigest()


def _fp_array(fp: str) -> np.ndarray:
    return np.frombuffer(bytes.fromhex(fp), dtype=np.uint8)


def _tree_mismatch(tree: dict, expected: dict | None) -> str | None:
    """Why ``tree`` cannot be this sweep's chunk result (None = it can):
    missing/extra quantities or shape/dtype drift against the worker's
    ``eval_shape`` output — the checks that turn a stale or torn chunk
    file into a recompute instead of a crash (or worse, silent bad data).
    """
    if expected is None:
        return None
    missing = sorted(set(expected) - set(tree))
    extra = sorted(set(tree) - set(expected))
    if missing or extra:
        return f"key mismatch (missing {missing}, unexpected {extra})"
    for k, s in expected.items():
        arr = np.asarray(tree[k])
        if tuple(arr.shape) != tuple(s.shape):
            return (f"shape mismatch for {k!r}: file has {arr.shape}, "
                    f"sweep expects {tuple(s.shape)}")
        if arr.dtype != s.dtype:
            return (f"dtype mismatch for {k!r}: file has {arr.dtype}, "
                    f"sweep expects {np.dtype(s.dtype)}")
    return None


def _load_chunks(directory: str, fp: str, n_chunks: int,
                 expected: dict | None = None) -> dict[int, dict]:
    """Completed chunk reductions from ``directory`` whose fingerprint
    matches ``fp``. Defensive by construction: mismatched, truncated,
    corrupt, or shape-drifted files are *skipped with a warning naming the
    chunk and the reason* and their chunk recomputes — a torn write from a
    preempted run (or a worker killed mid-save) can never crash a resume
    nor leak bad arrays into the reductions. ``expected`` (quantity name →
    ``ShapeDtypeStruct`` from the worker's ``eval_shape``) arms the
    shape/dtype validation; content hashes in the manifest (files written
    with ``integrity=True``) are verified where present."""
    from repro.checkpoint.ckpt import restore_checkpoint

    done: dict[int, dict] = {}
    if not os.path.isdir(directory):
        return done
    for name in sorted(os.listdir(directory)):
        if not (name.startswith("step_") and name.endswith(".npz")):
            continue
        path = os.path.join(directory, name)
        chunk_id = name[len("step_"):-len(".npz")].lstrip("0") or "0"
        try:
            like = {k: 0 for k in np.load(path).files}
            tree, step = restore_checkpoint(path, like, verify=True)
        except Exception as e:
            warnings.warn(
                f"skipping sweep checkpoint chunk {chunk_id} ({path}): "
                f"unreadable or corrupt ({e}); recomputing"
            )
            continue
        saved_fp = tree.pop("fingerprint", None)
        if (saved_fp is None
                or not np.array_equal(saved_fp, _fp_array(fp))
                or not 0 <= step < n_chunks):
            warnings.warn(
                f"skipping sweep checkpoint {path}: fingerprint/plan "
                "mismatch (different sweep)"
            )
            continue
        reason = _tree_mismatch(tree, expected)
        if reason is not None:
            warnings.warn(
                f"skipping sweep checkpoint chunk {chunk_id} ({path}): "
                f"{reason}; recomputing"
            )
            continue
        done[step] = tree
    return done


def _fill_chunk(expected: dict) -> dict:
    """Host-side stand-in for a chunk that never completed: NaN-filled
    floats / zero-filled ints at the worker's exact output shapes
    (``expected`` from ``eval_shape`` — nothing runs). Always paired with
    a ``False`` stretch in the coverage mask, so the fill is labeled."""

    def fill(s):
        if np.issubdtype(s.dtype, np.floating):
            return np.full(s.shape, np.nan, s.dtype)
        return np.zeros(s.shape, s.dtype)

    return {k: fill(s) for k, s in expected.items()}


@dataclasses.dataclass
class _SweepSetup:
    """Everything ``run`` and the dispatch workers/coordinator share: the
    normalized sweep definition plus the derived compile-cache keys. Built
    once by :func:`_prepare`; the dispatcher pickles the *inputs* (ps, cfg,
    seeds, knobs) and each worker rebuilds this identically, so every
    process compiles the same chunk program and produces bitwise-identical
    results. It holds host data only (the PRNG keys are made on demand by
    :meth:`keys`), so a worker can hand it to the dispatch coordinator,
    which never touches a device."""

    cfg: SimConfig
    M: int
    plan: SweepPlan
    reduce: str
    quantiles: tuple
    s0: int                # warmup samples (reporting)
    key_s0: int            # normalized compile-cache keys: only what the
    key_qs: tuple          # chosen reduction actually reads
    key_tau: tuple
    p_keys: tuple
    p_stack: dict          # padded parameter stack (scenario axis)
    seeds: tuple           # padded seeds (seed axis)

    def keys(self) -> jnp.ndarray:
        """The padded seed axis as PRNG keys (the setup's one device op)."""
        return jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(self.seeds, jnp.uint32))

    def worker(self):
        return _chunk_worker(self.cfg, self.M, self.plan, self.reduce,
                             self.key_s0, self.key_qs, self.key_tau,
                             self.p_keys)

    def chunk_params(self, c: int) -> dict:
        cp = self.plan.chunk_scenarios
        return {k: v[c * cp:(c + 1) * cp] for k, v in self.p_stack.items()}

    def expected_shapes(self) -> dict:
        """Quantity name -> ``ShapeDtypeStruct`` of one chunk's host
        result. Abstract-evals the *uncompiled* chunk program — nothing
        compiles, runs, or touches the jit cache."""
        fn = _worker_fn(self.cfg, self.M, self.reduce, self.key_s0,
                        self.key_qs, self.key_tau)
        return dict(jax.eval_shape(fn, self.keys(), self.chunk_params(0)))


def _validate(ps, reduce, tau_grid) -> tuple[list, int, tuple]:
    """Check a sweep definition: ``(scenarios, M, key_tau)``. Needs no
    device, so the dispatch coordinator runs it before any worker starts."""
    if isinstance(ps, FGParams):
        ps = [ps]
    if reduce not in REDUCERS:
        raise ValueError(f"unknown reduce mode {reduce!r}; known: {REDUCERS}")
    M = _check_params(ps)
    key_tau = ()
    if reduce == "o_tau":
        if tau_grid is None:
            raise ValueError('reduce="o_tau" needs a tau_grid')
        tau_grid = np.asarray(tau_grid, np.float64)
        dtaus = np.diff(tau_grid)
        if len(tau_grid) < 2 or not np.allclose(dtaus, dtaus[0]):
            raise ValueError("tau_grid must be a uniform grid")
        key_tau = (len(tau_grid), float(tau_grid[1] - tau_grid[0]))
    return list(ps), M, key_tau


def _prepare(ps, cfg, seeds, reduce, warmup_frac, chunk_size, quantiles,
             tau_grid, n_devices) -> _SweepSetup:
    """Validate and normalize a sweep definition into a :class:`_SweepSetup`.

    Touches JAX only to count devices when ``n_devices`` is None."""
    ps, M, key_tau = _validate(ps, reduce, tau_grid)
    plan = plan_sweep(len(ps), len(seeds), n_devices=n_devices,
                      chunk_size=chunk_size)

    p_stack = {
        k: _pad_rows(v, plan.pad_scenarios)
        for k, v in stack_dynamic_params(ps).items()
    }
    seeds = [int(s) for s in seeds]
    seeds += seeds[-1:] * (plan.pad_seeds - len(seeds))

    n_samples = cfg.n_slots // cfg.sample_every
    wf = cfg.warmup_frac if warmup_frac is None else warmup_frac
    s0 = min(int(n_samples * wf), n_samples - 1)
    # normalize the compile-cache key to what the reduction actually
    # reads: trace/final ignore the warmup index, only quantiles reads
    # the quantile levels, only o_tau reads the age grid — so varying
    # the unused knobs can't trigger a spurious recompilation
    key_s0 = s0 if reduce in ("mean", "quantiles", "o_tau") else 0
    key_qs = tuple(quantiles) if reduce == "quantiles" else ()
    return _SweepSetup(
        cfg=cfg, M=M, plan=plan, reduce=reduce, quantiles=tuple(quantiles),
        s0=s0, key_s0=key_s0, key_qs=key_qs, key_tau=key_tau,
        p_keys=tuple(sorted(p_stack)), p_stack=p_stack, seeds=tuple(seeds),
    )


def _setup_fingerprint(setup: _SweepSetup, seeds) -> str:
    return _sweep_fingerprint(
        setup.cfg, setup.M, setup.plan, setup.reduce, setup.key_s0,
        setup.key_qs, setup.key_tau, seeds, setup.p_stack,
    )


def _coverage_mask(plan: SweepPlan, uncovered: Sequence[int]) -> np.ndarray:
    """(n_scenarios,) bool: ``False`` exactly on the scenario rows of the
    chunks in ``uncovered`` (chunks slice the scenario axis, so the
    chunk → row mapping is exact; pad rows fall off the end)."""
    cov = np.ones((plan.n_scenarios,), bool)
    cp = plan.chunk_scenarios
    for c in uncovered:
        cov[c * cp:(c + 1) * cp] = False
    return cov


def _finalize(setup: _SweepSetup, host_chunks: list, *, devices_used: int,
              failed: Sequence[int] = (), quarantined: Sequence[int] = (),
              telemetry: dict | None = None):
    """Assemble chunk results (host dicts, in chunk order) into the sweep's
    return value — shared by the in-process runner and the dispatcher, so
    both produce byte-for-byte the same ``BatchSimOutputs``/``SweepSummary``
    from the same chunk reductions."""
    plan, cfg, reduce = setup.plan, setup.cfg, setup.reduce
    failed = tuple(sorted(failed))
    quarantined = tuple(sorted(quarantined))
    P, R = plan.n_scenarios, plan.n_seeds
    # what actually crossed the device/host boundary: the materialized
    # (padded) chunks, before the pad rows are sliced off
    host_bytes = sum(
        v.nbytes for hc in host_chunks for v in hc.values()
    )
    outs = {
        k: np.concatenate([hc[k] for hc in host_chunks])[:P, :R]
        for k in host_chunks[0]
    }
    t = _sample_times(cfg)
    coverage = _coverage_mask(plan, failed)

    if failed:
        warnings.warn(
            f"{len(failed)} sweep chunk(s) failed after retry and were "
            f"NaN/zero-filled: {list(failed)} (see SweepSummary.coverage)"
        )
    if "nbr_overflow" in outs:
        from repro.sim.engine import check_overflow

        # uncovered chunks are zero-filled — they can't trip the gate
        check_overflow(cfg, outs["nbr_overflow"], context="sweep")

    if reduce == "trace":
        return BatchSimOutputs(
            t=t,
            availability=outs["availability"],
            busy_frac=outs["busy_frac"],
            stored_info=outs["stored"],
            obs_birth=outs["obs_birth"],
            obs_holders=outs["obs_holders"],
            model_holders=outs["model_holders"],
            n_in_rz=outs["n_in_rz"],
            availability_z=outs["availability_z"],
            stored_info_z=outs["stored_z"],
            n_in_rz_z=outs["n_in_rz_z"],
            nbr_overflow=outs.get("nbr_overflow"),
            availability_c=outs.get("availability_c"),
            on_frac_c=outs.get("on_frac_c"),
            n_in_rz_c=outs.get("n_in_rz_c"),
            fault_events=outs.get("fault_events"),
            test_acc=outs.get("test_acc"),
            test_acc_holders=outs.get("test_acc_holders"),
            learn_obs=outs.get("learn_obs"),
            theta_var=outs.get("theta_var"),
            merge_stats=outs.get("merge_stats"),
            poisoned_frac=outs.get("poisoned_frac"),
            poisoned_frac_c=outs.get("poisoned_frac_c"),
            plan=plan, devices_used=devices_used, host_bytes=host_bytes,
            failed_chunks=failed, coverage=coverage,
            quarantined=quarantined, telemetry=telemetry,
        )
    if reduce == "o_tau":
        # the ratio is host-side arithmetic on the shipped histograms
        num, den = outs["o_tau_num"], outs["o_tau_den"]
        outs["o_tau"] = np.where(den > 0, num / np.maximum(den, 1), np.nan)
    return SweepSummary(
        reduce=reduce, t=t, warmup_samples=setup.s0, stats=outs, plan=plan,
        devices_used=devices_used, host_bytes=host_bytes,
        quantiles=setup.quantiles if reduce == "quantiles" else None,
        failed_chunks=failed, coverage=coverage, quarantined=quarantined,
        telemetry=telemetry,
    )


def run(
    ps: Sequence[FGParams] | FGParams,
    cfg: SimConfig,
    seeds: Sequence[int] = (0,),
    *,
    reduce: str = "trace",
    warmup_frac: float | None = None,
    chunk_size: int | None = None,
    quantiles: Sequence[float] = (0.1, 0.5, 0.9),
    tau_grid=None,
    n_devices: int | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    retry_policy=None,
    workers: int | None = None,
    queue_dir: str | None = None,
    xla_cache_dir: str | None = None,
):
    """Execute a (scenarios x seeds) sweep on the planned device mesh.

    Args:
      ps:         one ``FGParams`` or a sequence (the scenario axis); all
                  scenarios share the model count ``M``.
      cfg:        shared simulation geometry/discretization.
      seeds:      PRNG seeds (the replication axis).
      reduce:     ``"trace"`` (full per-sample traces, bitwise the
                  historical ``simulate_batch``) or an on-device
                  reduction: ``"mean"`` (post-warmup time-mean + std),
                  ``"final"`` (last sample), ``"quantiles"`` (post-warmup
                  time-quantiles), ``"o_tau"`` (the o(τ) estimator's
                  holder-fraction age histograms, accumulated on device —
                  requires ``tau_grid``; stats ship ``o_tau`` plus the
                  raw ``o_tau_num``/``o_tau_den`` histograms for
                  cross-seed aggregation, pinned against
                  ``observations.estimate_o_of_tau`` on the trace path).
      warmup_frac: fraction of samples discarded before reducing
                  (defaults to ``cfg.warmup_frac``; ignored for
                  ``"trace"``/``"final"``).
      chunk_size: scenarios per dispatched chunk (``None`` = one
                  dispatch). Chunks stream with double-buffering: the
                  next chunk is dispatched before the previous chunk's
                  outputs are pulled to the host.
      quantiles:  quantile levels for ``reduce="quantiles"``.
      tau_grid:   uniform observation-age grid starting at 0 for
                  ``reduce="o_tau"`` (its length and spacing define the
                  histogram bins, exactly like ``estimate_o_of_tau``).
      n_devices:  mesh size override (defaults to all visible devices).
      checkpoint_dir: when set, every completed chunk's host-side result
                  is saved there (``repro.checkpoint.ckpt`` — atomic
                  temp-rename writes with per-array content hashes and the
                  attempt number in the manifest) together with a
                  fingerprint of the (config, grid, plan, reduction,
                  seeds) quintuple, and chunk dispatch retries under
                  ``retry_policy`` (a chunk that exhausts its attempts is
                  NaN/zero-filled, listed in ``failed_chunks`` and masked
                  out of ``coverage``). Checkpointed execution
                  materializes each chunk synchronously (no double
                  buffering) so a saved chunk is always durable.
      resume:     with ``checkpoint_dir``, skip chunks whose saved
                  fingerprint matches this sweep — a killed-and-resumed
                  sweep reproduces the uninterrupted run's results
                  bitwise. Mismatched, truncated, corrupt, or
                  shape-drifted checkpoints are skipped with a warning
                  naming the chunk and reason, never reused.
      retry_policy: a :class:`repro.sim.dispatch.RetryPolicy` governing
                  per-chunk retries and backoff on the checkpointed path
                  (default: 2 attempts, the historical retry-once).
      workers:    run the sweep through the fault-tolerant multi-process
                  dispatcher instead of in-process: ``workers`` N worker
                  processes claim chunk tasks from a filesystem work
                  queue under ``queue_dir`` via atomic-rename leases with
                  heartbeat renewal; dead/stalled workers are detected
                  and their chunks re-dispatched with backoff under
                  ``retry_policy``. See
                  :func:`repro.sim.dispatch.run_dispatched` (which this
                  delegates to) for the full contract.
      queue_dir:  the work-queue directory for ``workers=`` (shared-dir
                  multi-host by construction; default: a temp dir, or
                  ``{checkpoint_dir}/.queue`` when ``checkpoint_dir`` is
                  set).
      xla_cache_dir: persistent XLA compile-cache directory shared by the
                  dispatcher's worker processes (default:
                  ``repro.launch.cache.enable_compile_cache``'s) — a warm
                  cache makes a fresh worker load the chunk program
                  instead of recompiling.

    An in-process call records its host phases as ``repro.spans``
    spans: an ``fg.sweep`` root (attr ``take``: the paths by which the
    engine step reads its per-node tables, ``"onehot+mxu"`` say,
    :func:`repro.sim.compute.take_paths`; attr ``models``: the model
    count M)
    with ``fg.sweep.prepare``, ``.keys``,
    ``.dispatch`` and ``.pull`` per chunk, ``.checkpoint`` and
    ``.finalize``.

    Returns:
      ``BatchSimOutputs`` for ``reduce="trace"`` — with the extra
      attributes ``plan``/``devices_used``/``host_bytes``/``coverage``
      attached — or a :class:`SweepSummary` for the reduced modes.
    """
    if workers is not None:
        from repro.sim import dispatch

        return dispatch.run_dispatched(
            ps, cfg, seeds, reduce=reduce, warmup_frac=warmup_frac,
            chunk_size=chunk_size, quantiles=quantiles, tau_grid=tau_grid,
            n_devices=n_devices, checkpoint_dir=checkpoint_dir,
            resume=resume, retry_policy=retry_policy, workers=workers,
            queue_dir=queue_dir, xla_cache_dir=xla_cache_dir,
        )

    with spans.span("fg.sweep", reduce=reduce, slots=cfg.n_slots) as root:
        with spans.span("fg.sweep.prepare"):
            setup = _prepare(ps, cfg, seeds, reduce, warmup_frac, chunk_size,
                             quantiles, tau_grid, n_devices)
        plan = setup.plan
        root.attrs.update(runs=plan.n_scenarios * plan.n_seeds,
                          chunks=plan.n_chunks,
                          take=take_paths(take_reads(cfg, setup.M)),
                          models=setup.M)
        with spans.span("fg.sweep.keys"):
            keys = setup.keys()
        return _run_chunks(setup, keys, seeds, checkpoint_dir, resume,
                           retry_policy)


def _run_chunks(setup: _SweepSetup, keys, seeds, checkpoint_dir, resume,
                retry_policy):
    """Dispatch, pull (and, with ``checkpoint_dir``, save) the chunks of
    one in-process sweep, then assemble its result."""
    plan = setup.plan
    worker_cell: list = []

    def dispatch_chunk(c):
        # the chunk slice is rebuilt per attempt: donation may have
        # invalidated a previous attempt's buffers. The worker resolves
        # lazily (a fully resumed sweep never touches the jit cache) but
        # exactly once per run.
        if not worker_cell:
            worker_cell.append(setup.worker())
        p_chunk = setup.chunk_params(c)
        with warnings.catch_warnings():
            # CPU cannot always alias donated input pages into outputs;
            # the donation is still honored where the backend supports it
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            return worker_cell[0](keys, p_chunk)

    devices_used = 0
    failed: list[int] = []

    def note_devices(out):
        nonlocal devices_used
        devices_used = max(
            devices_used,
            len(jax.tree_util.tree_leaves(out)[0].sharding.device_set),
        )

    if checkpoint_dir is None:
        host_chunks: list[dict] = []
        pending = None
        for c in range(plan.n_chunks):
            with spans.span("fg.sweep.dispatch", chunk=c):
                out = dispatch_chunk(c)
                note_devices(out)
            if pending is not None:
                # double buffer: materialize chunk c-1 while chunk c runs
                with spans.span("fg.sweep.pull", chunk=c - 1):
                    host_chunks.append(
                        jax.tree_util.tree_map(np.asarray, pending)
                    )
            pending = out
        with spans.span("fg.sweep.pull", chunk=plan.n_chunks - 1):
            host_chunks.append(jax.tree_util.tree_map(np.asarray, pending))
        with spans.span("fg.sweep.finalize"):
            return _finalize(setup, host_chunks, devices_used=devices_used)

    from repro.checkpoint.ckpt import save_checkpoint
    from repro.sim.dispatch import RetryPolicy

    policy = retry_policy if retry_policy is not None else RetryPolicy()
    fp = _setup_fingerprint(setup, seeds)
    expected = setup.expected_shapes()
    done = {}
    if resume:
        with spans.span("fg.sweep.checkpoint", resume=True):
            done = _load_chunks(checkpoint_dir, fp, plan.n_chunks,
                                expected=expected)
    telemetry: dict = {"chunks": {}}
    by_idx: dict[int, dict] = {}

    for c in range(plan.n_chunks):
        if c in done:
            by_idx[c] = done[c]
            telemetry["chunks"][c] = {"attempts": 0, "resumed": True}
            continue
        hc = None
        attempt = 0
        tried: list[spans.Span] = []     # the chunk's dispatch/pull spans
        for attempt in range(policy.max_attempts):
            # only Exception is retried — a kill signal
            # (KeyboardInterrupt/SystemExit) propagates, which is the
            # preemption this path checkpoints against
            try:
                with spans.span("fg.sweep.dispatch", chunk=c,
                                attempt=attempt) as s:
                    tried.append(s)
                    out = dispatch_chunk(c)
                with spans.span("fg.sweep.pull", chunk=c,
                                attempt=attempt) as s:
                    tried.append(s)
                    hc = jax.tree_util.tree_map(np.asarray, out)
                # validate the (possibly retried) output against the
                # worker's contract before anything is checkpointed — a
                # retry that returned drifted shapes must not poison the
                # checkpoint dir
                reason = _tree_mismatch(hc, expected)
                if reason is not None:
                    hc = None
                    raise RuntimeError(
                        f"chunk result failed validation: {reason}")
                note_devices(out)
                break
            except Exception as e:
                warnings.warn(
                    f"sweep chunk {c} dispatch failed "
                    f"(attempt {attempt + 1}/{policy.max_attempts}): {e!r}"
                )
                if attempt + 1 < policy.max_attempts:
                    delay = policy.backoff(attempt + 1, key=f"{fp}:{c}")
                    if delay > 0:
                        time.sleep(delay)
        # from the first attempt's dispatch to the end of the last
        # attempt's span, backoff included
        latency = (tried[-1].t1_ns - tried[0].t0_ns) * 1e-9
        if hc is None:
            failed.append(c)
            by_idx[c] = _fill_chunk(expected)
            telemetry["chunks"][c] = {
                "attempts": policy.max_attempts, "latency_s": latency,
            }
            continue
        with spans.span("fg.sweep.checkpoint", chunk=c):
            save_checkpoint(
                checkpoint_dir, c, dict(hc, fingerprint=_fp_array(fp)),
                meta={"chunk": c, "attempt": attempt,
                      "fingerprint": fp, "schema": "sweep-chunk-v1"},
                integrity=True, atomic=True,
            )
        by_idx[c] = hc
        telemetry["chunks"][c] = {
            "attempts": attempt + 1, "latency_s": latency,
        }
    host_chunks = [by_idx[c] for c in range(plan.n_chunks)]
    with spans.span("fg.sweep.finalize"):
        return _finalize(setup, host_chunks, devices_used=devices_used,
                         failed=failed, telemetry=telemetry)
