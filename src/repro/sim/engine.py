"""The ``lax.scan`` simulation driver: single runs and batched sweeps.

Composes the subsystems (mobility / contacts / compute / observations)
into one slot-step function, scans it over time, and exposes

* ``simulate(p, cfg, seed)``        — one system, one seed (the legacy API);
* ``simulate_batch(ps, cfg, seeds)``— a (scenarios x seeds) sweep *in a
  single jit compilation*: the scenario axis vmaps over stacked dynamic
  ``FGParams`` (T_L, T_T, T_M, t0, lam, tau_l, Λ) and the seed axis vmaps
  over PRNG keys. The paper's figure sweeps become one batched device
  program instead of a serial per-point loop (``benchmarks/sim_engine.py``
  measures the speedup).

The per-slot traced program is independent of the model count ``M`` (the
legacy Python-over-``M`` enqueue loops are scatter ops in
``repro.sim.compute``), so compile time no longer grows with ``M``.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.meanfield import FGParams
from repro.core.zones import ZoneSet, single_zone
from repro.sim import cells, compute, contacts, faults, observations
from repro.sim import learn as learning
from repro.sim.mobility import get_mobility
from repro.sim.state import init_sim_state

__all__ = [
    "SimConfig",
    "SimOutputs",
    "BatchSimOutputs",
    "ZoneSet",
    "effective_zones",
    "zone_churn",
    "check_overflow",
    "simulate",
    "simulate_batch",
    "dynamic_params",
    "stack_dynamic_params",
    "scan_carry_bytes",
    "take_reads",
]


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Geometry/mobility/discretization of the simulation (paper defaults).

    Hashable and frozen: it is a static jit argument, so two configs that
    compare equal share one compiled program regardless of the dynamic
    ``FGParams`` swept over it.
    """

    n_nodes: int = 200
    area_side: float = 200.0
    rz_radius: float = 100.0
    r_tx: float = 5.0
    speed: float = 1.0
    dir_change_rate: float = 1.0 / 20.0  # RDM heading renewal [1/s]
    dt: float = 0.25                     # slot [s]
    n_slots: int = 8000
    sample_every: int = 8                # output every k slots
    k_obs: int = 64                      # tracked observations per model
    q_train: int = 16                    # training queue slots per node
    q_merge: int = 16                    # merging queue slots per node
    warmup_frac: float = 0.3             # discarded transient fraction
    mobility: str = "rdm"                # key into repro.sim.mobility registry
    street_spacing: float = 25.0         # Manhattan-grid street spacing [m]
    pause_s: float = 0.0                 # RWP waypoint pause time [s]
    zones: ZoneSet | None = None         # k Replication Zones; None = the
                                         # legacy single centered disc of
                                         # radius rz_radius (bitwise-equal
                                         # to an explicit k=1 ZoneSet)
    contact_backend: str = "auto"        # "dense" (O(N²) packed sweep) |
                                         # "cells" (O(N) cell lists) |
                                         # "auto" (dense below
                                         # cells.AUTO_CELLS_MIN_N nodes —
                                         # paper-scale runs stay bitwise)
    cell_cap: int | None = None          # cells: node slots per grid cell
                                         # (None = density-derived auto)
    nbr_cap: int | None = None           # cells: neighbor-list cap per node
                                         # (None = density-derived auto)
    speed_range: tuple | None = None     # (lo, hi): per-node speeds drawn
                                         # U(lo, hi) (rdm mobility only —
                                         # validated below); None = every
                                         # node moves at cfg.speed
                                         # (bitwise the legacy engine)
    faults: Any = None                   # repro.sim.faults.FaultConfig;
                                         # None or a disabled config traces
                                         # exactly the fault-free program
    learn: Any = None                    # repro.sim.learn.LearnConfig: carry
                                         # real per-node model parameters and
                                         # train/merge them on the protocol's
                                         # events; None traces exactly the
                                         # learning-free program, and the
                                         # protocol itself is bitwise
                                         # unaffected either way
    overflow_mode: str = "warn"          # cells backend nbr_overflow > 0:
                                         # "warn" emits a structured
                                         # NeighborOverflowWarning post-run,
                                         # "strict" raises instead

    def __post_init__(self):
        if self.speed_range is not None and self.mobility != "rdm":
            raise ValueError(
                "speed_range is implemented for the 'rdm' mobility model "
                f"only (got mobility={self.mobility!r}); the other models "
                "would silently run at the constant cfg.speed"
            )
        if self.overflow_mode not in ("warn", "strict"):
            raise ValueError(
                f"unknown overflow_mode {self.overflow_mode!r}; known: "
                "'warn', 'strict'"
            )


def effective_zones(cfg: SimConfig) -> ZoneSet:
    """The ``ZoneSet`` a config runs: ``cfg.zones``, or the legacy single
    centered disc built from ``cfg.rz_radius``."""
    if cfg.zones is not None:
        return cfg.zones
    c = cfg.area_side / 2.0
    return single_zone((c, c), cfg.rz_radius)


@dataclasses.dataclass
class SimOutputs:
    """Per-sample traces (leading axis = sample index)."""

    t: np.ndarray                # (S,) sample times
    availability: np.ndarray     # (S, M) mean fraction of in-RZ nodes w/ model
    busy_frac: np.ndarray        # (S,)
    stored_info: np.ndarray      # (S,) mean obs (age<=tau_l) per in-RZ node
    obs_birth: np.ndarray        # (S, M, K) birth time of ring slot (-inf empty)
    obs_holders: np.ndarray      # (S, M, K) #in-RZ nodes having incorporated
    model_holders: np.ndarray    # (S, M) #in-RZ nodes with the model
    n_in_rz: np.ndarray          # (S,)
    # per-zone traces (trailing zone axis; zone 0 is the legacy RZ)
    availability_z: np.ndarray | None = None   # (S, M, K_zones)
    stored_info_z: np.ndarray | None = None    # (S, K_zones)
    n_in_rz_z: np.ndarray | None = None        # (S, K_zones)
    # cells contact backend only: running max of close pairs dropped per
    # slot by the bounded neighbor lists (0 = contact detection exact)
    nbr_overflow: np.ndarray | None = None     # (S,)
    # fault-injection telemetry (enabled FaultConfig only; C = n_classes)
    availability_c: np.ndarray | None = None   # (S, M, C) per-class in-RZ
                                               # model availability
    on_frac_c: np.ndarray | None = None        # (S, C) accessible fraction
    n_in_rz_c: np.ndarray | None = None        # (S, C)
    fault_events: np.ndarray | None = None     # (S, 3) cumulative
                                               # abort/link-fail/crash
    # gossip-learning telemetry (enabled LearnConfig only; repro.sim.learn)
    test_acc: np.ndarray | None = None         # (S,) population mean accuracy
    test_acc_holders: np.ndarray | None = None # (S,) mean over in-RZ holders
    learn_obs: np.ndarray | None = None        # (S,) mean obs count / holder
    theta_var: np.ndarray | None = None        # (S,) mean parameter variance
    merge_stats: np.ndarray | None = None      # (S, 6) cumulative merge-
                                               # screen counters
    # Byzantine telemetry (adversarial FaultConfig + enabled LearnConfig)
    poisoned_frac: np.ndarray | None = None    # (S,) poisoned fraction of
                                               # in-RZ holders
    poisoned_frac_c: np.ndarray | None = None  # (S, C) per-class split


@dataclasses.dataclass
class BatchSimOutputs:
    """Batched traces with leading (scenario, seed) axes.

    ``point(i, j)`` extracts the ``SimOutputs`` view of scenario ``i``,
    seed ``j`` for code written against the single-run API. The trailing
    fields (``plan`` onward) describe how the sweep runner executed the
    batch (``repro.sim.sweep`` / ``repro.sim.dispatch``); they stay
    ``None``/empty for instances built elsewhere."""

    t: np.ndarray                # (S,)
    availability: np.ndarray     # (P, R, S, M)
    busy_frac: np.ndarray        # (P, R, S)
    stored_info: np.ndarray      # (P, R, S)
    obs_birth: np.ndarray        # (P, R, S, M, K)
    obs_holders: np.ndarray      # (P, R, S, M, K)
    model_holders: np.ndarray    # (P, R, S, M)
    n_in_rz: np.ndarray          # (P, R, S)
    availability_z: np.ndarray | None = None   # (P, R, S, M, K_zones)
    stored_info_z: np.ndarray | None = None    # (P, R, S, K_zones)
    n_in_rz_z: np.ndarray | None = None        # (P, R, S, K_zones)
    nbr_overflow: np.ndarray | None = None     # (P, R, S) cells backend only
    availability_c: np.ndarray | None = None   # (P, R, S, M, C)
    on_frac_c: np.ndarray | None = None        # (P, R, S, C)
    n_in_rz_c: np.ndarray | None = None        # (P, R, S, C)
    fault_events: np.ndarray | None = None     # (P, R, S, 3)
    test_acc: np.ndarray | None = None         # (P, R, S)
    test_acc_holders: np.ndarray | None = None # (P, R, S)
    learn_obs: np.ndarray | None = None        # (P, R, S)
    theta_var: np.ndarray | None = None        # (P, R, S)
    merge_stats: np.ndarray | None = None      # (P, R, S, 6)
    poisoned_frac: np.ndarray | None = None    # (P, R, S)
    poisoned_frac_c: np.ndarray | None = None  # (P, R, S, C)
    plan: Any = None             # SweepPlan of the producing sweep
    devices_used: int | None = None
    host_bytes: int | None = None
    failed_chunks: tuple = ()    # sweep chunks that exhausted their retries
    coverage: Any = None         # (n_scenarios,) bool: False = filled rows
    quarantined: tuple = ()      # poison chunks (dispatched sweeps)
    telemetry: Any = None        # dispatch attempt/latency/requeue records

    @property
    def n_scenarios(self) -> int:
        return self.availability.shape[0]

    @property
    def n_seeds(self) -> int:
        return self.availability.shape[1]

    def point(self, scenario: int, seed: int) -> SimOutputs:
        def _z(arr):
            return None if arr is None else arr[scenario, seed]

        return SimOutputs(
            t=self.t,
            availability=self.availability[scenario, seed],
            busy_frac=self.busy_frac[scenario, seed],
            stored_info=self.stored_info[scenario, seed],
            obs_birth=self.obs_birth[scenario, seed],
            obs_holders=self.obs_holders[scenario, seed],
            model_holders=self.model_holders[scenario, seed],
            n_in_rz=self.n_in_rz[scenario, seed],
            availability_z=_z(self.availability_z),
            stored_info_z=_z(self.stored_info_z),
            n_in_rz_z=_z(self.n_in_rz_z),
            nbr_overflow=_z(self.nbr_overflow),
            availability_c=_z(self.availability_c),
            on_frac_c=_z(self.on_frac_c),
            n_in_rz_c=_z(self.n_in_rz_c),
            fault_events=_z(self.fault_events),
            test_acc=_z(self.test_acc),
            test_acc_holders=_z(self.test_acc_holders),
            learn_obs=_z(self.learn_obs),
            theta_var=_z(self.theta_var),
            merge_stats=_z(self.merge_stats),
            poisoned_frac=_z(self.poisoned_frac),
            poisoned_frac_c=_z(self.poisoned_frac_c),
        )


def zone_churn(zone_prev, zonew, *, inc, has_model, tq_model, mq_model,
               serving, serv_left):
    """Apply the zone-churn rule to the protocol state.

    A node drops its packed protocol state (incorporation words, model
    flags, queues, running job) exactly when it leaves the **union** of
    Replication Zones — ``zone_prev``/``zonew`` are the uint32 zone
    membership words of the previous and current slot. Crossing directly
    from one zone into another (the zone word changes but stays nonzero)
    *transfers* the state: migration keeps everything. With a single zone
    the words are 0/1 and ``left`` is bitwise the legacy
    ``in_rz_prev & ~in_rz``.

    Returns ``(left, dict-of-updated-fields)``; tested (property tests
    over random membership trajectories) in ``tests/test_sim_zones.py``.
    The actual drop is :func:`repro.sim.faults.drop_state` — the single
    state-drop path zone churn shares with crash-restart churn.
    """
    left = (zone_prev != 0) & (zonew == 0)
    return left, faults.drop_state(
        left, inc=inc, has_model=has_model, tq_model=tq_model,
        mq_model=mq_model, serving=serving, serv_left=serv_left,
    )


def dynamic_params(p: FGParams) -> dict:
    """The FGParams fields the engine treats as traced (sweepable without
    recompilation). ``M`` stays static — it sets array shapes."""
    return dict(
        t0=p.t0, T_L=p.T_L, T_T=p.T_T, T_M=p.T_M,
        lam=p.lam, tau_l=p.tau_l, Lam=float(p.Lam),
    )


def stack_dynamic_params(ps: Sequence[FGParams]) -> dict:
    """Stack per-scenario dynamic params into leading-axis float32 arrays
    (host numpy: building a sweep's inputs touches no device)."""
    dicts = [dynamic_params(p) for p in ps]
    return {
        k: np.asarray([d[k] for d in dicts], dtype=np.float32)
        for k in dicts[0]
    }


def _check_params(ps: Sequence[FGParams]) -> int:
    m_values = {int(p.M) for p in ps}
    if len(m_values) != 1:
        raise ValueError(
            f"one batch compiles for one model count M; got {sorted(m_values)}"
            " — split the sweep by M"
        )
    for p in ps:
        if p.W < p.M:
            raise NotImplementedError(
                "simulator covers the W >= M (w = 1) regime used in the "
                "paper's evaluation; pass M = min(M, W) for the general case"
            )
    return m_values.pop()


def _run(key, p_dyn: dict, cfg: SimConfig, M: int, trace: str = "full"):
    """Un-jitted scan driver: returns the per-slot output dict.

    The scan carry is the bit-packed ``SimState`` (see ``repro.sim.state``);
    all boolean-mask algebra below is uint32 word ops. Per-step constants
    (zone centers/radii, squared transmission radius) are hoisted here —
    nothing geometry-shaped is rebuilt inside ``step`` (drifting zone
    centers are a closed-form function of the slot time, not carried
    state).

    ``trace`` selects the per-sample output set: ``"full"`` emits every
    trace (the single-run / trace-sweep format), ``"light"`` drops the
    per-observation quantities (``obs_birth`` / ``obs_holders``) that only
    the o(τ) estimator consumes — reduced-output sweeps use it to skip the
    engine's one full ``inc`` unpack per sample.

    Each stage of the slot step, and the per-sample outputs, run under a
    ``jax.named_scope`` (``fg.mobility``, ``fg.contacts``, ...): op
    metadata only, so a device trace can attribute time to a stage.
    """
    dt = cfg.dt
    t0, T_L, T_T, T_M = (p_dyn[k] for k in ("t0", "T_L", "T_T", "T_M"))
    lam, tau_l, Lam = p_dyn["lam"], p_dyn["tau_l"], p_dyn["Lam"]
    r_tx2 = cfg.r_tx**2
    model = get_mobility(cfg.mobility)
    # contact-backend dispatch is static (cfg is a jit static arg): the
    # dense path traces exactly the PR-4 program; the cells path swaps
    # the O(N²) sweep for the cell-list neighbor stages and carries the
    # bounded neighbor list as ``prev_close``
    use_cells = cells.contact_backend(cfg) == "cells"
    grid = cells.make_grid(cfg) if use_cells else None

    zs = effective_zones(cfg)
    kz = zs.k
    zcenters = jnp.asarray(zs.centers, jnp.float32)      # (K, 2)
    zradii = jnp.asarray(zs.radii, jnp.float32)          # (K,)
    zdrift = jnp.asarray(zs.drift, jnp.float32) if zs.moving else None

    # ---- fault-injection constants (static gate: a None or disabled
    # FaultConfig keeps every branch below dead and the traced program —
    # including the PRNG split sequence — bitwise the fault-free one) ----
    fc = cfg.faults if (cfg.faults is not None and cfg.faults.enabled) else None
    faults_on = fc is not None
    if faults_on:
        n = cfg.n_nodes
        ids = faults.node_classes(fc, n)                 # (N,) static
        cls1h = jnp.asarray(faults.class_onehot(fc, n))  # (N, C)
        n_per_class = jnp.asarray(
            faults.class_onehot(fc, n).sum(axis=0), jnp.float32
        )
        # per-slot transition/event probabilities (compile-time constants)
        p_off = jnp.asarray(
            np.asarray([1.0 - np.exp(-c.rate_off * dt) for c in fc.classes],
                       np.float32)[ids]
        )
        p_on = jnp.asarray(
            np.asarray([1.0 - np.exp(-c.rate_on * dt) for c in fc.classes],
                       np.float32)[ids]
        )
        p_crash = float(1.0 - np.exp(-fc.crash_rate * dt))
        p_link = float(1.0 - np.exp(-fc.link_fail_rate * dt))
        is_fr = jnp.asarray(
            np.asarray([c.free_rider for c in fc.classes], bool)[ids]
        )

    # ---- gossip-learning constants (static gate like faults: a None
    # cfg.learn keeps every learn_on branch dead; an enabled one adds carry
    # fields and per-slot work but never touches the engine's PRNG chain,
    # so the *protocol* traces are bitwise identical either way) ----
    lc = cfg.learn if (cfg.learn is not None and cfg.learn.enabled) else None
    learn_on = lc is not None
    adv_on = trimmed_on = False
    if learn_on:
        task = learning.make_task(lc)    # teacher/init/test set, hoisted
        # ---- Byzantine gates: attacks ride cfg.faults.adversarial —
        # *independent* of the protocol-fault gate above, because
        # adversaries follow the protocol honestly (an attack-only config
        # keeps faults_on False and the protocol bitwise faults=None);
        # the trimmed-defense peer buffer rides lc.defense ----
        adv_on = cfg.faults is not None and cfg.faults.adversarial
        dc = lc.defense if (
            lc.defense is not None and lc.defense.enabled
        ) else None
        trimmed_on = dc is not None and dc.mode == "trimmed"
        if adv_on:
            adv = faults.adv_vectors(cfg.faults, cfg.n_nodes)  # static
            cls1h_adv = jnp.asarray(
                faults.class_onehot(cfg.faults, cfg.n_nodes)
            )

    def zone_member(pos, t_now):
        """(N, K) bool per-zone membership at time ``t_now``.

        Drifting zone centers reflect off the area boundary (the same
        specular fold the mobility models use); static sets skip the
        fold so the geometry — and the K = 1 path, which reproduces the
        legacy centered-disc expression exactly — stays bitwise
        stable."""
        if zdrift is not None:
            raw = zcenters + zdrift * t_now
            m = jnp.mod(raw, 2.0 * cfg.area_side)
            c = cfg.area_side - jnp.abs(cfg.area_side - m)
        else:
            c = zcenters
        if kz == 1:
            # bitwise the legacy `norm(pos - center) <= rz_radius`
            return (
                jnp.linalg.norm(pos - c[0], axis=-1) <= zradii[0]
            )[:, None]
        d = jnp.linalg.norm(pos[:, None, :] - c[None, :, :], axis=-1)
        return d <= zradii[None, :]

    def step(carry, slot_idx):
        state, key = carry
        t_now = slot_idx.astype(jnp.float32) * dt
        key, k_mob1, k_mob2, k_obs, k_who = jax.random.split(key, 5)

        # ---- fault layer: duty-cycle chain first, its keys drawn from an
        # *additional* split so the base split sequence above — and with it
        # every fault-free draw — stays bitwise untouched ----
        if faults_on:
            with jax.named_scope("fg.faults"):
                key, k_duty, k_crash, k_link, k_abort = jax.random.split(
                    key, 5)
                availw, on = faults.duty_step(
                    k_duty, state.availw, p_off, p_on, cfg.n_nodes
                )
                access = on
        else:
            access = None

        # ---- mobility & zone membership ----
        with jax.named_scope("fg.mobility"):
            mob = model.step(k_mob1, k_mob2, state.mob, cfg)
            member = zone_member(mob.pos, t_now)             # (N, K)
            zonew = compute.pack_mask(member)[:, 0]          # (N,) uint32
            in_rz = zonew != 0                               # union membership

            # ---- zone churn: leaving the *union* of zones drops everything;
            # crossing directly from one zone into another transfers state ----
            left, churned = zone_churn(
                state.zone_prev, zonew, inc=state.inc,
                has_model=state.has_model,
                tq_model=state.tq_model, mq_model=state.mq_model,
                serving=state.serving, serv_left=state.serv_left,
            )
            inc, has_model = churned["inc"], churned["has_model"]
            tq_model, mq_model = churned["tq_model"], churned["mq_model"]
            serving, serv_left = churned["serving"], churned["serv_left"]

            # ---- crash-restart churn: drop packed protocol state through
            # the same path zone churn uses; the node itself stays (and
            # stays on) --
            if faults_on:
                crashed = jax.random.uniform(k_crash, (cfg.n_nodes,)) < p_crash
                dropped = faults.drop_state(
                    crashed, inc=inc, has_model=has_model, tq_model=tq_model,
                    mq_model=mq_model, serving=serving, serv_left=serv_left,
                )
                inc, has_model = dropped["inc"], dropped["has_model"]
                tq_model, mq_model = dropped["tq_model"], dropped["mq_model"]
                serving, serv_left = dropped["serving"], dropped["serv_left"]

            # ---- learning churn: a node dropping its packed protocol state
            # also resets its model replica to the shared init ----
            if learn_on:
                drop = (left | crashed) if faults_on else left
                rr = learning.reset_replicas(
                    drop, state.theta, state.theta_cnt, state.theta_age,
                    task.theta0,
                    poisoned=state.poisoned if adv_on else None,
                    peer_fill=state.peer_fill if trimmed_on else None,
                )
                theta, theta_cnt, theta_age = (
                    rr["theta"], rr["theta_cnt"], rr["theta_age"]
                )
                poisoned = rr.get("poisoned")
                peer_fill = rr.get("peer_fill")

        # ---- contact dynamics ----
        # Dense backend: the O(N²) pairwise sweep in two stages — the
        # shared part (positions/RZ only — computed once per *seed* in
        # sweep batches) first, so the partner-proximity bit is a word
        # lookup in its packed contact matrix; the per-run candidate
        # search follows once this slot's eligibility is known. On TPU
        # the fused Pallas kernel runs later instead (no early matrix)
        # and the O(N) distance recompute supplies the proximity bit.
        # Cells backend: bounded per-node neighbor lists from the cell
        # grid (also shared per seed — they too depend only on positions
        # and zones) replace the matrix; the partner-proximity bit is
        # the O(N) pair recompute, bitwise the same criterion.
        with jax.named_scope("fg.contacts"):
            if use_cells:
                # access is seed-only state (its key chain never touches the
                # scenario-dependent p_dyn), so the neighbor stage stays a
                # shared per-seed stage under the barrier
                nbr, ovf = cells.neighbor_lists(
                    mob.pos, zonew, grid, r_tx2, access
                )
                nbr = compute.shared_barrier(nbr)
                still_close = contacts.pair_still_close(
                    mob.pos, zonew, state.partner, r_tx2, access
                )
            else:
                closew_shared, d2ctx = contacts.pairwise_close(
                    mob.pos, member, r_tx2, access
                )
                if closew_shared is None:
                    still_close = contacts.pair_still_close(
                        mob.pos, zonew, state.partner, r_tx2, access
                    )
                else:
                    still_close = contacts.partner_close_bit(
                        closew_shared, state.partner
                    )
            # mid-transfer link failure breaks the exchange exactly like
            # moving out of range (completed transfers are still delivered)
            if faults_on:
                lfail = faults.link_fail(k_link, p_link, state.partner)
                still_close = still_close & ~lfail
            elapsed, done, broke, ending, eff_time, pidx = (
                contacts.advance_exchanges(
                    partner=state.partner, exch_elapsed=state.exch_elapsed,
                    exch_total=state.exch_total, still_close=still_close,
                    dt=dt,
                )
            )
        with jax.named_scope("fg.deliveries"):
            delivered, sender_words = contacts.compute_deliveries(
                order_seed=state.order_seed, snap_has=state.snap_has,
                snap=state.snap, pidx=pidx, eff_time=eff_time, ending=ending,
                t0=t0, T_L=T_L,
            )
            if faults_on:
                # free-riders receive but never serve
                delivered = faults.gate_deliveries(delivered, pidx, is_fr)

        # ---- learning merge: a delivery of the learned model's instance
        # merges the sender's connection-time parameter snapshot into the
        # receiver (the paper's weighted-coefficient average, fused kernel)
        if learn_on:
            with jax.named_scope("fg.learn.merge"):
                md = learning.merge_deliveries(
                    lc, delivered[:, learning.LEARN_MODEL], pidx,
                    theta, theta_cnt, theta_age,
                    state.theta_snap, state.snap_cnt, state.snap_age, tau_l,
                    merge_stats=state.merge_stats,
                    poisoned=poisoned,
                    snap_poison=state.snap_poison if adv_on else None,
                    peer_buf=state.peer_buf if trimmed_on else None,
                    peer_fill=peer_fill,
                )
                theta, theta_cnt, theta_age = (
                    md["theta"], md["theta_cnt"], md["theta_age"]
                )
                merge_stats = md["merge_stats"]
                poisoned = md.get("poisoned", poisoned)
                peer_buf = md.get("peer_buf")
                peer_fill = md.get("peer_fill", peer_fill)

        # enqueue merge jobs for delivered instances that add information
        # (merge only when the received training set is not a subset of the
        # local one — Y of Definition 4). A received instance is NOT
        # used/propagated until merged (paper §III-C) — has_model flips only
        # at merge completion.
        with jax.named_scope("fg.deliveries"):
            adds = delivered & compute.packed_any(sender_words & ~inc)
            mq_model, mq_mask = compute.enqueue_ascending(
                mq_model, adds, (state.mq_mask, sender_words)
            )

        # ---- release ending pairs, form new connections ----
        with jax.named_scope("fg.matching"):
            partner = jnp.where(ending, -1, state.partner)
            elig = (partner < 0) & in_rz
            if faults_on:
                # redundant with the access-folded close sets, but keeps the
                # eligibility invariant explicit on every matching path
                elig = elig & on
            if use_cells:
                best, has = cells.candidate_best(
                    mob.pos, nbr, state.prev_close, elig
                )
                match = contacts.mutualize(best, has)
                closew = nbr        # the cells-path prev_close carry
            else:
                closew, match = contacts.match_candidates(
                    d2ctx, state.prev_close, elig
                )
            if faults_on:
                # per-contact connection-setup abort (symmetric coin)
                match, aborted = faults.abort_matches(k_abort, fc.p_abort,
                                                      match)
            conn = contacts.form_connections(
                partner=partner, match=match, has_model=has_model, inc=inc,
                snap=state.snap, snap_has=state.snap_has,
                exch_elapsed=elapsed, exch_total=state.exch_total,
                order_seed=state.order_seed, slot_idx=slot_idx, t0=t0, T_L=T_L,
            )
        # ---- learning snapshot: parameters are frozen alongside the
        # protocol's snap words when a connection forms; the Byzantine
        # attack then transforms the snapshot an adversarial node just
        # took — the serve side — leaving its live replica untouched ----
        if learn_on:
            with jax.named_scope("fg.learn.snapshot"):
                newly = match >= 0
                snap = learning.snapshot_params(
                    newly, theta, theta_cnt, theta_age,
                    state.theta_snap, state.snap_cnt, state.snap_age,
                    poisoned=poisoned,
                    snap_poison=state.snap_poison if adv_on else None,
                )
                if adv_on:
                    theta_snap, snap_cnt, snap_age, snap_poison = snap
                    theta_snap, snap_cnt, snap_age, snap_poison = (
                        learning.poison_snapshots(
                            adv, task, slot_idx, newly,
                            theta_snap, snap_cnt, snap_age, snap_poison,
                        )
                    )
                else:
                    theta_snap, snap_cnt, snap_age = snap

        # ---- observation generation & training enqueue ----
        with jax.named_scope("fg.observations"):
            obs_birth, obs_head, inc, want_train, slot_payload = (
                observations.generate_observations(
                    k_obs=k_obs, k_who=k_who, obs_birth=state.obs_birth,
                    obs_head=state.obs_head, inc=inc,
                    in_rz=(in_rz & on) if faults_on else in_rz,
                    lam=lam, Lam=Lam, dt=dt, t_now=t_now,
                )
            )
            tq_model, tq_slot = compute.enqueue_ascending(
                tq_model, want_train, (state.tq_slot, slot_payload)
            )

        # ---- compute server: finish jobs, then pick next (merge priority) --
        # an off node's compute is dormant: its service timer freezes
        # (per-node dt = 0) and it starts no new job (can_serve below)
        with jax.named_scope("fg.compute"):
            serv_left, fin_merge, fin_train = compute.advance_timers(
                serving, serv_left,
                jnp.where(on, dt, 0.0) if faults_on else dt,
            )
            inc, has_model = observations.apply_completions(
                fin_merge=fin_merge, fin_train=fin_train,
                serv_model=state.serv_model, serv_mask=state.serv_mask,
                serv_slot=state.serv_slot, inc=inc, has_model=has_model,
                obs_birth=obs_birth,
            )
            serving = jnp.where(fin_merge | fin_train, -1, serving)
        # ---- learning train step: a finished training job on the learned
        # model whose observation is still in the ring (the same freshness
        # gate apply_completions uses) takes one local SGD step ----
        if learn_on:
            with jax.named_scope("fg.learn.train"):
                did_train = (
                    fin_train
                    & (state.serv_model == learning.LEARN_MODEL)
                    & (compute.take_nodes(obs_birth[learning.LEARN_MODEL],
                                          state.serv_slot) > -jnp.inf)
                )
                theta, theta_cnt, theta_age = learning.train_completions(
                    lc, task, slot_idx, did_train, theta, theta_cnt, theta_age,
                    dt,
                )
        with jax.named_scope("fg.compute"):
            served = compute.pick_next_jobs(
                serving=serving, serv_left=serv_left,
                serv_model=state.serv_model, serv_mask=state.serv_mask,
                serv_slot=state.serv_slot, mq_model=mq_model, mq_mask=mq_mask,
                tq_model=tq_model, tq_slot=tq_slot, T_M=T_M, T_T=T_T,
                can_serve=on if faults_on else None,
            )

        fault_kw = {}
        if faults_on:
            with jax.named_scope("fg.faults"):
                events = jnp.stack([
                    jnp.sum(aborted),
                    jnp.sum((state.partner >= 0) & lfail),
                    jnp.sum(crashed),
                ]).astype(jnp.int32)
                fault_kw = dict(availw=availw,
                                fault_events=state.fault_events + events)
        learn_kw = {}
        if learn_on:
            learn_kw = dict(
                theta=theta, theta_cnt=theta_cnt, theta_age=theta_age,
                theta_snap=theta_snap, snap_cnt=snap_cnt, snap_age=snap_age,
                merge_stats=merge_stats,
            )
            if adv_on:
                learn_kw.update(poisoned=poisoned, snap_poison=snap_poison)
            if trimmed_on:
                learn_kw.update(peer_buf=peer_buf, peer_fill=peer_fill)
        new_state = state.replace(
            mob=mob, prev_close=closew, inc=inc, has_model=has_model,
            obs_birth=obs_birth, obs_head=obs_head, tq_slot=tq_slot,
            mq_mask=mq_mask, zone_prev=zonew,
            nbr_overflow=(jnp.maximum(state.nbr_overflow, ovf)
                          if use_cells else state.nbr_overflow),
            **conn, **served, **fault_kw, **learn_kw,
        )
        return (new_state, key), None

    def chunk(carry, chunk_idx):
        # advance sample_every slots, then materialize one output sample —
        # the sampled slots are exactly the legacy [s-1::s] subsampling, but
        # the trace only stacks (and only computes) outputs at sample points.
        slots = chunk_idx * cfg.sample_every + jnp.arange(cfg.sample_every)
        (state, key), _ = jax.lax.scan(step, carry, slots)
        t_now = slots[-1].astype(jnp.float32) * dt
        with jax.named_scope("fg.outputs"):
            out = observations.slot_outputs(
                inc=state.inc, has_model=state.has_model,
                obs_birth=state.obs_birth, in_rz=state.zone_prev != 0,
                member=compute.unpack_mask(state.zone_prev[:, None], kz),
                partner=state.partner, t_now=t_now, tau_l=tau_l,
                with_obs_trace=(trace == "full"),
            )
            if use_cells:
                out["nbr_overflow"] = state.nbr_overflow
            if faults_on:
                out.update(faults.fault_outputs(
                    on=compute.unpack_mask(
                        state.availw[None, :], cfg.n_nodes
                    )[0],
                    in_rz=state.zone_prev != 0, has_model=state.has_model,
                    cls1h=cls1h, n_per_class=n_per_class,
                    fault_events=state.fault_events,
                ))
            if learn_on:
                out.update(learning.learn_outputs(
                    lc, task, state.theta, state.theta_cnt,
                    has_model=state.has_model, in_rz=state.zone_prev != 0,
                    merge_stats=state.merge_stats,
                    poisoned=state.poisoned if adv_on else None,
                    cls1h=cls1h_adv if adv_on else None,
                ))
        return (state, key), out

    mob0, key = model.init(key, cfg)
    zonew0 = compute.pack_mask(zone_member(mob0.pos, 0.0))[:, 0]
    state0 = init_sim_state(mob0, zonew0, M=M, cfg=cfg)
    n_chunks = cfg.n_slots // cfg.sample_every
    (_, _), outs = jax.lax.scan(
        chunk, (state0, key), jnp.arange(n_chunks), length=n_chunks
    )
    return outs


@partial(jax.jit, static_argnames=("cfg", "M"))
def _run_single(key, p_dyn: dict, cfg: SimConfig, M: int):
    return _run(key, p_dyn, cfg, M)


@partial(jax.jit, static_argnames=("cfg", "M"))
def _run_batch(keys, p_stack: dict, cfg: SimConfig, M: int):
    """Unsharded (seeds x scenarios) nested-vmap reference runner.

    The sweep subsystem (``repro.sim.sweep``) is the production path —
    mesh-sharded, chunked, optionally reduced on device; this single-device
    form is kept as the bitwise reference it is pinned against."""
    over_seeds = jax.vmap(lambda k, pd: _run(k, pd, cfg, M), in_axes=(0, None))
    over_scenarios = jax.vmap(over_seeds, in_axes=(None, 0))
    return over_scenarios(keys, p_stack)


def scan_carry_bytes(cfg: SimConfig, M: int) -> int:
    """Bytes of the per-run ``lax.scan`` carry (``SimState`` + PRNG key),
    computed via ``eval_shape`` — nothing is materialized.

    This is the quantity the bit-packing optimization shrinks; the sim
    benchmark reports it so BENCH tracks the memory win."""
    def build():
        key = jax.random.PRNGKey(0)
        model = get_mobility(cfg.mobility)
        mob0, key = model.init(key, cfg)
        zonew0 = jnp.zeros((cfg.n_nodes,), jnp.uint32)
        return init_sim_state(mob0, zonew0, M=M, cfg=cfg), key

    shapes = jax.eval_shape(build)
    return sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(shapes)
    )


def take_reads(cfg: SimConfig, M: int) -> tuple:
    """``(rows, words)`` of the slot step's table reads by node or slot
    index (:func:`repro.sim.compute.take_nodes`; a bool column counts one
    word): what :func:`repro.sim.compute.take_paths` names the step's
    read paths from. The fault layer's reads are of one word a node row,
    as ``n_own``'s is."""
    n, kw = cfg.n_nodes, -(-cfg.k_obs // 32)
    reads = [
        (n, 2),                        # mutualize: (best, has)
        (n, 1),                        # form_connections: n_own
        # deliveries: (snap_has, snap), and order_seed at M > 1
        (n, 1 + kw) if M == 1 else (n, 1 + M + M * kw),
        (cfg.k_obs, M),                # apply_completions: obs_birth.T
    ]
    if (cells.contact_backend(cfg) == "cells"
            or jax.default_backend() == "tpu"):
        # pair_still_close: (pos, zonew), where the step keeps no packed
        # contact matrix to read the partner's bit from
        reads.append((n, 3))
    if cfg.learn is not None and cfg.learn.enabled:
        adv = cfg.faults is not None and cfg.faults.adversarial
        # the trained sample's birth; (snap_cnt, snap_age[, snap_poison])
        reads += [(cfg.k_obs, 1), (n, 3 if adv else 2)]
    return tuple(reads)


def check_overflow(cfg: SimConfig, max_ovf, *, context: str = "run") -> int:
    """Post-run graceful-degradation check of the cells-backend
    ``nbr_overflow`` diagnostic.

    ``max_ovf`` is any array (or None) of per-sample running overflow
    maxima. A positive value means contact detection silently dropped
    close pairs; under ``cfg.overflow_mode == "warn"`` this emits a
    structured :class:`repro.sim.cells.NeighborOverflowWarning`, under
    ``"strict"`` it raises. Returns the max as an int (0 when clean)."""
    if max_ovf is None:
        return 0
    mo = int(np.max(np.asarray(max_ovf))) if np.size(max_ovf) else 0
    if mo > 0:
        msg = (
            f"cell-list contact detection dropped close pairs ({context}: "
            f"running per-slot max {mo}); results undercount contacts — "
            "raise SimConfig.cell_cap / nbr_cap"
        )
        if cfg.overflow_mode == "strict":
            raise RuntimeError(msg)
        warnings.warn(msg, cells.NeighborOverflowWarning, stacklevel=2)
    return mo


def _sample_times(cfg: SimConfig) -> np.ndarray:
    # the engine emits one sample per sample_every slots, at slot indices
    # s-1, 2s-1, ... (the legacy [s-1::s] subsampling)
    s = cfg.sample_every
    return (np.arange(cfg.n_slots) * cfg.dt)[s - 1:: s]


def simulate(p: FGParams, cfg: SimConfig, seed: int = 0) -> SimOutputs:
    """Run the simulator for the FG system ``p`` (uses M, Λ, T_T, T_M, ...)."""
    M = _check_params([p])
    outs = _run_single(jax.random.PRNGKey(seed), dynamic_params(p), cfg, M)
    if "nbr_overflow" in outs:
        check_overflow(cfg, outs["nbr_overflow"], context="simulate")

    def _opt(k):
        return np.asarray(outs[k]) if k in outs else None

    return SimOutputs(
        t=_sample_times(cfg),
        availability=np.asarray(outs["availability"]),
        busy_frac=np.asarray(outs["busy_frac"]),
        stored_info=np.asarray(outs["stored"]),
        obs_birth=np.asarray(outs["obs_birth"]),
        obs_holders=np.asarray(outs["obs_holders"]),
        model_holders=np.asarray(outs["model_holders"]),
        n_in_rz=np.asarray(outs["n_in_rz"]),
        availability_z=np.asarray(outs["availability_z"]),
        stored_info_z=np.asarray(outs["stored_z"]),
        n_in_rz_z=np.asarray(outs["n_in_rz_z"]),
        nbr_overflow=_opt("nbr_overflow"),
        availability_c=_opt("availability_c"),
        on_frac_c=_opt("on_frac_c"),
        n_in_rz_c=_opt("n_in_rz_c"),
        fault_events=_opt("fault_events"),
        test_acc=_opt("test_acc"),
        test_acc_holders=_opt("test_acc_holders"),
        learn_obs=_opt("learn_obs"),
        theta_var=_opt("theta_var"),
        merge_stats=_opt("merge_stats"),
        poisoned_frac=_opt("poisoned_frac"),
        poisoned_frac_c=_opt("poisoned_frac_c"),
    )


def simulate_batch(
    ps: Sequence[FGParams] | FGParams,
    cfg: SimConfig,
    seeds: Sequence[int] = (0,),
) -> BatchSimOutputs:
    """One compiled (scenarios x seeds) Monte-Carlo sweep.

    Args:
      ps:    one ``FGParams`` or a sequence of them (the scenario axis).
             All scenarios must share the model count ``M``.
      cfg:   shared simulation geometry/discretization.
      seeds: PRNG seeds (the replication axis).

    Returns a ``BatchSimOutputs`` with traces shaped (len(ps), len(seeds),
    n_samples, ...).

    This is a thin wrapper over the sweep runner
    (``repro.sim.sweep.run(..., reduce="trace")``): the flattened
    (scenario x seed) work axis is padded and sharded over every visible
    XLA device (pure SPMD — no communication; the planner factorizes the
    device count over both axes, so seed-heavy and uneven grids
    parallelize too). On CPU hosts expose one device per core with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=$(nproc)``. For
    large grids prefer calling ``repro.sim.sweep.run`` directly — chunked
    streaming execution and on-device reductions keep device memory and
    host transfers flat.
    """
    from repro.sim import sweep

    return sweep.run(ps, cfg, seeds, reduce="trace")
