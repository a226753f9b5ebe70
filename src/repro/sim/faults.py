"""Fault-injection layer: per-node behavior classes for the simulator.

The paper's model assumes every node is always-on and every transfer either
completes or is cut only by RZ exit. Real opportunistic deployments see

* **duty-cycled radios** — a per-node two-state on/off Markov process. The
  accessibility of all N nodes is packed into ``ceil(N/32)`` uint32 words
  (the :func:`repro.sim.compute.pack_mask` layout) carried in ``SimState``;
  an *off* node neither detects contacts, nor can be contacted, nor serves
  (ongoing exchanges break, compute timers freeze, no new jobs start, no
  observations are recorded). Its protocol state is kept — sleep is not
  churn.
* **mid-transfer link failure** — each link end dies at ``link_fail_rate``
  [1/s]; a failed link breaks the ongoing exchange exactly like moving out
  of radio range (instances whose transfer already completed are still
  delivered).
* **per-contact transfer abort** — a newly matched pair aborts connection
  setup with probability ``p_abort`` (both ends see the same coin, so the
  abort is symmetric and the pair simply never forms).
* **crash-restart churn** — each node crashes at ``crash_rate`` [1/s] and
  restarts immediately, dropping its packed protocol state through exactly
  the ``zone_churn`` drop path (:func:`drop_state`).
* **free-riders** — class-flagged nodes that receive model instances but
  never serve them to a partner.
* **Byzantine (adversarial) classes** — nodes that follow the *protocol*
  honestly but poison the *learning* payload they serve
  (``FaultClass.adv_mode``): sign-flipped parameters (``"signflip"``),
  scaled-noise injection (``"noise"``), stale replay of the shared init
  (``"replay"``), or inflated-metadata lying (``"liar"`` — bogus
  ``theta_cnt``/``theta_age`` that hijack the ``obs_count``/``staleness``
  merge weights). Attacks apply at the *serve side* of the learning layer
  (``repro.sim.learn.poison_snapshots``), never to the protocol state, so
  an adversarial-only config keeps ``enabled == False`` and the protocol
  traces bitwise ``faults=None``; :attr:`FaultConfig.adversarial` gates
  the learn-layer machinery instead.

Everything here is keyed off a hashable frozen :class:`FaultConfig` riding
the static ``SimConfig`` jit argument. The all-zero-rates config reports
``enabled == False`` and the engine then traces **exactly** the fault-free
program (no extra PRNG splits, no extra carry fields) — pinned bitwise in
``tests/test_sim_faults.py``.

Class membership is static: nodes are assigned to classes in contiguous
index blocks by :func:`node_classes` (deterministic, shape-only), so the
per-node rate vectors are compile-time constants and the per-class
telemetry is a fixed one-hot contraction.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.sim import compute

__all__ = [
    "FaultClass", "FaultConfig", "node_classes", "class_onehot",
    "init_avail", "duty_step", "drop_state", "link_fail", "abort_matches",
    "gate_deliveries", "fault_outputs", "adv_vectors", "ADV_MODES",
    "EV_ABORT", "EV_LINKFAIL", "EV_CRASH", "N_EVENTS",
]

#: Indices into the cumulative ``fault_events`` counter carried by the
#: engine (node-level events; symmetric pair events count both ends).
EV_ABORT, EV_LINKFAIL, EV_CRASH = 0, 1, 2
N_EVENTS = 3

#: Known adversarial serve-side behaviors (``FaultClass.adv_mode``).
#: ``"none"`` = honest; the others poison the served learning payload.
ADV_MODES = ("none", "signflip", "noise", "replay", "liar")


@dataclasses.dataclass(frozen=True)
class FaultClass:
    """One behavior class: a fraction of the population sharing duty-cycle
    rates, the free-rider flag and the adversarial serve behavior.
    ``rate_off == 0`` means always-on; ``adv_mode == "none"`` means honest.

    ``adv_scale`` parameterizes the attack: the noise σ for ``"noise"``
    and the claimed (bogus) observation count for ``"liar"``; it is unused
    by ``"signflip"``/``"replay"``."""

    frac: float = 1.0        # fraction of nodes in this class
    rate_off: float = 0.0    # on -> off transition rate [1/s]
    rate_on: float = 0.0     # off -> on transition rate [1/s]
    free_rider: bool = False  # receives but never serves
    adv_mode: str = "none"   # serve-side attack (see ADV_MODES)
    adv_scale: float = 1.0   # attack magnitude (noise sigma / liar count)
    name: str = "default"

    @property
    def duty(self) -> float:
        """Stationary accessible (on) fraction of the two-state chain."""
        if self.rate_off <= 0.0:
            return 1.0
        if self.rate_on <= 0.0:
            return 0.0
        return self.rate_on / (self.rate_on + self.rate_off)


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Hashable fault model (a static jit argument via ``SimConfig.faults``).

    ``classes`` partitions the population (fractions must sum to 1);
    ``link_fail_rate``/``crash_rate`` are per-node Poisson rates [1/s] and
    ``p_abort`` a per-contact probability. The all-default config is
    *disabled*: the engine then traces the exact fault-free program.
    """

    classes: tuple = (FaultClass(),)
    link_fail_rate: float = 0.0   # per link-end mid-transfer failure [1/s]
    p_abort: float = 0.0          # per-contact connection-setup abort prob
    crash_rate: float = 0.0       # per-node crash-restart rate [1/s]

    def __post_init__(self):
        if not self.classes:
            raise ValueError("FaultConfig needs at least one FaultClass")
        fracs = [c.frac for c in self.classes]
        if any(f < 0 for f in fracs) or abs(sum(fracs) - 1.0) > 1e-6:
            raise ValueError(
                f"class fractions must be >= 0 and sum to 1, got {fracs}"
            )
        for r in (self.link_fail_rate, self.crash_rate):
            if r < 0:
                raise ValueError("fault rates must be >= 0")
        if not 0.0 <= self.p_abort < 1.0:
            raise ValueError("p_abort must be in [0, 1)")
        for c in self.classes:
            if c.adv_mode not in ADV_MODES:
                raise ValueError(
                    f"unknown adv_mode {c.adv_mode!r}; known: {ADV_MODES}"
                )
            if c.adv_mode != "none" and c.adv_scale <= 0.0:
                raise ValueError("adversarial classes need adv_scale > 0")

    @property
    def n_classes(self) -> int:
        return len(self.classes)

    @property
    def enabled(self) -> bool:
        """True iff any *protocol* fault mechanism is active. Disabled
        configs keep the engine bitwise-identical to ``faults=None``.
        Adversarial serve behavior is deliberately excluded: Byzantine
        nodes follow the protocol honestly (see :attr:`adversarial`), so
        an attack-only config still traces the fault-free protocol."""
        return (
            self.link_fail_rate > 0.0
            or self.p_abort > 0.0
            or self.crash_rate > 0.0
            or any(
                c.rate_off > 0.0 or c.free_rider for c in self.classes
            )
        )

    @property
    def adversarial(self) -> bool:
        """True iff any class poisons the learning payload it serves.
        Gates the learn-layer attack machinery (``repro.sim.learn``)
        independently of :attr:`enabled`."""
        return any(c.adv_mode != "none" for c in self.classes)

    @property
    def adv_frac(self) -> float:
        """Population fraction of adversarial nodes."""
        return sum(c.frac for c in self.classes if c.adv_mode != "none")


def node_classes(fc: FaultConfig, n: int) -> np.ndarray:
    """(N,) int32 static class id per node: contiguous index blocks sized
    by the class fractions (block boundaries at ``round(cumsum(frac)*N)``,
    the last class absorbing rounding remainder)."""
    bounds = np.round(
        np.cumsum([c.frac for c in fc.classes]) * n
    ).astype(np.int64)
    bounds[-1] = n
    ids = np.zeros((n,), np.int32)
    lo = 0
    for ci, hi in enumerate(bounds):
        ids[lo:hi] = ci
        lo = max(lo, int(hi))
    return ids


def class_onehot(fc: FaultConfig, n: int) -> np.ndarray:
    """(N, C) bool static class-membership matrix."""
    ids = node_classes(fc, n)
    return ids[:, None] == np.arange(fc.n_classes, dtype=np.int32)[None, :]


def adv_vectors(fc: FaultConfig, n: int) -> dict:
    """Static per-node attack vectors (numpy — compile-time constants).

    Returns ``is_adv`` (N,) bool plus one bool mask per attack mode
    (``signflip``/``noise``/``replay``/``liar``) and ``scale`` (N,) f32
    (the class ``adv_scale`` broadcast to its members)."""
    ids = node_classes(fc, n)
    modes = np.asarray([c.adv_mode for c in fc.classes])[ids]
    return dict(
        is_adv=modes != "none",
        signflip=modes == "signflip",
        noise=modes == "noise",
        replay=modes == "replay",
        liar=modes == "liar",
        scale=np.asarray(
            [c.adv_scale for c in fc.classes], np.float32
        )[ids],
    )


def init_avail(n: int) -> jnp.ndarray:
    """Initial packed availability word: every node on (the duty chain
    relaxes to its stationary distribution within the warmup)."""
    return compute.pack_mask(jnp.ones((n,), bool)[None, :])[0]


def duty_step(k, availw, p_off, p_on, n: int):
    """One slot of the per-node on/off Markov chain.

    ``availw`` is the packed ``ceil(N/32)``-word availability;
    ``p_off``/``p_on`` the per-node per-slot transition probabilities
    (``1 - exp(-rate * dt)``, compile-time constants). Returns
    ``(availw_new, on)`` with ``on`` the (N,) bool accessibility of this
    slot."""
    on_prev = compute.unpack_mask(availw[None, :], n)[0]
    u = jax.random.uniform(k, (n,))
    on = jnp.where(on_prev, u >= p_off, u < p_on)
    return compute.pack_mask(on[None, :])[0], on


def drop_state(drop, *, inc, has_model, tq_model, mq_model, serving,
               serv_left):
    """Drop the packed protocol state of the flagged nodes.

    This is the *single* state-drop path of the engine: zone churn
    (``engine.zone_churn``) and crash-restart churn both apply it, so the
    "what is lost" semantics cannot drift apart. ``drop`` is an (N,) bool.
    """
    return dict(
        inc=jnp.where(drop[:, None, None], jnp.uint32(0), inc),
        has_model=jnp.where(drop[:, None], False, has_model),
        tq_model=jnp.where(drop[:, None], -1, tq_model),
        mq_model=jnp.where(drop[:, None], -1, mq_model),
        serving=jnp.where(drop, -1, serving),
        serv_left=jnp.where(drop, 0.0, serv_left),
    )


def link_fail(k, p_link, partner):
    """Symmetric per-slot mid-transfer link failure mask.

    Each node draws one uniform; the pair link fails when *either* end's
    draw is below ``p_link`` (so both ends observe the same break —
    ``fail[i]`` implies ``fail[partner[i]]``). Only meaningful where
    ``partner >= 0``."""
    n = partner.shape[0]
    pidx = jnp.clip(partner, 0, n - 1)
    u = jax.random.uniform(k, (n,))
    return (u < p_link) | (compute.take_nodes(u, pidx) < p_link)


def abort_matches(k, p_abort, match):
    """Symmetric per-contact setup abort: ``(match_new, aborted)``.

    Both ends of a matched pair read the coin of the lower node index, so
    either both abort or neither does and the mutual-match invariant
    (``match[match[i]] == i``) is preserved."""
    n = match.shape[0]
    pair_lo = jnp.minimum(
        jnp.arange(n, dtype=match.dtype), jnp.clip(match, 0, n - 1)
    )
    u = jax.random.uniform(k, (n,))
    aborted = (match >= 0) & (compute.take_nodes(u, pair_lo) < p_abort)
    return jnp.where(aborted, -1, match), aborted


def gate_deliveries(delivered, pidx, is_free_rider):
    """Suppress deliveries whose *sender* is a free-rider.

    ``delivered`` is the (N, M) receiver-side delivery flags and ``pidx``
    the clipped partner (sender) index; a free-rider still receives (its
    own row is untouched) but never appears as a server."""
    return delivered & ~compute.take_nodes(is_free_rider, pidx)[:, None]


def fault_outputs(*, on, in_rz, has_model, cls1h, n_per_class,
                  fault_events) -> dict:
    """Per-sample degradation telemetry.

    Returns ``availability_c`` (M, C) — per-class model availability among
    in-RZ class members, the sim-side twin of
    ``meanfield.solve_fixed_point_classes``'s per-class ``a`` —
    ``on_frac_c`` (C,) accessible fraction per class, ``n_in_rz_c`` (C,)
    and the cumulative ``fault_events`` (abort/link-fail/crash) counters.
    Counts are exact in f32 (<= N), so the one-hot contraction is bitwise
    the boolean sum."""
    cls_f = cls1h.astype(jnp.float32)                         # (N, C)
    in_cls = jnp.where(in_rz[:, None], cls_f, 0.0)
    n_rz_c = jnp.sum(in_cls, axis=0)                          # (C,)
    avail_c = (
        jnp.einsum("nm,nc->mc", has_model.astype(jnp.float32), in_cls)
        / jnp.maximum(n_rz_c, 1.0)[None, :]
    )
    on_frac_c = (
        jnp.sum(jnp.where(on[:, None], cls_f, 0.0), axis=0)
        / jnp.maximum(n_per_class, 1.0)
    )
    return dict(
        availability_c=avail_c,
        on_frac_c=on_frac_c,
        n_in_rz_c=n_rz_c.astype(jnp.int32),
        fault_events=fault_events,
    )
