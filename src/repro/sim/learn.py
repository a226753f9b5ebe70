"""Gossip-Learning layer: real per-node models on the simulation substrate.

The engine tracks the paper's *protocol* (model ids, incorporation bits,
queues); this layer attaches an actual parameter vector to every node and
turns the protocol's events into learning:

* **delivery** — when a D2D exchange delivers model 0's instance, the
  receiver merges the sender's *snapshotted* parameter vector into its own
  with a ``repro.core.merge.merge_weights`` policy (uniform / obs_count /
  staleness), applied through the fused ``gossip_merge_rows`` kernel
  (compiled on TPU, bit-identical jnp reference elsewhere). This is
  gossipy's MERGE_UPDATE semantics on the sim's contact process.
* **train completion** — when a node finishes a training job on a fresh
  observation (``fin_train``), it takes one local SGD step
  (``repro.optim.sgd``) on a minibatch of its synthetic stream: an
  *observation* of the paper = ``batch`` labeled samples here.
* **churn** — leaving the RZ union (or crash-restart) resets the replica
  to the shared init, exactly like the packed protocol state drop.
* **connection formation** — the parameter vector is snapshotted alongside
  the protocol's ``snap`` words, so what a partner receives is what the
  node held when the exchange started.

The synthetic task is a fixed linear teacher: ``y = argmax(x W* + σ g)``
over i.i.d. normal features — deterministic in ``data_seed``, shared by
every node and scenario (only the *timing* of events differs), so learning
curves are comparable across a (λ, T_T) sweep. Models come from
``repro.models.tiny`` (logistic regression / tiny MLP on a flat vector).

Everything is keyed off a hashable frozen :class:`LearnConfig` riding the
static ``SimConfig.learn`` jit argument — ``learn=None`` traces exactly
the learning-free program (no extra carry fields, no extra PRNG use).
**The learning layer never feeds back into the protocol**: with learning
enabled the protocol traces (availability, busy, stored, ...) stay bitwise
identical to the ``learn=None`` run at the same seed (the layer draws its
minibatches from its own fold_in chain, never from the engine's key), so
the paper-validation results are unchanged by carrying models — pinned in
``tests/test_sim_learn.py``.

Telemetry (per output sample, riding the sweep reductions like the fault
keys): ``test_acc`` (population mean test accuracy), ``test_acc_holders``
(mean over in-RZ model holders — the paper's per-user quantity),
``learn_obs`` (mean observations incorporated per holding node — the
measured twin of Lemma 4's stored information), and ``theta_var`` (mean
parameter variance across holders — the vanishing-variance diagnostic of
decentralized averaging, PAPERS.md: arXiv 2404.04616).

**Byzantine layer** (PR 10): adversarial classes
(``FaultClass.adv_mode``, see ``repro.sim.faults``) poison the payload
they *serve* — the attack transforms the connection-time snapshot in
:func:`poison_snapshots`, so the receive/merge path and every protocol
trace stay untouched; defenses (``LearnConfig.defense``, a
``repro.core.merge.DefenseConfig``) screen the peer inside
:func:`merge_deliveries` (non-finite guard → metadata count clip →
norm clip → distance gate → trimmed-median combine). A ``poisoned``
contamination flag propagates through accepted merges (the sim-side twin
of ``core.meanfield.solve_contamination_classes``) and cumulative
``merge_stats`` counters make the realized defense acceptance rates
measurable. All of it is gated: attack machinery only when
``faults.adversarial``, defense machinery only when
``defense.enabled`` — the off config traces the exact PR-8 program.
"""

from __future__ import annotations

import dataclasses

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.merge import (
    DefenseConfig, clip_peer_counts, distance_accept, merge_weights,
    norm_clip_factors, trimmed_peer,
)
from repro.kernels.gossip_merge import (
    gossip_merge_rows, gossip_merge_rows_scaled,
)
from repro.models import tiny
from repro.optim.optimizers import sgd
from repro.sim.compute import take_nodes

__all__ = ["LearnConfig", "LearnTask", "make_task", "init_fields",
           "reset_replicas", "merge_deliveries", "snapshot_params",
           "poison_snapshots", "train_completions", "learn_outputs",
           "LEARN_MODEL", "MS_ATTEMPT", "MS_ATTEMPT_POISON",
           "MS_NONFINITE", "MS_NORMCLIP", "MS_DISTREJ",
           "MS_DISTREJ_POISON", "N_MERGE_STATS"]

#: The model id the learning layer attaches to (deliveries/training of
#: other ids leave the parameter vectors untouched).
LEARN_MODEL = 0

#: Indices into the cumulative ``merge_stats`` counter (carried whenever
#: learning is on): delivery-merge attempts, attempts whose payload was
#: poisoned, non-finite peers skipped by the entry guard, peers down-scaled
#: by the norm clip, peers rejected by the distance gate, and
#: distance-rejections whose payload was poisoned. The *_POISON splits let
#: the contamination twin consume the measured defense acceptance rate.
(MS_ATTEMPT, MS_ATTEMPT_POISON, MS_NONFINITE, MS_NORMCLIP,
 MS_DISTREJ, MS_DISTREJ_POISON) = range(6)
N_MERGE_STATS = 6

#: Saturation for the observation counters. Merging *sums* the two counts
#: (the union-of-training-sets approximation, same as the datacenter
#: protocol's bookkeeping), which compounds roughly once per delivery —
#: unbounded it overflows float32 on long runs and turns the obs_count
#: weights into NaN. At the cap w_own = c/(c+p) is exactly 0.5.
CNT_CAP = 1.0e12


@dataclasses.dataclass(frozen=True)
class LearnConfig:
    """Hashable learning-twin parameters (static via ``SimConfig.learn``).

    ``merge_policy`` selects the ``repro.core.merge`` weighting; ``lr`` and
    ``batch`` govern the local SGD step taken at each train completion;
    ``label_noise`` is the teacher's logit noise σ (Bayes error > 0 keeps
    accuracy trajectories informative instead of saturating); ``data_seed``
    fixes the task (teacher, init, test set, stream) independently of the
    simulation seed.
    """

    model: str = "logreg"         # repro.models.tiny family
    n_features: int = 16
    n_classes: int = 2
    hidden: int = 16              # mlp only
    lr: float = 0.5
    batch: int = 8                # samples per local step (one observation)
    n_test: int = 256             # shared held-out set
    label_noise: float = 0.5      # teacher logit noise σ
    merge_policy: str = "obs_count"
    data_seed: int = 0
    defense: Any = None           # repro.core.merge.DefenseConfig; None or
                                  # a disabled config keeps the merge path
                                  # bitwise the undefended program

    def __post_init__(self):
        # delegate architecture validation (and fail at config build time)
        self.spec  # noqa: B018
        if self.lr <= 0.0 or self.batch < 1 or self.n_test < 1:
            raise ValueError("need lr > 0, batch >= 1, n_test >= 1")
        if self.label_noise < 0.0:
            raise ValueError("label_noise must be >= 0")
        if self.merge_policy not in ("uniform", "obs_count", "staleness"):
            raise ValueError(
                f"unknown merge policy {self.merge_policy!r}; known: "
                "'uniform', 'obs_count', 'staleness'"
            )
        if self.defense is not None and not isinstance(
            self.defense, DefenseConfig
        ):
            raise ValueError(
                "LearnConfig.defense must be a repro.core.merge."
                f"DefenseConfig (got {type(self.defense).__name__})"
            )

    @property
    def spec(self) -> tiny.TinySpec:
        return tiny.TinySpec(
            model=self.model, n_features=self.n_features,
            n_classes=self.n_classes, hidden=self.hidden,
        )

    @property
    def param_dim(self) -> int:
        return self.spec.dim

    @property
    def enabled(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class LearnTask:
    """Per-config constants hoisted out of the scan (all derived
    deterministically from ``LearnConfig.data_seed``)."""

    theta0: jnp.ndarray       # (D,) shared replica init
    w_true: jnp.ndarray       # (F, C) linear teacher
    x_test: jnp.ndarray       # (n_test, F)
    y_test: jnp.ndarray       # (n_test,)
    stream_key: jnp.ndarray   # base key of the per-slot minibatch stream


def _labels(key, lc: LearnConfig, x, w_true):
    """Teacher labels: ``argmax(x W* + σ g)`` (σ = 0 → noiseless), with the
    float32 product the task defines (``HIGHEST``: the TPU's default
    matmul precision would round the inputs to bfloat16)."""
    logits = jnp.matmul(x, w_true, precision=jax.lax.Precision.HIGHEST)
    if lc.label_noise > 0.0:
        logits = logits + lc.label_noise * jax.random.normal(
            key, logits.shape, jnp.float32
        )
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def make_task(lc: LearnConfig) -> LearnTask:
    base = jax.random.PRNGKey(lc.data_seed)
    k_teacher, k_init, k_test, k_ytest, k_stream = jax.random.split(
        jax.random.fold_in(base, 0x7EAC), 5
    )
    w_true = jax.random.normal(
        k_teacher, (lc.n_features, lc.n_classes), jnp.float32
    )
    x_test = jax.random.normal(k_test, (lc.n_test, lc.n_features), jnp.float32)
    return LearnTask(
        theta0=tiny.init_theta(k_init, lc.spec),
        w_true=w_true,
        x_test=x_test,
        y_test=_labels(k_ytest, lc, x_test, w_true),
        stream_key=k_stream,
    )


def init_fields(lc: LearnConfig, n: int, fc=None) -> dict:
    """Initial learning carry: every node (and every connection snapshot)
    starts at the shared init with zero observation count and zero age.

    ``fc`` is the (possibly None) ``FaultConfig``: an adversarial one adds
    the contamination-flag carry; an enabled trimmed defense adds the
    recent-peer ring buffer. Each extra field is gated so the off config
    keeps the PR-8 carry — except ``merge_stats``, which rides whenever
    learning is on (the non-finite entry guard is always armed)."""
    task = make_task(lc)
    theta = jnp.broadcast_to(task.theta0, (n, task.theta0.shape[0]))
    zeros = jnp.zeros((n,), jnp.float32)
    fields = dict(
        theta=theta, theta_cnt=zeros, theta_age=zeros,
        theta_snap=theta, snap_cnt=zeros, snap_age=zeros,
        merge_stats=jnp.zeros((N_MERGE_STATS,), jnp.int32),
    )
    if fc is not None and fc.adversarial:
        fields.update(
            poisoned=jnp.zeros((n,), bool),
            snap_poison=jnp.zeros((n,), bool),
        )
    dc = lc.defense
    if dc is not None and dc.enabled and dc.mode == "trimmed":
        fields.update(
            peer_buf=jnp.zeros(
                (n, dc.recent_peers, theta.shape[1]), jnp.float32
            ),
            peer_fill=jnp.zeros((n,), jnp.int32),
        )
    return fields


def reset_replicas(drop, theta, theta_cnt, theta_age, theta0, *,
                   poisoned=None, peer_fill=None):
    """Churn/crash: replica back to the shared init (the parameter-space
    twin of ``faults.drop_state``). Connection snapshots are *not* reset —
    like the protocol's ``snap`` words, they belong to the exchange. The
    contamination flag and the recent-peer buffer fill (when carried)
    reset with the replica: a fresh init is clean and peer-less."""
    out = dict(
        theta=jnp.where(drop[:, None], theta0[None, :], theta),
        theta_cnt=jnp.where(drop, 0.0, theta_cnt),
        theta_age=jnp.where(drop, 0.0, theta_age),
    )
    if poisoned is not None:
        out["poisoned"] = jnp.where(drop, False, poisoned)
    if peer_fill is not None:
        out["peer_fill"] = jnp.where(drop, 0, peer_fill)
    return out


def merge_deliveries(lc: LearnConfig, received, pidx, theta, theta_cnt,
                     theta_age, theta_snap, snap_cnt, snap_age, tau_l, *,
                     merge_stats, poisoned=None, snap_poison=None,
                     peer_buf=None, peer_fill=None) -> dict:
    """Apply the paper's merging transformation on this slot's deliveries.

    ``received (N,)`` flags receivers of model ``LEARN_MODEL``; ``pidx`` is
    the clipped partner (sender) index. The received coefficients are the
    sender's *snapshot at connection formation* — matching the protocol,
    which transfers ``snap``, not live state. Weights follow
    ``lc.merge_policy``; counts add (training-set union) and ages take the
    min (the merged instance is as fresh as its freshest input).

    The Byzantine screens run in order: (1) the **non-finite guard**
    (always armed — one NaN replica must not poison the population even
    with defenses off), then with an enabled ``lc.defense`` (2) the
    metadata **count clip**, (3) the **norm clip** (down-scales the
    payload, fused into the kernel), (4) the **distance gate** (rejects
    the merge outright), and (5) the **trimmed-median** combine against
    the recent-accepted-peer ring buffer. Cumulative ``merge_stats``
    counters record attempts/rejections (poison-attributed when the
    contamination carry rides along). Returns a dict of the updated
    fields (only the gated-in ones present).
    """
    n = theta.shape[0]
    # the parameter rows stay an indexed gather: on the TPU a one-hot
    # select of D words per row costs more than this row gather
    peer_theta = theta_snap[pidx]
    if snap_poison is not None:
        peer_cnt, peer_age, peer_poison = take_nodes(
            (snap_cnt, snap_age, snap_poison), pidx)
    else:
        peer_cnt, peer_age = take_nodes((snap_cnt, snap_age), pidx)
        peer_poison = jnp.zeros((n,), bool)

    # (1) non-finite entry guard: a corrupted payload or bookkeeping skips
    # the merge entirely (the receiver keeps its replica untouched)
    finite = (
        jnp.all(jnp.isfinite(peer_theta), axis=-1)
        & jnp.isfinite(peer_cnt) & jnp.isfinite(peer_age)
    )
    accept = received & finite

    dc = lc.defense if (lc.defense is not None and lc.defense.enabled) \
        else None
    scale = None
    norm_clipped = jnp.zeros((), jnp.int32)
    dist_rej = jnp.zeros((), jnp.int32)
    dist_rej_poison = jnp.zeros((), jnp.int32)
    if dc is not None:
        # (2) metadata count clip: bound the *claimed* peer count before it
        # reaches the merge weights and the count accumulation
        if dc.cnt_clip > 0.0:
            peer_cnt = clip_peer_counts(theta_cnt, peer_cnt, dc.cnt_clip)
        # (3) norm clip: down-scale an over-norm payload (fused into the
        # kernel via the per-row scale)
        if dc.norm_clip > 0.0:
            scale = norm_clip_factors(peer_theta, dc.norm_clip)
            norm_clipped = jnp.sum(accept & (scale < 1.0)).astype(jnp.int32)
        # (4) distance gate: reject peers outside the robust radius
        if dc.dist_gate > 0.0:
            gated_peer = (
                peer_theta if scale is None else scale[:, None] * peer_theta
            )
            near = distance_accept(
                theta, gated_peer, dc.dist_gate, dc.dist_floor
            )
            dist_rej = jnp.sum(accept & ~near).astype(jnp.int32)
            dist_rej_poison = jnp.sum(
                accept & ~near & peer_poison
            ).astype(jnp.int32)
            accept = accept & near

    w_own, _ = merge_weights(
        lc.merge_policy, theta_cnt, peer_cnt, theta_age, peer_age, tau_l
    )
    w_own = jnp.broadcast_to(jnp.asarray(w_own, jnp.float32), (n,))

    out = {}
    if dc is not None and dc.mode == "trimmed":
        # (5) trimmed mode: push the accepted (clipped) payload into the
        # ring buffer, then combine against the coordinate-wise median of
        # the recent accepted peers — a minority of poisoned entries
        # cannot move it
        pushed = (
            peer_theta if scale is None else scale[:, None] * peer_theta
        ).astype(jnp.float32)
        slot = jnp.mod(peer_fill, dc.recent_peers)
        buf_new = peer_buf.at[jnp.arange(n), slot].set(pushed)
        peer_buf = jnp.where(accept[:, None, None], buf_new, peer_buf)
        peer_fill = jnp.where(accept, peer_fill + 1, peer_fill)
        med = trimmed_peer(theta, peer_buf, peer_fill)
        theta = gossip_merge_rows(theta, med, w_own, accept)
        out.update(peer_buf=peer_buf, peer_fill=peer_fill)
    elif scale is not None:
        theta = gossip_merge_rows_scaled(
            theta, peer_theta, w_own, scale, accept
        )
    else:
        theta = gossip_merge_rows(theta, peer_theta, w_own, accept)

    theta_cnt = jnp.where(
        accept, jnp.minimum(theta_cnt + peer_cnt, CNT_CAP), theta_cnt
    )
    theta_age = jnp.where(
        accept, jnp.minimum(theta_age, peer_age), theta_age
    )

    stats = jnp.stack([
        jnp.sum(received).astype(jnp.int32),
        jnp.sum(received & peer_poison).astype(jnp.int32),
        jnp.sum(received & ~finite).astype(jnp.int32),
        norm_clipped,
        dist_rej,
        dist_rej_poison,
    ])
    out.update(
        theta=theta, theta_cnt=theta_cnt, theta_age=theta_age,
        merge_stats=merge_stats + stats,
    )
    if poisoned is not None:
        # contamination spreads through accepted poisoned payloads
        out["poisoned"] = poisoned | (accept & peer_poison)
    return out


def snapshot_params(newly, theta, theta_cnt, theta_age, theta_snap,
                    snap_cnt, snap_age, *, poisoned=None, snap_poison=None):
    """Snapshot the parameter vector (and its merge bookkeeping) when a
    connection forms — the learning twin of ``form_connections``'s
    ``snap``/``snap_has`` copy. The contamination flag (when carried)
    snapshots alongside: what a partner receives is as poisoned as the
    node was at connection time."""
    out = (
        jnp.where(newly[:, None], theta, theta_snap),
        jnp.where(newly, theta_cnt, snap_cnt),
        jnp.where(newly, theta_age, snap_age),
    )
    if snap_poison is None:
        return out
    return out + (jnp.where(newly, poisoned, snap_poison),)


def poison_snapshots(adv: dict, task: LearnTask, slot_idx, newly,
                     theta_snap, snap_cnt, snap_age, snap_poison):
    """Serve-side Byzantine attack: transform the *snapshot* adversarial
    nodes just took, leaving their live replica — and every protocol
    trace — untouched.

    ``adv`` holds the static per-node attack vectors
    (``repro.sim.faults.adv_vectors``). Modes: ``signflip`` serves the
    negated parameters amplified by ``adv_scale`` (scale 1 = the plain
    flip; larger scales are the classic boosted model-poisoning update),
    ``noise`` adds ``adv_scale``-σ Gaussian noise (keyed off the learning
    layer's own stream chain, never the engine key), ``replay`` always
    serves the shared init, and ``liar`` serves honest parameters under a
    bogus observation count ``adv_scale`` with age 0 (hijacking the
    ``obs_count``/``staleness`` weights). The served payload of an
    adversary is always flagged poisoned."""
    is_adv = jnp.asarray(adv["is_adv"])
    hit = newly & is_adv
    poisoned = theta_snap
    if adv["signflip"].any():
        poisoned = jnp.where(
            jnp.asarray(adv["signflip"])[:, None],
            -jnp.asarray(adv["scale"])[:, None] * poisoned, poisoned,
        )
    if adv["replay"].any():
        poisoned = jnp.where(
            jnp.asarray(adv["replay"])[:, None],
            task.theta0[None, :], poisoned,
        )
    if adv["noise"].any():
        k_noise = jax.random.fold_in(
            jax.random.fold_in(task.stream_key, 0xBAD), slot_idx
        )
        g = jax.random.normal(k_noise, theta_snap.shape, jnp.float32)
        poisoned = jnp.where(
            jnp.asarray(adv["noise"])[:, None],
            poisoned + jnp.asarray(adv["scale"])[:, None] * g, poisoned,
        )
    theta_snap = jnp.where(hit[:, None], poisoned, theta_snap)
    if adv["liar"].any():
        liar_hit = hit & jnp.asarray(adv["liar"])
        snap_cnt = jnp.where(liar_hit, jnp.asarray(adv["scale"]), snap_cnt)
        snap_age = jnp.where(liar_hit, 0.0, snap_age)
    snap_poison = jnp.where(hit, True, snap_poison)
    return theta_snap, snap_cnt, snap_age, snap_poison


def train_completions(lc: LearnConfig, task: LearnTask, slot_idx, did_train,
                      theta, theta_cnt, theta_age, dt):
    """One local SGD step per node that completed training this slot.

    The minibatch is drawn from the node's synthetic stream keyed on
    ``(data_seed, slot)`` — node ``i`` reads row ``i`` of the slot draw, so
    the stream is deterministic and *independent of the engine's PRNG
    chain* (the protocol stays bitwise identical with learning enabled).
    Ages advance by ``dt`` every slot and reset on a fresh local step;
    counts add the one incorporated observation.
    """
    n = theta.shape[0]
    k_slot = jax.random.fold_in(task.stream_key, slot_idx)
    kx, ky = jax.random.split(k_slot)
    x = jax.random.normal(kx, (n, lc.batch, lc.n_features), jnp.float32)
    y = _labels(ky, lc, x, task.w_true)
    spec = lc.spec
    grads = jax.vmap(jax.grad(lambda th, xb, yb: tiny.tiny_loss(
        spec, th, xb, yb
    )))(theta, x, y)
    stepped, _ = sgd(lc.lr).update(grads, {}, theta, slot_idx)
    theta = jnp.where(did_train[:, None], stepped, theta)
    theta_cnt = jnp.where(did_train, theta_cnt + 1.0, theta_cnt)
    theta_age = jnp.where(did_train, 0.0, theta_age + dt)
    return theta, theta_cnt, theta_age


def learn_outputs(lc: LearnConfig, task: LearnTask, theta, theta_cnt,
                  has_model, in_rz, *, merge_stats, poisoned=None,
                  cls1h=None) -> dict:
    """Per-sample learning telemetry (see the module docstring).

    Holder-conditioned means are masked means with an *explicit* fill for
    the zero-holder slot (no holders → ``test_acc_holders`` falls back to
    the population mean, counts/variance to 0) so a no-holder warmup
    window cannot NaN — or silently zero-bias — the sweep reductions.
    With the contamination carry on, adds ``poisoned_frac`` (poisoned
    fraction among in-RZ holders) and its per-class split
    ``poisoned_frac_c`` (the sim-side twin of
    ``solve_contamination_classes``)."""
    acc = tiny.tiny_accuracy(lc.spec, theta, task.x_test, task.y_test)  # (N,)
    hold = has_model[:, LEARN_MODEL] & in_rz
    w = hold.astype(jnp.float32)
    n_hold = jnp.sum(w)
    denom = jnp.maximum(n_hold, 1.0)
    any_hold = n_hold > 0.0
    mu = jnp.sum(w[:, None] * theta, axis=0) / denom                 # (D,)
    var = jnp.sum(
        w[:, None] * jnp.square(theta - mu[None, :]), axis=0
    ) / denom
    out = dict(
        test_acc=jnp.mean(acc),
        test_acc_holders=jnp.where(
            any_hold, jnp.sum(w * acc) / denom, jnp.mean(acc)
        ),
        learn_obs=jnp.where(any_hold, jnp.sum(w * theta_cnt) / denom, 0.0),
        theta_var=jnp.where(any_hold, jnp.mean(var), 0.0),
        merge_stats=merge_stats,
    )
    if poisoned is not None:
        p = poisoned.astype(jnp.float32)
        out["poisoned_frac"] = jnp.where(
            any_hold, jnp.sum(w * p) / denom, 0.0
        )
        in_cls = jnp.where(hold[:, None], cls1h.astype(jnp.float32), 0.0)
        n_c = jnp.sum(in_cls, axis=0)                                # (C,)
        out["poisoned_frac_c"] = jnp.where(
            n_c > 0.0,
            jnp.einsum("n,nc->c", p, in_cls) / jnp.maximum(n_c, 1.0),
            0.0,
        )
    return out
