"""Model-merging operators (the paper's *merging* transformation).

The paper defines merging as a transformation of two instances of a model
whose output's training set is the union of the inputs' training sets; for
ANNs "the coefficients of the model obtained through merging are derived as a
weighted average of the coefficients of the merged model instances".

We implement that weighted average with three weighting policies:

* ``uniform``    — plain 0.5/0.5 average (classic gossip averaging);
* ``obs_count``  — weights proportional to the number of observations each
  instance has incorporated (FedAvg-style; mirrors the union-of-training-sets
  semantics: the count of the merged instance is the sum, approximating the
  union under the paper's "non-unique data points" caveat);
* ``staleness``  — weights ``exp(-age / tau_l)``: fresher instances dominate,
  reflecting the paper's observation-lifetime τ_l.

These run inside the gossip protocol (see ``repro.core.gossip``) and are the
op that the ``gossip_merge`` Pallas kernel fuses on TPU.

Byzantine defenses (:class:`DefenseConfig`, riding ``LearnConfig.defense``)
screen the peer *before* the weighted average:

* ``norm_clip``   — scale an over-norm peer payload down to the clip radius
  (bounds the energy any single poisoned merge can inject);
* ``dist_gate``   — reject peers farther than a robust radius from the own
  parameters; the radius is *relative* (``dist_gate * (dist_floor +
  ‖own‖)``) so the gate is scale-free as training grows ‖θ‖;
* ``cnt_clip``    — clamp the peer's *claimed* observation count to a
  multiple of the own count (defeats inflated-metadata lying that would
  hijack the ``obs_count``/``staleness`` weights);
* ``mode="trimmed"`` — merge against the coordinate-wise median of the
  ``recent_peers`` last *accepted* peer payloads instead of the raw peer
  (a minority of poisoned entries cannot move the median).

The primitives here are pure jnp; the sim learning layer
(``repro.sim.learn.merge_deliveries``) composes them with the per-row
``gossip_merge_rows``/``gossip_merge_rows_scaled`` kernel path.
"""

from __future__ import annotations

import dataclasses

from typing import Literal

import jax
import jax.numpy as jnp

__all__ = ["merge_weights", "merge_pytrees", "MergePolicy", "DefenseConfig",
           "norm_clip_factors", "distance_accept", "clip_peer_counts",
           "trimmed_peer"]

MergePolicy = Literal["uniform", "obs_count", "staleness"]

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class DefenseConfig:
    """Hashable Byzantine-defense knobs (static via ``LearnConfig.defense``).

    Every knob at its default is *off*: a default/``None`` config keeps the
    merge path bitwise the undefended program. ``norm_clip``/``dist_gate``/
    ``cnt_clip`` at ``0.0`` disable that screen; ``mode="average"`` is the
    plain weighted average."""

    norm_clip: float = 0.0     # clip radius for the peer payload norm
    dist_gate: float = 0.0     # accept iff ||peer-own|| <= gate*(floor+||own||)
    dist_floor: float = 1e-3   # absolute floor of the relative gate radius
    cnt_clip: float = 0.0      # cap peer_cnt at cnt_clip * (1 + own_cnt)
    mode: str = "average"      # "average" | "trimmed"
    recent_peers: int = 3      # trimmed mode: accepted-peer ring buffer size

    def __post_init__(self):
        for r in (self.norm_clip, self.dist_gate, self.cnt_clip):
            if r < 0.0:
                raise ValueError("defense radii/clips must be >= 0")
        if self.dist_floor <= 0.0:
            raise ValueError("dist_floor must be > 0")
        if self.mode not in ("average", "trimmed"):
            raise ValueError(
                f"unknown defense mode {self.mode!r}; known: "
                "'average', 'trimmed'"
            )
        if self.mode == "trimmed" and self.recent_peers < 1:
            raise ValueError("trimmed mode needs recent_peers >= 1")

    @property
    def enabled(self) -> bool:
        return (
            self.norm_clip > 0.0
            or self.dist_gate > 0.0
            or self.cnt_clip > 0.0
            or self.mode != "average"
        )


def norm_clip_factors(peer_theta, radius: float):
    """(N,) f32 down-scaling factor ``min(1, radius/||peer||)`` per row
    (1 everywhere for in-radius peers — the honest path is untouched)."""
    nrm = jnp.linalg.norm(peer_theta.astype(jnp.float32), axis=-1)
    return jnp.minimum(1.0, radius / jnp.maximum(nrm, _EPS))


def distance_accept(own_theta, peer_theta, gate: float, floor: float):
    """(N,) bool acceptance of the relative robust-radius gate:
    ``||peer - own|| <= gate * (floor + ||own||)``, with a cold-replica
    escape — a near-init own replica (``||own|| <= floor``) accepts
    anything, because it has no trust anchor yet and rejecting would also
    reject every *honest* trained peer (a freshly churn-reset node sits as
    far from the honest consensus as a poisoned payload does). The radius
    depends only on the *receiver's* state, so an attacker cannot inflate
    its own acceptance threshold."""
    own = own_theta.astype(jnp.float32)
    own_nrm = jnp.linalg.norm(own, axis=-1)
    d = jnp.linalg.norm(peer_theta.astype(jnp.float32) - own, axis=-1)
    return (d <= gate * (floor + own_nrm)) | (own_nrm <= floor)


def clip_peer_counts(own_cnt, peer_cnt, clip: float):
    """Clamp the peer's claimed observation count to ``clip * (1 +
    own_cnt)`` — the metadata-liar screen."""
    return jnp.minimum(peer_cnt, clip * (1.0 + own_cnt))


def trimmed_peer(own_theta, peer_buf, peer_fill):
    """Coordinate-wise median over {own} ∪ {valid ring-buffer entries}.

    ``peer_buf`` is (N, B, D) of the last accepted peer payloads, written
    ring-wise; ``peer_fill`` (N,) counts total accepted peers, so entries
    ``min(fill, B)`` onward are unwritten and are masked to the *own* row
    (a cold buffer merges a node with itself — a no-op)."""
    n, b, _ = peer_buf.shape
    valid = jnp.arange(b)[None, :] < jnp.minimum(peer_fill, b)[:, None]
    own = own_theta.astype(jnp.float32)[:, None, :]
    buf = jnp.where(valid[:, :, None], peer_buf.astype(jnp.float32), own)
    return jnp.median(jnp.concatenate([own, buf], axis=1), axis=1)


def merge_weights(
    policy: MergePolicy,
    own_count: jnp.ndarray,
    peer_count: jnp.ndarray,
    own_age: jnp.ndarray,
    peer_age: jnp.ndarray,
    tau_l: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Return (w_own, w_peer) with w_own + w_peer == 1."""
    if policy == "uniform":
        w_own = jnp.asarray(0.5)
    elif policy == "obs_count":
        # two untrained replicas (both counts zero) merge symmetrically:
        # without the fallback w_own = 0/0 would be NaN. The safe divisor
        # only fills the untaken branch, so fractional (clipped) counts
        # keep their exact ratio.
        tot = own_count + peer_count
        w_own = jnp.where(
            tot > 0.0, own_count / jnp.where(tot > 0.0, tot, 1.0), 0.5
        )
    elif policy == "staleness":
        # shift by the min age: w_own only depends on the age *gap*, and
        # the fresher side's score is exactly 1, so two equally-ancient
        # instances split 0.5/0.5 instead of exp underflowing both scores
        # to zero (w_own = 0/eps = 0, an asymmetric merge of equals)
        m = jnp.minimum(own_age, peer_age)
        s_own = jnp.exp(-(own_age - m) / tau_l)
        s_peer = jnp.exp(-(peer_age - m) / tau_l)
        w_own = s_own / (s_own + s_peer)
    else:
        raise ValueError(f"unknown merge policy {policy!r}")
    return w_own, 1.0 - w_own


def merge_pytrees(own, peer, w_own, w_peer):
    """Leafwise weighted average: the ANN merging operation of §III-B."""
    return jax.tree.map(
        lambda a, b: (w_own * a.astype(jnp.float32)
                      + w_peer * b.astype(jnp.float32)).astype(a.dtype),
        own, peer,
    )
