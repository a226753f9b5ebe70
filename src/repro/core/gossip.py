"""Floating Gossip as a distributed-training protocol on a JAX device mesh.

This is the paper's scheme adapted to TPU pods (DESIGN.md §2). The mapping:

* an FG *node*  ⟷  a model replica living on one slice of the gossip mesh
  axes (e.g. one ``data`` index, or one ``(pod, data)`` pair in multi-pod);
* a D2D *contact* ⟷ one entry of a pairwise matching executed with
  ``jax.lax.ppermute`` under ``shard_map`` (both directions of a pair are in
  the same permutation, so the exchange is bidirectional like the paper's);
* *transfer success* S(a) and *busy* probability b ⟷ per-pair / per-node
  Bernoulli gates, symmetric across the pair (both ends derive the same
  random bits from (round, pair) so they agree on the outcome);
* *merging* ⟷ a weighted parameter average (``repro.core.merge``), with the
  observation-count bookkeeping mirroring the union of training sets;
* *churn* (nodes leaving the RZ) ⟷ probabilistic replica reset to the
  default model (fresh-initialization parameters);
* the paper's Prop. 1 insight — smaller transfers succeed more often — maps
  to *segmented gossip*: each round exchanges only ``1/segments`` of every
  leaf, cutting per-round link bytes (a beyond-paper optimization knob).

Matchings are static (``ppermute`` requires a static permutation); the round
index selects one via ``lax.switch``:

* ``random``    — K precomputed uniformly-random pairings: faithful to the
  paper's random opportunistic contacts;
* ``hypercube`` — partner = index XOR 2^(round mod log2 R): deterministic,
  every pair of replicas mixes within log2(R) rounds (beyond-paper variant
  with provably faster information spreading).

Everything here operates on parameter pytrees and is architecture-agnostic —
the whole assigned zoo trains under either mode (DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.merge import MergePolicy, merge_weights
from repro.kernels.gossip_merge import gossip_merge


__all__ = [
    "GossipConfig",
    "init_gossip_state",
    "hypercube_matchings",
    "random_matchings",
    "build_gossip_round",
    "protocol_from_meanfield",
]


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """Protocol parameters. The stochastic gates (success/busy/churn) are the
    mean-field operating point of the paper; see ``protocol_from_meanfield``.
    """

    axis_names: tuple[str, ...] = ("data",)
    period: int = 1                  # gossip every `period` optimizer steps
    matching: str = "random"         # "random" (paper) | "hypercube" (opt.)
    n_random_matchings: int = 16
    success_prob: float = 1.0        # S(a): transfer success per contact
    busy_prob: float = 0.0           # b: node unavailable this round
    churn_prob: float = 0.0          # α/N per round: replica reset
    merge_policy: MergePolicy = "obs_count"
    segments: int = 1                # segmented gossip (1 = whole model)
    seed: int = 0


def init_gossip_state(R: int) -> dict:
    """Per-replica bookkeeping, all shaped (R,), sharded on the gossip axes.

    ``count`` — observations (local batches) incorporated into the replica;
    ``age`` — steps since the replica last saw a fresh observation.
    """
    return dict(
        count=jnp.zeros((R,), jnp.float32),
        age=jnp.zeros((R,), jnp.float32),
    )


def hypercube_matchings(R: int) -> list[list[tuple[int, int]]]:
    if R & (R - 1):
        raise ValueError(f"hypercube matching needs power-of-two R, got {R}")
    out = []
    for k in range(int(math.log2(R))):
        out.append([(i, i ^ (1 << k)) for i in range(R)])
    return out


def random_matchings(R: int, K: int, seed: int) -> list[list[tuple[int, int]]]:
    """K random pairings — always involutions. Faithful to random D2D
    contacts.

    For even R every matching is a perfect pairing. For odd R one node per
    round is necessarily unmatched; it is **self-paired** (``perm[i] = i``),
    which the exchange treats as a no-op (``build_gossip_round`` gates
    success on ``partner != i``) — exactly a node that found no contact
    partner this round. The historical bug left the leftover node pointing
    at node 0 (a non-involution: the "exchange" was asymmetric).
    """
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(K):
        order = rng.permutation(R)
        # identity init: with odd R the leftover order[-1] self-pairs
        perm = list(range(R))
        for a, b in zip(order[0::2], order[1::2]):
            perm[a], perm[b] = b, a
        out.append([(i, perm[i]) for i in range(R)])
    return out


def _axis_sizes(mesh: Mesh, names: Sequence[str]) -> list[int]:
    return [mesh.shape[n] for n in names]


def _flat_axis_index(names: Sequence[str], sizes: Sequence[int]) -> jnp.ndarray:
    idx = jnp.asarray(0, jnp.int32)
    for n, s in zip(names, sizes):
        idx = idx * s + jax.lax.axis_index(n)
    return idx


def build_gossip_round(
    mesh: Mesh,
    param_specs: Any,            # pytree of PartitionSpec matching params
    cfg: GossipConfig,
):
    """Build ``round_fn(params, state, default_params, round_idx) -> (params, state)``.

    ``params`` leaves carry a leading replica axis of size R (= product of
    the gossip mesh axes), sharded over those axes; inner dims may be
    sharded over "model" — ppermute moves each model-parallel column to the
    same partner, so a logical replica merges coherently across its shards.
    """
    names = tuple(cfg.axis_names)
    sizes = _axis_sizes(mesh, names)
    R = int(np.prod(sizes))
    if cfg.matching == "hypercube":
        matchings = hypercube_matchings(R)
    elif cfg.matching == "random":
        matchings = random_matchings(R, cfg.n_random_matchings, cfg.seed)
    else:
        raise ValueError(f"unknown matching {cfg.matching!r}")
    partner_tab = jnp.asarray(
        [[dst for _, dst in m] for m in matchings], jnp.int32
    )  # (K, R)
    n_match = len(matchings)

    scalar_spec = P(names)

    def body(params, count, age, default, round_idx):
        i = _flat_axis_index(names, sizes)
        m = (round_idx % n_match).astype(jnp.int32)

        def exchange(k):
            perm = matchings[k]
            swap = lambda x: jax.lax.ppermute(x, names, perm)
            return (
                jax.tree.map(swap, params),
                swap(count),
                swap(age),
            )

        peer_params, peer_count, peer_age = jax.lax.switch(
            m, [lambda k=k: exchange(k) for k in range(n_match)]
        )
        partner = partner_tab[m, i]

        # --- symmetric stochastic gates (same bits on both ends) ---
        base = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), round_idx)
        pair_id = jnp.minimum(i, partner) * R + jnp.maximum(i, partner)
        k_pair = jax.random.fold_in(base, pair_id)
        transfer_ok = jax.random.uniform(k_pair, ()) < cfg.success_prob
        u_busy = jax.random.uniform(jax.random.fold_in(base, i), ())
        u_busy_peer = jax.random.uniform(jax.random.fold_in(base, partner), ())
        both_free = (u_busy >= cfg.busy_prob) & (u_busy_peer >= cfg.busy_prob)
        success = transfer_ok & both_free & (partner != i)

        # --- merge (paper's weighted-coefficient average) ---
        c_own, c_peer = count[0], peer_count[0]
        a_own, a_peer = age[0], peer_age[0]
        w_own, w_peer = merge_weights(
            cfg.merge_policy, c_own, c_peer, a_own, a_peer, tau_l=1.0e4
        )

        def merge_leaf(x, px):
            if cfg.segments <= 1:
                # the fused Pallas kernel (compiled on TPU; its bit-identical
                # jnp reference elsewhere — w_peer == 1 - w_own exactly, so
                # the reference reproduces the historical inline expression)
                return gossip_merge(x, px, w_own, success)
            # segmented gossip: merge only chunk (round mod segments)
            flat = x.reshape(-1)
            pflat = px.reshape(-1)
            seg_len = -(-flat.shape[0] // cfg.segments)
            pad = seg_len * cfg.segments - flat.shape[0]
            flat_p = jnp.pad(flat, (0, pad))
            pflat_p = jnp.pad(pflat, (0, pad))
            s = (round_idx % cfg.segments).astype(jnp.int32) * seg_len
            seg = jax.lax.dynamic_slice(flat_p, (s,), (seg_len,))
            pseg = jax.lax.dynamic_slice(pflat_p, (s,), (seg_len,))
            mseg = (w_own * seg.astype(jnp.float32)
                    + w_peer * pseg.astype(jnp.float32)).astype(x.dtype)
            mseg = jnp.where(success, mseg, seg)
            out = jax.lax.dynamic_update_slice(flat_p, mseg, (s,))
            return out[: flat.shape[0]].reshape(x.shape)

        new_params = jax.tree.map(merge_leaf, params, peer_params)
        # training-set union ≈ count sum; staleness = min age
        new_count = jnp.where(success, count + peer_count, count)
        new_age = jnp.where(success, jnp.minimum(age, peer_age), age)

        # --- churn: replica exits the RZ and is replaced by a default one ---
        if cfg.churn_prob > 0.0:
            u_churn = jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(base, i), 0x5EED), ()
            )
            reset = u_churn < cfg.churn_prob
            new_params = jax.tree.map(
                lambda x, d: jnp.where(reset, d, x), new_params, default
            )
            new_count = jnp.where(reset, jnp.zeros_like(new_count), new_count)
            new_age = jnp.where(reset, jnp.zeros_like(new_age), new_age)

        return new_params, new_count, new_age

    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, scalar_spec, scalar_spec, param_specs, P()),
        out_specs=(param_specs, scalar_spec, scalar_spec),
        check_vma=False,
    )

    def round_fn(params, state: dict, default_params, round_idx):
        params, count, age = sharded(
            params, state["count"], state["age"], default_params,
            jnp.asarray(round_idx, jnp.int32),
        )
        return params, dict(count=count, age=age)

    return round_fn, R


def protocol_from_meanfield(p, sol, *, round_interval: float, **overrides):
    """Instantiate GossipConfig gates from a mean-field operating point.

    Bridges the paper's analysis to the datacenter protocol: per-round
    transfer success = S(a), busy prob = b, churn per round = α/N · Δt.
    """
    kw = dict(
        success_prob=float(sol.S),
        busy_prob=float(sol.b),
        churn_prob=min(float(p.alpha / p.N * round_interval), 1.0),
    )
    kw.update(overrides)
    return GossipConfig(**kw)
