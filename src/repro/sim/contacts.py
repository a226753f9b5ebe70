"""D2D contact dynamics: pair matching, exchange progression, deliveries.

Implements the paper's §III-B contact protocol: two non-busy nodes inside
the RZ that *newly* come within the transmission radius establish a
connection (setup time ``t0``), snapshot their model instances and exchange
them one at a time (``T_L`` each, in a per-connection random order),
staying busy until the exchange finishes or the contact breaks. Instances
whose cumulative transfer time fit in the effective contact duration are
delivered at the moment the exchange ends.

The O(N²) pairwise sweep is delegated to ``repro.kernels.contacts`` and
runs as two stages — :func:`pairwise_close` (positions/RZ only: the
**bit-packed** ``ceil(N/32)``-word contact matrix plus the d² context;
shared per seed in sweep batches) and :func:`match_candidates` (the
per-run best new-contact candidate + mutual-best matching). On TPU the
fused Pallas kernel runs the whole sweep in the second stage instead.
Only per-node work — the partner-proximity bit and the mutual-best
check — remains here; its reads of a partner's row go through
``repro.sim.compute.take_nodes`` (one-hot in the dense range: a select,
or an MXU product for wide rows; a gather above it). Exchange snapshots (``snap``) travel bit-packed as
well.

This module is the *dense* contact backend. For large N the engine
swaps these stages for the O(N) cell-list backend (``repro.sim.cells``,
``SimConfig.contact_backend``), which reuses :func:`pair_still_close`
and :func:`mutualize` and is match-for-match equivalent while never
materializing an (N, N) object.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.contacts import (apply_access, candidate_best_ref,
                                    pairwise_close_ref)
from repro.sim.compute import take_nodes

__all__ = [
    "mutualize",
    "mutual_best_pairs",
    "close_matrix",
    "pair_still_close",
    "pairwise_close",
    "match_candidates",
    "partner_close_bit",
    "advance_exchanges",
    "compute_deliveries",
    "form_connections",
]


def mutualize(best: jnp.ndarray, has: jnp.ndarray) -> jnp.ndarray:
    """Reciprocity check shared by the dense, packed, and cell-list
    matchers: keep ``best[i]`` only where i and best[i] each have a
    candidate and point at each other; -1 elsewhere. ``best`` may carry
    the -1 no-candidate sentinel (it indexes the last row, which the
    ``has`` gate then discards)."""
    n = best.shape[0]
    best_of_best, has_best = take_nodes((best, has), best)
    mutual = (best_of_best == jnp.arange(n)) & has & has_best
    return jnp.where(mutual, best, -1)


_mutualize = mutualize


def mutual_best_pairs(scores: jnp.ndarray) -> jnp.ndarray:
    """Greedy-ish pair matching: i<->j paired iff each is the other's best.

    ``scores`` is (N, N) with +inf for ineligible pairs. Returns partner
    index per node, or -1. Mutual-best matching misses some simultaneous
    contacts, which is rare at the paper's densities (validated vs g).
    """
    best = jnp.argmin(scores, axis=1)
    has = jnp.isfinite(jnp.min(scores, axis=1))
    return _mutualize(best, has)


def close_matrix(pos: jnp.ndarray, in_rz: jnp.ndarray, r_tx) -> jnp.ndarray:
    """(N, N) proximity matrix among in-RZ nodes (zero diagonal), plus the
    squared-distance matrix it was thresholded from.

    Written as two (N, N) elementwise squares rather than a reduce over a
    materialized (N, N, 2) difference — bitwise the same sum, but it lowers
    to plain vector code (the broadcast-reduce form is the slowest op of
    the batched step on CPU). Kept as the dense-boolean reference (the
    mobility contact-rate probe uses it); the engine hot path runs the
    packed :func:`pairwise_close` / :func:`match_candidates` stages
    instead."""
    n = pos.shape[0]
    dx = pos[:, None, 0] - pos[None, :, 0]
    dy = pos[:, None, 1] - pos[None, :, 1]
    d2 = dx * dx + dy * dy
    close = (d2 <= r_tx**2) & in_rz[:, None] & in_rz[None, :]
    return close & ~jnp.eye(n, dtype=bool), d2


def pair_still_close(pos, zonew, partner, r_tx2, access=None):
    """The contact-matrix entries ``close[i, partner[i]]``, one per node.

    ``zonew`` is the ``(N,)`` uint32 zone-membership word
    (``repro.kernels.contacts.zone_words``); the pair is still close iff
    within radius *and* still sharing a zone. Bitwise the same value as
    ``close[i, partner[i]]`` of the dense matrix (same subtraction
    order), without materializing it; only meaningful where
    ``partner >= 0``. ``access`` is the optional per-node accessibility
    mask of the fault layer (``repro.kernels.contacts.apply_access``) —
    a duty-cycled node that switched off breaks its pair exactly like
    leaving radio range."""
    zonew = apply_access(zonew, access)
    n = pos.shape[0]
    pidx = jnp.clip(partner, 0, n - 1)
    ppos, pzone = take_nodes((pos, zonew), pidx)
    dx = pos[:, 0] - ppos[:, 0]
    dy = pos[:, 1] - ppos[:, 1]
    d2 = dx * dx + dy * dy
    return (d2 <= r_tx2) & ((zonew & pzone) != 0) \
        & (jnp.arange(n) != pidx)


def pairwise_close(pos, member, r_tx2, access=None):
    """Shared stage of the per-slot pairwise sweep: ``(closew, d2ctx)``.

    ``member`` is the ``(N,)`` bool single-RZ membership or the
    ``(N, K)`` multi-zone membership matrix (contacts then require a
    shared zone). ``closew`` is the packed contact matrix of this slot
    (the next ``prev_close`` carry); ``d2ctx`` is the backend context
    :func:`match_candidates` finishes the candidate search from. Both
    depend only on positions and zone membership — in sweep batches they
    are computed once per seed and broadcast over scenarios. On TPU the
    kernel fuses the whole sweep instead: the context carries the raw
    inputs and :func:`match_candidates` invokes the fused kernel.
    """
    if jax.default_backend() == "tpu":
        return None, (pos, apply_access(member, access), r_tx2)
    closew, d2b3 = pairwise_close_ref(pos, member, r_tx2, access=access)
    return closew, (closew, d2b3)


def match_candidates(d2ctx, prevw, elig):
    """Per-run stage: mutual-best matching among new eligible contacts.

    Returns ``(closew, match)``: the bit-packed contact matrix (the next
    ``prev_close`` carry) and the mutual-best partner index (or -1) among
    *candidate* pairs — newly in contact (not close in ``prevw``) with
    both sides eligible. Equivalent to scoring
    ``where(new_contact & elig_i & elig_j, d2, inf)`` through
    :func:`mutual_best_pairs` without materializing the (N, N) score
    matrix — bitwise so, pinned by the engine equivalence tests."""
    if jax.default_backend() == "tpu":
        pos, member, r_tx2 = d2ctx
        from repro.kernels.contacts import pairwise_contacts

        closew, best_j, has = pairwise_contacts(
            pos, member, elig, prevw, r_tx2, interpret=False
        )
        return closew, _mutualize(best_j, has)
    closew, d2b3 = d2ctx
    best_j, has = candidate_best_ref(d2b3, closew, prevw, elig)
    return closew, _mutualize(best_j, has)


def partner_close_bit(closew, partner):
    """``close[i, partner[i]]`` read from the packed contact matrix.

    Bitwise the row bit of ``closew`` (which :func:`pairwise_close` built
    with the same subtraction order as :func:`pair_still_close`), via one
    word gather instead of re-deriving pair distances; only meaningful
    where ``partner >= 0``."""
    n = closew.shape[0]
    pidx = jnp.clip(partner, 0, n - 1)
    word = jnp.take_along_axis(
        closew, (pidx // 32)[:, None].astype(jnp.int32), axis=1
    )[:, 0]
    return ((word >> (pidx.astype(jnp.uint32) % 32)) & 1) != 0


def advance_exchanges(
    *, partner, exch_elapsed, exch_total, still_close, dt
):
    """Tick ongoing exchanges; classify completion vs contact break.

    ``still_close`` is the per-node proximity bit at ``(i, partner[i])``
    (:func:`pair_still_close`). Returns (elapsed, done, broke, ending,
    eff_time, pidx): ``eff_time`` is the portion of the exchange usable
    for transfers — the full planned duration on completion, the elapsed
    time minus the broken slot on a break (the broken slot did not
    finish).
    """
    n = partner.shape[0]
    busy = partner >= 0
    pidx = jnp.clip(partner, 0, n - 1)
    still = still_close & busy
    elapsed = jnp.where(busy, exch_elapsed + dt, 0.0)
    done = busy & (elapsed >= exch_total)
    broke = busy & ~still & ~done
    ending = done | broke
    eff_time = jnp.where(done, exch_total, jnp.maximum(elapsed - dt, 0.0))
    return elapsed, done, broke, ending, eff_time, pidx


def _deliveries_general(
    *, order_seed, snap_has, snap, pidx, eff_time, ending, t0, T_L
):
    """The any-M delivery path: per-connection random send order (one
    threefry hash per node per slot), rank via double argsort, both under
    the scope ``fg.deliveries.order``."""
    m_count = snap_has.shape[1]

    def send_rank(order_seed_i, sender_has):
        rnd = jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(0), order_seed_i), (m_count,)
        )
        rnd = jnp.where(sender_has, rnd, jnp.inf)
        return jnp.argsort(jnp.argsort(rnd))  # 0-based among all models

    seed, has, words = take_nodes((order_seed, snap_has, snap), pidx)
    with jax.named_scope("fg.deliveries.order"):
        rank = jax.vmap(send_rank)(seed, has)
    fin = t0 + (rank + 1).astype(jnp.float32) * T_L
    delivered = has & (fin <= eff_time[:, None])
    return delivered & ending[:, None], words


def compute_deliveries(
    *, order_seed, snap_has, snap, pidx, eff_time, ending, t0, T_L
):
    """Per (receiver, model) delivery flags for exchanges ending this slot.

    The sender transmits its snapshotted instances in a random order seeded
    per connection; an instance is delivered iff its completion offset
    ``t0 + (rank + 1) T_L`` fits within the effective contact time.
    Returns (delivered (N, M) bool, sender_mask (N, M, ceil(K/32)) packed
    words — ``snap`` is carried bit-packed)."""
    m_count = snap_has.shape[1]

    if m_count == 1:
        # Single-model fast path (the paper's default M=1 sweeps): a lone
        # instance always has send rank 0, so the per-connection order PRNG
        # and the double argsort of :func:`_deliveries_general` drop out.
        # Bit-identical to the general path — pinned against it in
        # ``tests/test_sim_contacts.py``.
        fin = t0 + jnp.float32(1.0) * T_L
        has, words = take_nodes((snap_has, snap), pidx)
        delivered = has & (fin <= eff_time)[:, None]
        return delivered & ending[:, None], words

    return _deliveries_general(
        order_seed=order_seed, snap_has=snap_has, snap=snap, pidx=pidx,
        eff_time=eff_time, ending=ending, t0=t0, T_L=T_L,
    )


def form_connections(
    *,
    partner, match,
    has_model, inc, snap, snap_has,
    exch_elapsed, exch_total, order_seed,
    slot_idx, t0, T_L,
):
    """Start the exchanges of this slot's mutually-matched pairs.

    ``partner`` must already have ending pairs released (set to -1) and
    ``match`` is the :func:`match_candidates` mutual-best result. The
    planned exchange covers every non-default instance both sides hold
    (the w = 1 case; the subscription cap W is handled by the caller
    restricting M), so the planned busy time is ``t0 + (n_i + n_j) T_L``.
    ``inc``/``snap`` are packed word arrays — the snapshot is a plain
    word copy.
    """
    n = partner.shape[0]
    newly = match >= 0
    midx = jnp.clip(match, 0, n - 1)

    n_own = jnp.sum(has_model, axis=-1)
    n_exch = n_own + take_nodes(n_own, midx)
    total = t0 + n_exch.astype(jnp.float32) * T_L
    partner = jnp.where(newly, match, partner)
    exch_elapsed = jnp.where(newly, 0.0, exch_elapsed)
    exch_total = jnp.where(newly, total, exch_total)
    snap = jnp.where(newly[:, None, None], inc, snap)
    snap_has = jnp.where(newly[:, None], has_model, snap_has)
    order_seed = jnp.where(
        newly,
        (slot_idx.astype(jnp.uint32) * jnp.uint32(2654435761)
         + jnp.arange(n, dtype=jnp.uint32)),
        order_seed,
    )
    return dict(
        partner=partner, exch_elapsed=exch_elapsed, exch_total=exch_total,
        snap=snap, snap_has=snap_has, order_seed=order_seed,
    )
