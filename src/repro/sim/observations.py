"""Observation bookkeeping: ring generation, observers, job completions,
and per-slot trace outputs.

Observations are tracked explicitly: each model has a ring of ``K`` recent
observations with birth times; each node keeps an incorporation mask per
(model, obs slot), stored **bit-packed** as ``ceil(K/32)`` uint32 words
(the ``repro.sim.compute.pack_mask`` layout). Merging ORs word rows
(training-set union); training ORs a packed one-hot; ring recycling ANDs
out one; stored-information counts are popcounts. Per output slot this
yields model availability, busy fraction, per-node stored information
(ages <= tau_l), and per-observation holder counts from which o(tau) is
estimated post-hoc.

Unlike the legacy simulator, the number of simultaneous observers ``Λ`` is
a *traced* quantity here (top-Λ selection is expressed as a rank
threshold), so scenario batches can sweep it without recompilation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.sim.compute import (packed_onehot, packed_popcount, pack_mask,
                               shared_barrier, take_nodes, unpack_mask)

__all__ = ["generate_observations", "apply_completions", "slot_outputs",
           "estimate_o_of_tau"]

#: Observer-rank implementation switch: at or below this node count the
#: O(N²) compare-reduce wins on CPU (it vectorizes where XLA's CPU sort
#: runs a scalar comparator loop); above it the O(N log N) double stable
#: argsort keeps the whole step sub-quadratic (the cells contact backend's
#: regime). Both compute the identical rank — the node's position in the
#: row sorted by (score, node id) — so the selected observer set is the
#: same at any N.
RANK_DENSE_MAX_N = 512


def _observer_ranks(who_scores: jnp.ndarray) -> jnp.ndarray:
    """(M, N) position of each node in its row sorted by (score, node id):
    the number of scores below its own plus the number of equal scores of
    lower node ids. A tie of f32 scores goes to the lower id, as in a
    stable sort, so ``rank < Λ`` admits exactly Λ nodes."""
    n = who_scores.shape[1]
    if n <= RANK_DENSE_MAX_N:
        own = who_scores[:, :, None]
        other = who_scores[:, None, :]
        lower_id = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]
        return jnp.sum(jnp.where(lower_id, other <= own, other < own),
                       axis=-1)
    order = jnp.argsort(who_scores, axis=-1, stable=True)
    return jnp.argsort(order, axis=-1, stable=True)


def generate_observations(
    *, k_obs, k_who, obs_birth, obs_head, inc, in_rz, lam, Lam, dt, t_now
):
    """Draw per-model observation arrivals and pick their Λ observers.

    Returns (obs_birth, obs_head, inc, want_train (N, M), slot_payload
    (N, M)) where ``want_train`` flags nodes that recorded the new
    observation (to be enqueued for training on ring slot
    ``slot_payload``)."""
    m_count, k_count = obs_birth.shape
    n = in_rz.shape[0]

    new_obs = jax.random.uniform(k_obs, (m_count,)) < lam * dt
    slot_of = obs_head
    obs_birth = jnp.where(
        new_obs[:, None] & (jnp.arange(k_count)[None, :] == slot_of[:, None]),
        t_now, obs_birth,
    )
    obs_head = jnp.where(new_obs, (obs_head + 1) % k_count, obs_head)
    # clear incorporation bits of the recycled slot (packed word and-not)
    recycled = jnp.where(
        new_obs[:, None], packed_onehot(slot_of, k_count), jnp.uint32(0)
    )
    inc = inc & ~recycled[None]

    # Λ random in-RZ nodes record each new observation. Score nodes i.i.d.
    # (out-of-RZ nodes pushed to the back) and take the Λ smallest scores,
    # f32 ties to the lower node id — the first Λ of a stable argsort, but
    # Λ stays dynamic (a traced threshold, not a static slice), so scenario
    # batches can sweep it. Selection is expressed through each node's
    # *rank* (its position in the (score, id) order) rather than a sort,
    # while the O(N²) compare-reduce vectorizes where XLA's CPU sort
    # lowers to a scalar comparator loop. Like the scores
    # themselves, the rank matrix depends only on the per-seed key chain,
    # so sweep batches compute it once per seed, not once per scenario.
    who_scores = jax.random.uniform(k_who, (m_count, n)) + (~in_rz)[None, :] * 1e3
    rank = shared_barrier(_observer_ranks(who_scores))
    lam_n = jnp.clip(jnp.round(Lam).astype(jnp.int32), 1, n)
    is_obs = (rank < lam_n) & in_rz[None, :] & new_obs[:, None]
    want_train = is_obs.T                                          # (N, M)
    slot_payload = jnp.broadcast_to(slot_of[None, :], (n, m_count))
    return obs_birth, obs_head, inc, want_train, slot_payload


def apply_completions(
    *, fin_merge, fin_train, serv_model, serv_mask, serv_slot,
    inc, has_model, obs_birth,
):
    """Apply finished merge/train jobs to the incorporation state.

    Merge completion ORs the job's (packed) snapshot words into the node's
    own words for the served model (training-set union) and grants the
    model; train completion ORs the packed one-hot of the (model, slot)
    bit — only if the observation slot was not recycled since the job was
    enqueued."""
    m_count, k_count = obs_birth.shape

    onehot_m = jax.nn.one_hot(serv_model, m_count, dtype=bool)      # (N, M)
    inc = inc | jnp.where(
        (fin_merge[:, None] & onehot_m)[:, :, None],
        serv_mask[:, None, :], jnp.uint32(0),
    )
    has_model = has_model | (fin_merge[:, None] & onehot_m)

    # fresh[n, m] = obs_birth[m, serv_slot[n]] > -inf (no (N, M, K) copy)
    fresh = take_nodes(obs_birth.T, serv_slot) > -jnp.inf
    onehot_kw = packed_onehot(serv_slot, k_count)                   # (N, KW)
    inc = inc | jnp.where(
        (fin_train[:, None] & onehot_m & fresh)[:, :, None],
        onehot_kw[:, None, :], jnp.uint32(0),
    )
    has_model = has_model | (fin_train[:, None] & onehot_m & fresh)
    return inc, has_model


def slot_outputs(*, inc, has_model, obs_birth, in_rz, partner, t_now, tau_l,
                 member=None, with_obs_trace: bool = True):
    """Per-slot observables (the quantities Figs. 1-4 are built from).

    ``inc`` arrives bit-packed; stored-information is a popcount and the
    per-observation holder counts unpack once per *sample* (not per slot),
    so the packed format never costs the inner loop anything.

    ``in_rz`` is the *union* zone membership (the legacy single-RZ
    semantics — every union-level trace is unchanged). ``member`` — the
    ``(N, K_zones)`` per-zone membership matrix — additionally emits the
    per-zone traces ``availability_z`` (M, K), ``stored_z`` (K,) and
    ``n_in_rz_z`` (K,), each with a *trailing* zone axis; for a single
    zone these are the union traces with a length-1 zone axis appended.

    ``with_obs_trace=False`` drops the per-observation quantities
    (``obs_birth`` ring snapshot and the holder-count GEMV, which needs the
    only full unpack of ``inc`` in the engine) — the light mode used by
    reduced-output sweeps (``repro.sim.sweep``), where only the scalar
    observables feed the on-device reduction and the o(τ) estimator is not
    run."""
    k_count = obs_birth.shape[1]
    age = t_now - obs_birth  # (M, K)
    live = (obs_birth > -jnp.inf) & (age <= tau_l)
    livew = pack_mask(live)                                   # (M, KW)
    stored = jnp.sum(packed_popcount(inc & livew[None]), axis=1)  # per node
    n_rz = jnp.maximum(jnp.sum(in_rz), 1)
    out = dict(
        availability=jnp.sum(has_model & in_rz[:, None], axis=0) / n_rz,
        busy_frac=jnp.sum((partner >= 0) & in_rz) / n_rz,
        stored=jnp.sum(jnp.where(in_rz, stored, 0)) / n_rz,
        model_holders=jnp.sum(has_model & in_rz[:, None], axis=0),
        n_in_rz=jnp.sum(in_rz),
    )
    if member is not None:
        n_z = jnp.sum(member, axis=0)                         # (K,)
        denom = jnp.maximum(n_z, 1)
        out["n_in_rz_z"] = n_z
        out["availability_z"] = jnp.sum(
            has_model[:, :, None] & member[:, None, :], axis=0
        ) / denom[None, :]                                    # (M, K)
        out["stored_z"] = jnp.sum(
            jnp.where(member, stored[:, None], 0), axis=0
        ) / denom                                             # (K,)
    if with_obs_trace:
        inc_bits = unpack_mask(inc, k_count)                  # (N, M, K)
        # holder counts as a GEMV over the node axis — counts <= N are
        # exact in f32, so this is bitwise the boolean-sum result at
        # matmul speed
        out["obs_birth"] = obs_birth
        out["obs_holders"] = jnp.einsum(
            "n,nmk->mk", in_rz.astype(jnp.float32),
            inc_bits.astype(jnp.float32),
        ).astype(jnp.int32)
    return out


def o_tau_histograms(*, t, obs_birth, obs_holders, model_holders,
                     n_tau: int, dtau):
    """Device-side o(τ) accumulation: ``(num, den)`` age histograms.

    The observation-age histogram underlying the o(τ) estimator, as one
    vectorized reduction over the (sample, model, ring-slot) axes:
    every live observation (finite age ≥ 0) of a model with at least one
    holder contributes its holder *fraction* to ``num`` and 1 to ``den``
    at age bin ``floor(age / dtau)``; o(τ) is ``num / den``. Inputs may
    carry arbitrary leading batch axes (the sweep runner passes
    ``(scenario, seed)``); the histograms are accumulated per run.

    Shapes: ``t (S,)``, ``obs_birth``/``obs_holders`` ``(..., S, M, K)``,
    ``model_holders`` ``(..., S, M)`` → ``(..., n_tau)`` each.

    The binning is expressed as a one-hot contraction (no scatter — XLA
    lowers batched scatters to scalar loops on CPU); memory is
    ``trace_size × n_tau`` booleans inside the fused reduce, so keep
    ``n_tau`` modest for big sweeps.
    """
    age = t[:, None, None] - obs_birth                     # (..., S, M, K)
    holders = jnp.maximum(model_holders, 1)[..., None]
    frac = obs_holders / holders
    bins = jnp.floor(age / dtau).astype(jnp.int32)
    ok = (
        jnp.isfinite(age) & (age >= 0)
        & (model_holders > 0)[..., None]
        & (bins < n_tau) & (bins >= 0)
    )
    onehot = bins[..., None] == jnp.arange(n_tau, dtype=jnp.int32)
    sel = ok[..., None] & onehot                           # (..., S, M, K, T)
    axes = tuple(range(sel.ndim - 4, sel.ndim - 1))        # S, M, K
    num = jnp.sum(jnp.where(sel, frac[..., None], 0.0), axis=axes)
    den = jnp.sum(sel, axis=axes).astype(jnp.float32)
    return num, den


def estimate_o_of_tau(out, tau_grid: np.ndarray, warmup_frac: float = 0.3):
    """Empirical o(τ): holders-of-observation / holders-of-model at age τ.

    ``out`` is a ``SimOutputs`` (or any object with ``t``, ``obs_birth``,
    ``obs_holders``, ``model_holders`` sample traces). One vectorized
    histogram pass (:func:`o_tau_histograms`) over the post-warmup
    samples — the historical per-(sample, model) Python loop at trace
    scale cost seconds per run and kept the o(τ) estimator host-bound;
    the sweep runner exposes the same reduction on device as
    ``reduce="o_tau"``.
    """
    s0 = int(len(out.t) * warmup_frac)
    dtau = float(tau_grid[1] - tau_grid[0])
    num, den = o_tau_histograms(
        t=jnp.asarray(out.t[s0:], jnp.float32),
        obs_birth=jnp.asarray(out.obs_birth[s0:]),
        obs_holders=jnp.asarray(out.obs_holders[s0:], jnp.float32),
        model_holders=jnp.asarray(out.model_holders[s0:], jnp.float32),
        n_tau=len(tau_grid), dtau=dtau,
    )
    num, den = np.asarray(num), np.asarray(den)
    return np.where(den > 0, num / np.maximum(den, 1), np.nan)
