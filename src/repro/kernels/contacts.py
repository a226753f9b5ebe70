"""Tiled Pallas pairwise-contact kernel (plus its ``jnp`` oracle).

The per-slot hot path of the simulator is the O(N²) pairwise sweep:
squared distances, the transmission-radius threshold, the zone-membership
gate (a pair is admissible iff the two nodes share at least one
Replication Zone — per-node uint32 zone *words*, whose intersection test
is bitwise the historical ``in_rz_i & in_rz_j`` at a single zone), and
the mutual-best candidate reduction used for pair matching.
The kernel fuses all four so that neither the (N, N) float32 distance
matrix nor the (N, N) boolean contact matrix ever materializes in HBM —
per i-row tile it emits

* ``closew``  — the contact matrix row, **bit-packed** to ``ceil(N/32)``
  ``uint32`` words (the ``repro.sim.compute.pack_mask`` LSB-first layout,
  directly usable as the scan-carry ``prev_close``), and
* ``best_j`` / ``has`` — the row argmin of d² over *candidate* pairs
  (close ∧ not-previously-close ∧ both-eligible) and whether any
  candidate exists, from which the caller finishes mutual-best matching
  in O(N).

All three outputs are discrete (packed bits / index / flag) on purpose:
XLA contracts ``dx*dx + dy*dy`` into an FMA or not depending on the
surrounding codegen (tile shape, fusion context), so a raw float d²
output could differ between lowerings in the last ulp. The *ordering*
each path derives from its own d² is self-consistent, and the discrete
outputs are bitwise stable (a flip would need two candidate distances
within one ulp of each other).

Grid: (n_i,) over row tiles. Each step reads the whole column set in
*bit-plane* order — a ``(32, N/32)`` array per quantity whose row ``b``
holds columns ``b, 32 + b, 64 + b, ...`` — so the close bits of plane
``b`` OR straight into bit ``b`` of every output word: no unsigned
reduction and no reshape of the lane axis, neither of which the TPU
lowering accepts. N ≤ a few thousand keeps a (blk_i, N/32) word tile and
its 32 planes small in VMEM.

Dispatch rule (``repro.sim.contacts.pairwise_close`` /
``match_candidates``): the compiled kernel runs only on TPU backends;
everywhere else the bit-identical ``jnp`` reference runs as two stages —
``pairwise_close_ref`` (shared per seed in sweep batches) and
``candidate_best_ref`` (per run). Interpret mode is reserved for tests,
which pin the kernel to the combined reference
(``pairwise_contacts_ref``) bit for bit (``tests/test_kernels.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "pairwise_contacts",
    "pairwise_contacts_ref",
    "pairwise_close_ref",
    "candidate_best_ref",
    "apply_access",
    "zone_words",
    "cell_close_words",
    "cell_close_words_ref",
    "padded_cell_id",
    "cell_neighborhood_offsets",
    "interior_cell_ids",
]

_FAR = 1e9  # padding coordinate: d2 = O(1e18) is finite and > any r_tx²




def _as_member(in_rz: jnp.ndarray) -> jnp.ndarray:
    """Normalize RZ membership to the multi-zone ``(N, K)`` bool form.

    Every contact entry point accepts either the legacy single-zone
    ``(N,)`` bool vector (treated as one zone) or a ``(N, K)`` per-zone
    membership matrix (K <= 32 discs of a ``ZoneSet``)."""
    return in_rz[:, None] if in_rz.ndim == 1 else in_rz


def zone_words(in_rz: jnp.ndarray) -> jnp.ndarray:
    """(N,) uint32 zone-membership words (bit ``z`` = member of zone z).

    Accepts ``(N,)`` bool (legacy single zone → bit 0) or ``(N, K)``
    bool. Two nodes may exchange iff their words intersect — for a
    single zone that is bitwise the historical ``in_rz_i & in_rz_j``
    gate."""
    from repro.sim.compute import pack_mask

    member = _as_member(in_rz)
    if member.shape[1] > 32:
        raise ValueError("zone membership words support at most 32 zones")
    return pack_mask(member)[..., 0]


def apply_access(in_rz, access):
    """Fold a per-node accessibility mask into the zone membership.

    ``access`` (an ``(N,)`` bool, or ``None`` for the always-on program)
    rides *alongside* the zone-word mask on every contact path: an
    inaccessible node is stripped of its zone membership **for contact
    purposes only** — it passes no zone-sharing gate on the dense ref, the
    fused Pallas kernel, or either cell-list path, so the four backends
    stay consistent by construction (pinned in ``tests/test_sim_faults``).
    Accepts all three membership encodings (``(N,)`` bool, ``(N, K)``
    bool, ``(N,)`` uint32 zone word); ``access=None`` returns the input
    unchanged (the fault-free program is untouched)."""
    if access is None:
        return in_rz
    if in_rz.dtype == jnp.uint32:
        return jnp.where(access, in_rz, jnp.uint32(0))
    if in_rz.ndim == 1:
        return in_rz & access
    return in_rz & access[:, None]


def pairwise_close_ref(pos, in_rz, r_tx2, access=None):
    """Shared stage of the pairwise sweep: packed contact matrix + d².

    Everything here depends only on positions and zone membership — in a
    (scenario x seed) sweep batch these are functions of the per-seed
    PRNG chain alone, so ``vmap`` computes this stage once per seed and
    broadcasts it across the scenario axis. Returns ``(closew, d2b3)``:
    the bit-packed contact matrix and the padded bitcast-d² context
    ``(N, ceil(N/32), 32)`` consumed by :func:`candidate_best_ref`.

    ``in_rz`` may be the legacy ``(N,)`` bool vector or a ``(N, K)``
    multi-zone membership matrix (see :func:`_as_member`); the contact
    gate is *zone-sharing* — ``close[i, j]`` requires i and j to be
    members of at least one common zone. In the packed word domain that
    is a per-row OR of the per-zone column masks: row i's admissible
    columns are ``OR_z (member[i, z] ? colw[z] : 0)`` with ``colw[z]``
    the packed member set of zone z — for K = 1 bitwise the historical
    ``where(in_rz_i, inside & rzw, 0)`` single-RZ gating.

    ``closew[i] >> j & 1`` is bitwise ``close[i, j]`` of the dense matrix
    (same subtraction order), so the engine extracts partner-proximity
    bits from it instead of recomputing pair distances.
    """
    from repro.sim.compute import pack_mask, packed_onehot, shared_barrier

    member = _as_member(apply_access(in_rz, access))
    n = pos.shape[0]
    nw = (n + 31) // 32
    dx = pos[:, None, 0] - pos[None, :, 0]
    dy = pos[:, None, 1] - pos[None, :, 1]
    d2 = shared_barrier(dx * dx + dy * dy)
    inside = pack_mask(d2 <= r_tx2)                      # (N, NW)
    colw = pack_mask(member.T)                           # (K, NW)
    diagw = packed_onehot(jnp.arange(n), n)              # constant-folded
    rowmask = jnp.zeros((n, nw), jnp.uint32)
    for z in range(member.shape[1]):                     # K is static, small
        rowmask = rowmask | jnp.where(
            member[:, z, None], colw[z][None, :], jnp.uint32(0)
        )
    closew = inside & rowmask & ~diagw
    d2b = jax.lax.bitcast_convert_type(d2, jnp.uint32)
    d2b3 = shared_barrier(jnp.pad(
        d2b, ((0, 0), (0, nw * 32 - n)),
        constant_values=np.uint32(0xFFFFFFFF),
    ).reshape(n, nw, 32))
    return closew, d2b3


def candidate_best_ref(d2b3, closew, prevw, elig):
    """Per-run stage: best new-contact candidate per row.

    ``candw = closew & ~prevw & elig_i & elig_j`` in the packed word
    domain, then a hierarchical masked argmin over the d² context (see
    :func:`pairwise_contacts_ref`). Only this stage depends on protocol
    state, so in sweep batches it is the only part paid per (scenario,
    seed) work item.
    """
    from repro.sim.compute import pack_mask

    eligw = pack_mask(elig)
    candw = jnp.where(
        elig[:, None], closew & ~prevw & eligw[None, :], jnp.uint32(0)
    )
    # Candidate scores as *bitcast* uint32: for non-negative floats the
    # integer order equals the float order, d² is a sum of squares (never
    # negative, never NaN), and the all-ones sentinel plays the role of
    # +inf — so integer min reduces are bitwise the float argmin while
    # vectorizing measurably better on CPU.
    #
    # The argmin is *hierarchical* to make the batched sweep cheap: one
    # full-width pass reduces each 32-column word block to its masked
    # minimum (candidate bits expand arithmetically: ``bit - 1`` is 0x0 for
    # a set bit and 0xFFFFFFFF for a clear one, OR-ing the sentinel in),
    # and the winning index is then recovered from the single winning word
    # — first word whose min attains the row min, first lane in that word
    # attaining it — via an O(N·32) block gather. That visits the (N, N)
    # domain ONCE instead of twice (min + masked index-min), which is the
    # difference that matters when a sweep batches this per run while d²
    # stays shared across the scenario axis. First-minimum tie-breaking is
    # identical: the first j attaining the global min lives in the first
    # word whose masked min equals it.
    ff = jnp.uint32(0xFFFFFFFF)
    nw = closew.shape[1]
    lanes = jnp.arange(32, dtype=jnp.uint32)
    masked = d2b3 | (((candw[:, :, None] >> lanes) & jnp.uint32(1))
                     - jnp.uint32(1))
    wmin = jnp.min(masked, axis=-1)                      # (N, NW)
    bmin = jnp.min(wmin, axis=-1)                        # (N,)
    has = bmin != ff
    wstar = jnp.clip(
        jnp.min(
            jnp.where(wmin == bmin[:, None],
                      jnp.arange(nw, dtype=jnp.int32), nw),
            axis=-1,
        ),
        0, nw - 1,
    )
    # rebuild the winning 32-lane block from its small pieces (gathering
    # ``masked`` itself would force materializing the full (N, N) buffer)
    d2_blk = jnp.take_along_axis(d2b3, wstar[:, None, None], axis=1)[:, 0]
    cw_blk = jnp.take_along_axis(candw, wstar[:, None], axis=1)
    blk = d2_blk | (((cw_blk >> lanes) & jnp.uint32(1)) - jnp.uint32(1))
    lane = jnp.min(
        jnp.where(blk == bmin[:, None], jnp.arange(32, dtype=jnp.int32), 32),
        axis=-1,
    )
    # no-candidate rows report the -1 sentinel (historically they leaked
    # the all-sentinel argmin's index 0, which callers had to remember to
    # gate on ``has``); the Pallas kernel applies the same where, so the
    # two stay bitwise equal on every output
    return jnp.where(has, wstar * 32 + lane, -1), has


def pairwise_contacts_ref(pos, in_rz, elig, prevw, r_tx2, access=None):
    """Pure-``jnp`` oracle (and the CPU/GPU execution path).

    Composition of the two stages: the shared pairwise sweep
    (:func:`pairwise_close_ref` — d², radius compare, packed contact
    matrix; every mask combination happens in the 32x-smaller packed word
    domain) and the per-run candidate argmin
    (:func:`candidate_best_ref`). The engine calls the stages separately
    so sweep batches pay the first one once per seed; this combined form
    is the interface the Pallas kernel is pinned against bit for bit.

    Args:
      pos:    (N, 2) float32 positions.
      in_rz:  (N,) bool RZ membership, or (N, K) bool per-zone
              membership (the contact gate is then zone-*sharing*).
      elig:   (N,) bool pairing eligibility (idle, in RZ).
      prevw:  (N, ceil(N/32)) packed previous-slot contact matrix.
      r_tx2:  squared transmission radius.
      access: optional (N,) bool accessibility mask alongside the zone
              mask (:func:`apply_access`); ``None`` = every node on.

    Returns ``(closew, best_j, has)`` as described in the module
    docstring.
    """
    closew, d2b3 = pairwise_close_ref(pos, in_rz, r_tx2, access=access)
    best_j, has = candidate_best_ref(d2b3, closew, prevw, elig)
    return closew, best_j, has


def _or_bit(words, bit, b: int):
    """``words | (bit << b)`` in int32 — the in-kernel half of the
    :func:`repro.sim.compute.pack_mask` layout. Mosaic has no unsigned
    reductions and cannot split the lane axis into (word, bit), so the
    kernels build each word from 32 bit planes with shifts and ORs (bit 31
    lands in the sign bit) and the wrapper bitcasts the result to uint32."""
    return words | (bit.astype(jnp.int32) << b)


def _kernel(xi_ref, yi_ref, zwi_ref, eligi_ref, xb_ref, yb_ref, zwb_ref,
            eligb_ref, prevw_ref, closew_ref, bestj_ref, has_ref, *,
            r_tx2, blk_i):
    # Row inputs are (blk_i, 1) columns. Column inputs are bit-plane major,
    # (32, nw): entry [b, w] is node 32 * w + b, so plane b is one sublane
    # row and its close bits OR straight into bit b of every word.
    ti = pl.program_id(0)
    xi, yi = xi_ref[...], yi_ref[...]
    zwi = zwi_ref[...]
    eligi = eligi_ref[...] != 0
    prev = prevw_ref[...]                             # (blk_i, nw) int32
    shape = prev.shape
    row = ti * blk_i + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col0 = 32 * jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    closew = jnp.zeros(shape, jnp.int32)
    best = jnp.full(shape, jnp.inf, jnp.float32)      # per-word running min
    best_b = jnp.zeros(shape, jnp.int32)              # ... and its first bit
    for b in range(32):
        dx = xi - xb_ref[b:b + 1, :]                  # (blk_i, nw)
        dy = yi - yb_ref[b:b + 1, :]
        d2 = dx * dx + dy * dy
        # zone-sharing gate on the membership words — for a single zone the
        # words are 0/1 and this is bitwise the old in_rz_i & in_rz_j
        close = (
            (d2 <= r_tx2)
            & ((zwi & zwb_ref[b:b + 1, :]) != 0)
            & (row != col0 + b)
        )
        closew = _or_bit(closew, close, b)
        was = (jax.lax.shift_right_logical(prev, b) & 1) != 0
        cand = close & ~was & eligi & (eligb_ref[b:b + 1, :] != 0)
        score = jnp.where(cand, d2, jnp.inf)
        better = score < best                         # strict: first bit wins
        best = jnp.where(better, score, best)
        best_b = jnp.where(better, b, best_b)
    closew_ref[...] = closew

    # row argmin with the oracle's first-minimum tie-break: the lowest
    # column attaining the row min lives in the lowest word attaining it,
    # at that word's first attaining bit
    bmin = jnp.min(best, axis=1, keepdims=True)       # (blk_i, 1)
    has = bmin < jnp.inf
    j = jnp.min(
        jnp.where(best == bmin, col0 + best_b, jnp.iinfo(jnp.int32).max),
        axis=1, keepdims=True,
    )
    bestj_ref[...] = jnp.where(has, j, -1)
    has_ref[...] = has.astype(jnp.int32)


def _bit_planes(v: jnp.ndarray) -> jnp.ndarray:
    """(n_pad,) -> (32, n_pad // 32) with [b, w] = v[32 * w + b]."""
    return v.reshape(-1, 32).T


def _as_i32(words: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(words, jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("r_tx2", "blk_i", "interpret")
)
def pairwise_contacts(pos, in_rz, elig, prevw, r_tx2, access=None, *,
                      blk_i: int = 128, interpret: bool = False):
    """Fused Pallas pairwise-contact pass (see module docstring).

    ``in_rz`` is either the legacy ``(N,)`` bool membership, a ``(N, K)``
    multi-zone membership matrix, or a precomputed ``(N,)`` uint32 zone
    word (:func:`zone_words`); the in-kernel contact gate is the
    zone-word intersection, bitwise the historical RZ gate at K = 1.
    ``N`` is padded to a multiple of ``max(blk_i, 32)`` with far-away
    coordinates (masked out of every output); ``closew`` pad bits are zero
    by construction, matching ``pack_mask``, and pad zone words are zero
    (pad rows never pass the gate).
    """
    n = pos.shape[0]
    blk_i = min(blk_i, -(-n // 32) * 32)
    blk_i = max(32, (blk_i // 32) * 32)   # keep tiles 32-aligned for packing
    n_pad = -(-n // blk_i) * blk_i
    pad = n_pad - n

    zw = in_rz if in_rz.dtype == jnp.uint32 else zone_words(in_rz)
    # the accessibility mask rides alongside the zone words: an off node's
    # word is zeroed before the kernel, so the in-kernel intersection gate
    # needs no change and kernel/oracle stay bitwise comparable
    zw = apply_access(zw, access)
    x = jnp.pad(pos[:, 0], (0, pad), constant_values=_FAR)
    y = jnp.pad(pos[:, 1], (0, pad), constant_values=_FAR)
    rz = _as_i32(jnp.pad(zw, (0, pad)))
    el = jnp.pad(elig.astype(jnp.int32), (0, pad))
    nw, nw_pad = prevw.shape[1], n_pad // 32
    prevw = _as_i32(jnp.pad(prevw, ((0, pad), (0, nw_pad - nw))))

    kernel = functools.partial(_kernel, r_tx2=r_tx2, blk_i=blk_i)
    rows = pl.BlockSpec((blk_i, 1), lambda i: (i, 0))
    planes = pl.BlockSpec((32, nw_pad), lambda i: (0, 0))
    words = pl.BlockSpec((blk_i, nw_pad), lambda i: (i, 0))
    closew, best_j, has = pl.pallas_call(
        kernel,
        grid=(n_pad // blk_i,),
        in_specs=[rows] * 4 + [planes] * 4 + [words],
        out_specs=[words, rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, nw_pad), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        ],
        interpret=interpret,
        name="pairwise_contacts",
    )(x[:, None], y[:, None], rz[:, None], el[:, None],
      _bit_planes(x), _bit_planes(y), _bit_planes(rz), _bit_planes(el),
      prevw)
    closew = jax.lax.bitcast_convert_type(closew, jnp.uint32)
    return closew[:n, :nw], best_j[:n, 0], has[:n, 0] != 0


# --------------------------------------------------------------------------
# Cell-list (3×3 neighborhood) close-word kernel — the large-N contact path
# --------------------------------------------------------------------------
#
# Inputs are *cell-major* planes built by ``repro.sim.cells``: for a
# padded grid of ``(ncx + 2) * (ncy + 2)`` cells (one-cell empty border
# ring) and per-cell capacity ``cap``, each plane is ``(n_pad_cells,
# cap)`` — x, y (far-filled for empty slots), the uint32 zone word (0 for
# empty slots) and the node id (-1 for empty slots). For every *interior*
# cell the pass compares its ≤ cap nodes against the ≤ 9·cap nodes of the
# 3×3 neighborhood and emits the close decision **bit-packed over the
# candidate axis**: ``(ncx * ncy, cap, ceil(9 cap / 32))`` uint32 words.
# Neither an (N, N) object nor even an (N, 9 cap) boolean ever reaches
# HBM — the word output is 32x smaller, and the caller
# (``repro.sim.cells.neighbor_lists``) turns it into bounded per-node
# neighbor lists.
#
# The Pallas grid runs one step per interior grid row. The kernel sees
# each plane as ``(ncx + 2, cap, NYp)`` — slots on sublanes, the cells of
# a grid row on lanes — and takes rows cx - 1, cx, cx + 1 as three
# blocks; the dy = ±1 neighbors are lane rolls of those rows, and the
# border ring makes every neighbor of an interior cell a real (empty)
# cell. Like the pairwise kernel, outputs are discrete (packed bits, built
# bit plane by bit plane) so kernel and oracle are bitwise comparable.


_CELL_PLANES = 4            # x, y, zone word, node id
_NEIGHBORHOOD = 9


# The padded-grid layout — border ring of width 1, row-major interior,
# stride ncy + 2 — is defined ONCE here; ``repro.sim.cells`` (binning,
# node-centric gathers) and the kernel/oracle below all derive their
# indexing from these two helpers.


def padded_cell_id(cx, cy, ncy: int):
    """Flattened padded-grid id of interior cell ``(cx, cy)``."""
    return (cx + 1) * (ncy + 2) + (cy + 1)


def cell_neighborhood_offsets(ncy: int) -> tuple[int, ...]:
    """The 3×3 neighborhood as flattened padded-grid offsets."""
    s = ncy + 2
    return tuple(dx * s + dy for dx in (-1, 0, 1) for dy in (-1, 0, 1))


def interior_cell_ids(ncx: int, ncy: int) -> jnp.ndarray:
    """(ncx * ncy,) padded-grid ids of the interior cells, row-major."""
    cxy = jnp.arange(ncx * ncy, dtype=jnp.int32)
    return padded_cell_id(cxy // ncy, cxy % ncy, ncy)


def _cell_close(xi, yi, zi, ii, xj, yj, zj, ij, r_tx2):
    """The shared close decision of kernel and oracle: (rows, cands) ->
    packed close words. ``i`` axes are the center cell's slots, ``j``
    axes the concatenated 3×3 candidate slots."""
    from repro.sim.compute import pack_mask

    dx = xi[:, None] - xj[None, :]
    dy = yi[:, None] - yj[None, :]
    d2 = dx * dx + dy * dy
    close = (
        (d2 <= r_tx2)
        & ((zi[:, None] & zj[None, :]) != 0)
        & (ii[:, None] != ij[None, :])           # same id = same node (or
        & (ij[None, :] >= 0)                     # both empty, id -1)
    )
    return pack_mask(close)


def cell_close_words_ref(xc, yc, zc, idc, ncx: int, ncy: int, r_tx2):
    """Pure-``jnp`` oracle of the cell kernel (word domain, bit-identical).

    Args are the cell-major planes described above (``(n_pad_cells,
    cap)`` each); returns ``(ncx * ncy, cap, ceil(9 cap / 32))`` packed
    close words for the interior cells in row-major (cx, cy) order.
    """
    cap = xc.shape[1]
    pids = interior_cell_ids(ncx, ncy)                       # (C,)
    nbrp = pids[:, None] + jnp.asarray(
        cell_neighborhood_offsets(ncy), jnp.int32
    )

    def gather9(plane):
        return plane[nbrp].reshape(ncx * ncy, _NEIGHBORHOOD * cap)

    return jax.vmap(_cell_close, in_axes=(0,) * 8 + (None,))(
        xc[pids], yc[pids], zc[pids], idc[pids],
        gather9(xc), gather9(yc), gather9(zc), gather9(idc), r_tx2,
    )


def _cell_kernel(*refs, r_tx2, cap, nwords):
    # refs: 4 planes x 3 row views (dx = -1, 0, +1), then the output block.
    # Each view is one padded grid row laid out (cap, NYp): slots on
    # sublanes, cells on lanes. The dy = +-1 neighbors are lane rolls of a
    # view (the border ring keeps every interior cell's neighbors in range;
    # the wrapped lanes only ever feed border columns, which are dropped).
    views = [[refs[p * 3 + r][0] for r in range(3)]
             for p in range(_CELL_PLANES)]
    out_ref = refs[_CELL_PLANES * 3]
    xc, yc, zc, ic = (v[1] for v in views)            # center row, (cap, NYp)
    nyp = xc.shape[1]
    words = [jnp.zeros(xc.shape, jnp.int32) for _ in range(nwords)]
    for q in range(_NEIGHBORHOOD):
        dx, dy = q // 3 - 1, q % 3 - 1
        nb = [v[dx + 1] if dy == 0
              else pltpu.roll(v[dx + 1], (-dy) % nyp, axis=1)
              for v in views]
        for s in range(cap):
            k = q * cap + s                           # candidate slot index
            xk, yk, zk, ik = (v[s:s + 1, :] for v in nb)
            ddx = xc - xk
            ddy = yc - yk
            d2 = ddx * ddx + ddy * ddy
            close = (
                (d2 <= r_tx2)
                & ((zc & zk) != 0)
                & (ic != ik)                          # same id = same node (or
                & (ik >= 0)                           # both empty, id -1)
            )
            words[k // 32] = _or_bit(words[k // 32], close, k % 32)
    for w in range(nwords):
        out_ref[0, w] = words[w]


@functools.partial(
    jax.jit, static_argnames=("ncx", "ncy", "r_tx2", "interpret")
)
def cell_close_words(xc, yc, zc, idc, ncx: int, ncy: int, r_tx2, *,
                     interpret: bool = False):
    """Tiled Pallas 3×3-cell-neighborhood close pass (see block comment).

    One grid step per interior grid row ``cx``: each input plane is laid
    out row-major as ``(ncx + 2, cap, NYp)`` (cells on lanes, padded to a
    lane multiple) and contributes the three rows ``cx - 1 .. cx + 1`` as
    ``(1, cap, NYp)`` blocks; the close bits of all cells in the row are
    built as 32 bit planes per output word. Pinned bitwise against
    :func:`cell_close_words_ref` in ``tests/test_kernels.py``.
    """
    cap = xc.shape[1]
    nx, ny = ncx + 2, ncy + 2
    nyp = -(-ny // 128) * 128
    nwords = (_NEIGHBORHOOD * cap + 31) // 32

    def rows(plane, fill):
        p = plane.reshape(nx, ny, cap).transpose(0, 2, 1)
        return jnp.pad(p, ((0, 0), (0, 0), (0, nyp - ny)),
                       constant_values=fill)

    planes = (rows(xc, _FAR), rows(yc, _FAR), rows(_as_i32(zc), 0),
              rows(idc, -1))
    in_specs, inputs = [], []
    for plane in planes:
        for r in range(3):
            in_specs.append(pl.BlockSpec(
                (1, cap, nyp), functools.partial(lambda i, r: (i + r, 0, 0),
                                                 r=r)))
            inputs.append(plane)

    kernel = functools.partial(_cell_kernel, r_tx2=r_tx2, cap=cap,
                               nwords=nwords)
    out = pl.pallas_call(
        kernel,
        grid=(ncx,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nwords, cap, nyp),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ncx, nwords, cap, nyp), jnp.int32),
        interpret=interpret,
        name="cell_close_words",
    )(*inputs)
    # interior columns back to the oracle's (cell, slot, word) layout
    out = out[:, :, :, 1:ncy + 1].transpose(0, 3, 2, 1)
    return jax.lax.bitcast_convert_type(
        out.reshape(ncx * ncy, cap, nwords), jnp.uint32
    )
