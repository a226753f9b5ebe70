"""Vectorized compute-queue operations (merge/train priority queues), the
bit-packed mask word layout shared by the whole engine, and
:func:`take_nodes`, the engine's reads of a per-node table by node index.

The legacy simulator enqueued jobs with a Python loop over the model count
``M`` (one masked scatter per model), so the traced program — and hence
compile time — grew linearly with ``M``. The ops here are pure scatters
whose *trace* is independent of ``M``: only array extents change.

Packed word layout
------------------

Every boolean protocol mask (incorporation masks, exchange snapshots, the
served merge payload, the previous-slot contact matrix) is stored as
``uint32`` words over its trailing axis: a length-``K`` boolean axis
becomes ``ceil(K/32)`` words, where **bit ``j`` of word ``w`` is element
``32*w + j``** (LSB-first, the :func:`pack_mask` convention) and the pad
bits of the last word are always zero. Set operations then become bitwise
word ops —

* union        ``a | b``
* intersection ``a & b``
* difference   ``a & ~b``        (pad bits stay 0: ``~b`` flips them on,
  but every ``&`` partner keeps them off)
* any/count    ``packed_any`` / ``packed_popcount``
* single bit   ``packed_onehot``

— which is exact (no float round trip), so the packed engine stays
*bitwise* equivalent to a boolean step (the plain reference
``bench/reference.py``, ``tests/test_sim_engine.py``) while shrinking the
``lax.scan`` carry ~8x (XLA stores a bool in one byte; 32 bools per word
is 4 bytes) and cutting the memory traffic the batched CPU engine is
bound by.

Queue convention (unchanged from the legacy simulator): a queue is an
``(N, Q)`` int32 array of model ids with ``-1`` marking a free slot. Jobs
are stored front-compact only by accident of arrival; service always takes
the lowest-index occupied slot (FIFO within the fixed arrival order), and
enqueues fill free slots in ascending slot order.

``enqueue_ascending`` reproduces the legacy loop semantics exactly:

* candidate items are the ``True`` entries of a per-node ``(N, M)`` ``want``
  matrix, considered in ascending ``m`` order (the legacy loop order);
* each item takes the next free slot in ascending slot order;
* items beyond the free capacity are dropped (the legacy behaviour when
  ``jnp.any(free)`` went False).

This is verified bit-for-bit against a reference per-``M`` loop in
``tests/test_sim_queue_ops.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.sim.cells import AUTO_CELLS_MIN_N

__all__ = [
    "enqueue_ascending", "pick_next_jobs", "advance_timers",
    "pack_mask", "unpack_mask", "packed_onehot", "packed_any",
    "packed_popcount", "shared_barrier", "take_nodes", "take_path",
    "take_paths", "TAKE_SELECT_MAX", "TAKE_MXU_MIN_WORDS",
]

#: Longest table :func:`take_nodes` reads by a one-hot select; longer
#: tables take the indexed gather. The dense contact range: there the
#: slot already does O(N²) work, while the cells backend (city scale)
#: must stay O(N) per read.
TAKE_SELECT_MAX = AUTO_CELLS_MIN_N

#: Narrowest row, in 32-bit words, that :func:`take_nodes` reads by a
#: one-hot MXU product rather than the select. On a TPU v5e the product
#: is the faster from 4 words at 200 rows and from 2 at 64, 200 readers
#: (``scripts/take_crossover.py``). Never below 4: the reads of the
#: single-model step (at most 3 words) keep the select.
TAKE_MXU_MIN_WORDS = 4

#: Every answer of :func:`take_path`, in the order :func:`take_paths`
#: names them.
TAKE_PATHS = ("index", "onehot", "mxu")


def take_path(rows: int, words: int) -> str:
    """How :func:`take_nodes` reads a table of ``rows`` rows of ``words``
    32-bit words each (a bool column counts one word): ``"index"`` (the
    gather), ``"onehot"`` (the select) or ``"mxu"`` (the product)."""
    if rows > TAKE_SELECT_MAX:
        return "index"
    return "mxu" if words >= TAKE_MXU_MIN_WORDS else "onehot"


def take_paths(reads) -> str:
    """The paths a set of ``(rows, words)`` reads take, joined by ``+`` in
    :data:`TAKE_PATHS` order (``"onehot+mxu"``)."""
    used = {take_path(rows, words) for rows, words in reads}
    return "+".join(p for p in TAKE_PATHS if p in used)


def take_nodes(table, idx: jnp.ndarray):
    """``table[idx]`` for a per-node (or per-slot) table and a vector of
    row indices in ``[-L, L)``, ``L = table.shape[0]`` (a negative index
    wraps, as in ``table[idx]``). ``table`` may be a tuple of tables with
    the same rows, read through one select or product: the result is then
    the tuple of their reads. Tables of 4-byte dtypes or bool.

    The path is :func:`take_path` of the rows and the words per row of all
    the tables together. Up to :data:`TAKE_SELECT_MAX` rows the read is
    one-hot, since an indexed gather on the TPU is a serial loop of
    ~10 ns per element:

    * narrow rows, a select under the scope ``fg.take``: an ``(L, N)``
      compare of the row ids against ``idx`` (table rows on sublanes,
      readers on lanes), the matching row kept as uint32 bits and
      OR-reduced over the rows;
    * rows of :data:`TAKE_MXU_MIN_WORDS` words or more, a matrix product
      under ``fg.take`` / ``fg.take.mxu``: each word split into four 8-bit
      planes (a bool column into one), held in bfloat16, which is exact
      for 0-255, and multiplied by the ``(N, L)`` bfloat16 one-hot with
      float32 accumulation. Each output sums one non-zero term, so it is
      the plane exactly, and the planes join back into the words.

    Both move bits unchanged, so the result is bitwise ``table[idx]`` for
    every value (NaN payloads, ``-0.0``, ``±inf``). Longer tables trace
    exactly ``table[idx]``."""
    tables = table if isinstance(table, tuple) else (table,)
    path = take_path(tables[0].shape[0],
                     sum(math.prod(t.shape[1:]) for t in tables))
    if path == "index":
        got = tuple(t[idx] for t in tables)
    else:
        with jax.named_scope("fg.take"):
            if path == "mxu":
                with jax.named_scope("fg.take.mxu"):
                    got = _take_mxu(tables, idx)
            else:
                got = _take_select(tables, idx)
    return got if isinstance(table, tuple) else got[0]


def _words(t) -> jnp.ndarray:
    """``t`` as ``(L, W)`` uint32 words (a bool as 0 / 1)."""
    w = (t.astype(jnp.uint32) if t.dtype == jnp.bool_
         else jax.lax.bitcast_convert_type(t, jnp.uint32))
    return w.reshape(t.shape[0], -1)


def _from_words(word, t, idx):
    """The inverse of :func:`_words` for ``idx``'s readers of ``t``."""
    word = word.reshape(idx.shape + t.shape[1:])
    return (word != 0 if t.dtype == jnp.bool_
            else jax.lax.bitcast_convert_type(word, t.dtype))


def _take_select(tables, idx):
    n = tables[0].shape[0]
    cols = [_words(t) for t in tables]
    bits = jnp.concatenate(cols, axis=1)
    idx = jnp.where(idx < 0, idx + n, idx)
    onehot = jnp.arange(n, dtype=idx.dtype)[:, None] == idx[None, :]
    picked = jax.lax.reduce(
        jnp.where(onehot, bits.T[:, :, None], jnp.uint32(0)), np.uint32(0),
        jax.lax.bitwise_or, (1,),
    ).T                                                     # (N, W)
    out, w0 = [], 0
    for t, c in zip(tables, cols):
        out.append(_from_words(picked[:, w0:w0 + c.shape[1]], t, idx))
        w0 += c.shape[1]
    return tuple(out)


#: Bit offsets of a word's four byte planes.
_SHIFTS = (0, 8, 16, 24)


def _n_planes(t) -> int:
    return 1 if t.dtype == jnp.bool_ else len(_SHIFTS)


def _take_mxu(tables, idx):
    n = tables[0].shape[0]
    cols = [_words(t) for t in tables]
    # per table, plane k holds byte k of every word (a bool column has one)
    planes = jnp.concatenate(
        [(c >> s) & jnp.uint32(0xFF) for t, c in zip(tables, cols)
         for s in _SHIFTS[:_n_planes(t)]], axis=1).astype(jnp.bfloat16)
    idx = jnp.where(idx < 0, idx + n, idx)
    onehot = (idx[:, None] == jnp.arange(n, dtype=idx.dtype)[None, :]
              ).astype(jnp.bfloat16)                        # (N, L)
    picked = jax.lax.dot_general(
        onehot, planes, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.uint32)  # (N, P)
    out, p0 = [], 0
    for t, c in zip(tables, cols):
        w = c.shape[1]
        word = picked[:, p0:p0 + w]
        for k in range(1, _n_planes(t)):
            word = word | (picked[:, p0 + k * w:p0 + (k + 1) * w]
                           << _SHIFTS[k])
        p0 += _n_planes(t) * w
        out.append(_from_words(word, t, idx))
    return tuple(out)


def shared_barrier(x):
    """``lax.optimization_barrier``: a materialization point.

    XLA's producer-duplicating fusion otherwise inlines the producing
    computation into *every* consumer — in a sweep batch that re-computes
    per-seed-shared intermediates (the pairwise distance matrix, the
    observer-rank matrix) once per scenario inside each fused per-run
    consumer, silently undoing the work sharing ``vmap`` set up (measured
    ~25% of full-sweep wall time for the distance matrix). The barrier is
    the identity and batches under ``vmap``, so results are bit-identical
    (pinned in ``tests/test_sim_queue_ops.py``).
    """
    return jax.lax.optimization_barrier(x)


def pack_mask(mask: jnp.ndarray) -> jnp.ndarray:
    """Pack a trailing boolean axis of length K into ceil(K/32) uint32 words.

    The merge queue carries an incorporation mask per queued job; packed,
    the queue payload shrinks 32x — it is the largest buffer the scan
    carries, and on CPU the batched engine is memory-traffic-bound. Bit
    packing is exact, so the engine stays bit-equivalent to the boolean
    reference."""
    k = mask.shape[-1]
    pad = (-k) % 32
    if pad:
        mask = jnp.concatenate(
            [mask, jnp.zeros((*mask.shape[:-1], pad), bool)], axis=-1
        )
    words = (k + pad) // 32
    grouped = mask.reshape(*mask.shape[:-1], words, 32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(
        jnp.where(grouped, weights, jnp.uint32(0)), axis=-1, dtype=jnp.uint32
    )


def unpack_mask(words: jnp.ndarray, k: int) -> jnp.ndarray:
    """Inverse of :func:`pack_mask` for a trailing axis of K bits."""
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    flat = bits.reshape(*words.shape[:-1], words.shape[-1] * 32)
    return flat[..., :k].astype(bool)


def packed_onehot(idx: jnp.ndarray, k: int) -> jnp.ndarray:
    """Packed one-hot: words for a K-bit mask with only bit ``idx`` set.

    ``idx`` is any integer-shaped array (values in [0, K)); the result
    appends a trailing axis of ``ceil(K/32)`` words."""
    idx = idx.astype(jnp.uint32)
    word = (idx // 32)[..., None]
    bit = (idx % 32)[..., None]
    lanes = jnp.arange((k + 31) // 32, dtype=jnp.uint32)
    return jnp.where(lanes == word, jnp.uint32(1) << bit, jnp.uint32(0))


def packed_any(words: jnp.ndarray) -> jnp.ndarray:
    """``jnp.any`` over the packed trailing word axis."""
    return jnp.any(words != 0, axis=-1)


def packed_popcount(words: jnp.ndarray) -> jnp.ndarray:
    """Number of set bits over the packed trailing word axis (int32)."""
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32), axis=-1)


def enqueue_ascending(queue: jnp.ndarray, want: jnp.ndarray, *payloads):
    """Enqueue every wanted model id into the first free slots, vectorized.

    Args:
      queue: ``(N, Q)`` int32 queue of model ids, ``-1`` = free.
      want:  ``(N, M)`` bool — enqueue model ``m`` for node ``n``.
      payloads: pairs ``(dest, src)`` where ``dest`` is ``(N, Q, ...)`` queue
        payload storage and ``src`` is ``(N, M, ...)`` per-item payload;
        payload rows are written alongside the model id.

    Returns:
      ``(new_queue, *new_payload_dests)``.

    The item->slot assignment is expressed as a dense (N, M, Q) rank-match
    select rather than a scatter: item ``m`` (with arrival rank ``k`` among
    this slot's wanted items) lands in the free slot whose free-rank is
    ``k``. XLA lowers scatters to serialized per-element loops on CPU
    (catastrophically so under vmap); the dense select is pure elementwise
    work + a reduction over ``M`` and vectorizes across batched runs.
    """
    m = want.shape[1]
    q = queue.shape[1]
    free = queue < 0                                     # (N, Q)

    if m == 1:
        # Single-model fast path (the paper's default M=1 sweeps): the only
        # candidate goes to the first free slot — one min reduce, no
        # cumsums. Bit-identical to the general path below.
        first_free = jnp.min(
            jnp.where(free, jnp.arange(q, dtype=jnp.int32), q), axis=1
        )
        ok = want[:, 0] & (first_free < q)
        sel_q = (jnp.arange(q)[None, :] == first_free[:, None]) & ok[:, None]
        new_queue = jnp.where(sel_q, 0, queue)
        new_payloads = []
        for store, src in payloads:
            extra = src.ndim - 2
            sel_e = sel_q.reshape(sel_q.shape + (1,) * extra)
            src_row = src[:, 0][:, None].astype(store.dtype)
            new_payloads.append(jnp.where(sel_e, src_row, store))
        return (new_queue, *new_payloads)

    free_rank = jnp.cumsum(free, axis=1) - 1             # rank among free slots
    n_free = jnp.sum(free, axis=1)                       # (N,)

    rank = jnp.cumsum(want, axis=1) - 1                  # (N, M) arrival rank
    ok = want & (rank < n_free[:, None])
    # sel[n, m, q] — item m of node n lands in slot q (one-hot over both m
    # and q wherever an assignment exists)
    sel = free[:, None, :] & (free_rank[:, None, :] == rank[:, :, None]) \
        & ok[:, :, None]
    taken = jnp.any(sel, axis=1)                         # (N, Q)
    m_ids = jnp.arange(m, dtype=queue.dtype)[None, :, None]
    new_queue = jnp.where(
        taken, jnp.sum(sel * m_ids, axis=1, dtype=queue.dtype), queue
    )

    new_payloads = []
    for store, src in payloads:
        extra = src.ndim - 2                             # trailing payload dims
        sel_e = sel.reshape(sel.shape + (1,) * extra)
        src_e = jnp.expand_dims(src, 2)                  # (N, M, 1, ...)
        if store.dtype == jnp.bool_:
            val = jnp.any(sel_e & src_e, axis=1)
        else:
            val = jnp.sum(sel_e * src_e, axis=1).astype(store.dtype)
        taken_e = taken.reshape(taken.shape + (1,) * extra)
        new_payloads.append(jnp.where(taken_e, val, store))
    return (new_queue, *new_payloads)


def advance_timers(serving: jnp.ndarray, serv_left: jnp.ndarray, dt):
    """Tick running jobs; return (serv_left, finished_merge, finished_train)."""
    serv_left = jnp.where(serving >= 0, serv_left - dt, serv_left)
    fin = (serving >= 0) & (serv_left <= 0.0)
    return serv_left, fin & (serving == 0), fin & (serving == 1)


def pick_next_jobs(
    *,
    serving: jnp.ndarray,       # (N,) -1 idle / 0 merge / 1 train
    serv_left: jnp.ndarray,
    serv_model: jnp.ndarray,
    serv_mask: jnp.ndarray,     # (N, ceil(K/32)) packed merge payload
    serv_slot: jnp.ndarray,     # (N,)  train payload
    mq_model: jnp.ndarray,      # (N, QM)
    mq_mask: jnp.ndarray,       # (N, QM, ceil(K/32)) packed uint32
    tq_model: jnp.ndarray,      # (N, QT)
    tq_slot: jnp.ndarray,       # (N, QT)
    T_M,
    T_T,
    can_serve=None,             # (N,) bool: node may start a job this slot
):
    """Assign idle servers their next job: merge queue first (non-preemptive
    priority), then training. Returns the updated server fields and queues.

    The merge payload stays bit-packed end to end: the queue word rows move
    into ``serv_mask`` verbatim (no unpack on the hot path). Head-of-queue
    extraction is a dense one-hot sum, not a gather — XLA lowers (batched)
    gathers to scalar loops on CPU, which dominated the step profile.

    ``can_serve`` (fault layer: node is on/accessible) gates *starting* a
    job only — queued work waits; ongoing service is frozen separately via
    the per-node ``dt`` of :func:`advance_timers`. ``None`` (default)
    leaves the program untouched."""
    qm = mq_model.shape[1]
    qt = tq_model.shape[1]

    def row_sel(arr, sel):
        # arr[n, first[n]] as a one-hot reduction over the queue axis
        sel = sel.reshape(sel.shape + (1,) * (arr.ndim - 2))
        return jnp.sum(jnp.where(sel, arr, arr.dtype.type(0)), axis=1)

    def first_true(cond):
        # first True index (or Q if none) as a plain min reduce — argmax's
        # variadic reduce lowers to a scalar loop on CPU
        q = cond.shape[-1]
        return jnp.min(
            jnp.where(cond, jnp.arange(q, dtype=jnp.int32), q), axis=-1
        )

    m_avail = jnp.any(mq_model >= 0, axis=-1)
    m_first = first_true(mq_model >= 0)
    take_m = (serving < 0) & m_avail
    if can_serve is not None:
        take_m = take_m & can_serve
    sel_m = (jnp.arange(qm)[None, :] == m_first[:, None]) & take_m[:, None]
    serv_model = jnp.where(take_m, row_sel(mq_model, sel_m), serv_model)
    serv_mask = jnp.where(take_m[:, None], row_sel(mq_mask, sel_m), serv_mask)
    mq_model = jnp.where(sel_m, -1, mq_model)
    serving = jnp.where(take_m, 0, serving)
    serv_left = jnp.where(take_m, T_M, serv_left)

    t_avail = jnp.any(tq_model >= 0, axis=-1)
    t_first = first_true(tq_model >= 0)
    take_t = (serving < 0) & t_avail
    if can_serve is not None:
        take_t = take_t & can_serve
    sel_t = (jnp.arange(qt)[None, :] == t_first[:, None]) & take_t[:, None]
    serv_model = jnp.where(take_t, row_sel(tq_model, sel_t), serv_model)
    serv_slot = jnp.where(take_t, row_sel(tq_slot, sel_t), serv_slot)
    tq_model = jnp.where(sel_t, -1, tq_model)
    serving = jnp.where(take_t, 1, serving)
    serv_left = jnp.where(take_t, T_T, serv_left)

    return dict(
        serving=serving, serv_left=serv_left, serv_model=serv_model,
        serv_mask=serv_mask, serv_slot=serv_slot,
        mq_model=mq_model, tq_model=tq_model,
    )
