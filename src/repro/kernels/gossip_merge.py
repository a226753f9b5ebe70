"""Fused gossip-merge Pallas kernel: the FG merging operation.

Computes ``out = success ? w_own * own + (1 - w_own) * peer : own`` over a
flat parameter buffer in fp32 accumulation, in one pass — the merge runs
right after the ppermute delivers the peer replica, so fusing the convex
combination avoids materializing ``w*own`` / ``(1-w)*peer`` temporaries in
HBM (the merge is purely memory-bound: 2 reads + 1 write per element).

Scalars (w_own, success) ride in SMEM via PrefetchScalarGridSpec so one
compiled kernel serves every round's weights.

Two entry points:

* :func:`gossip_merge` — scalar (w_own, success) over an any-shape buffer;
  the datacenter gossip path (``repro.core.gossip.build_gossip_round``)
  merges whole replicas through it.
* :func:`gossip_merge_rows` — per-row ``(N,)`` weights/success over an
  ``(N, D)`` buffer; the sim-substrate Gossip-Learning layer
  (``repro.sim.learn``) merges every node's parameter vector against its
  partner's snapshot in one call.
* :func:`gossip_merge_rows_scaled` — the defended-merge variant: a per-row
  ``scale`` multiplies the peer payload inside the fused combine
  (``w*own + (1-w)*(scale*peer)``), so the Byzantine norm-clip screen
  (``repro.core.merge.DefenseConfig.norm_clip``) costs no extra pass over
  the ``(N, D)`` buffer. ``scale == 1`` everywhere is bitwise
  :func:`gossip_merge_rows`.

Dispatch rule (the ``kernels/contacts.py`` pattern): with
``interpret=None`` (the default) the **compiled** kernel runs only on TPU
backends; everywhere else the bit-identical ``jnp`` reference
(``repro.kernels.ref.gossip_merge_ref``) runs instead. Interpret mode is
reserved for tests, which pin the kernel against the reference bit for
bit on padded/odd-length buffers (``tests/test_kernels.py``,
``tests/test_sim_learn.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gossip_merge", "gossip_merge_rows", "gossip_merge_rows_scaled"]

BLK = 16 * 1024  # 64 KiB fp32 per operand block — 3 operands well under VMEM
BLK_ROWS = 256   # rows per grid step of the per-row kernel
LANE = 128       # TPU lane width: trailing dims pad to a multiple of this


def _kernel(scalars_ref, own_ref, peer_ref, out_ref):
    w = scalars_ref[0]
    success = scalars_ref[1]
    own = own_ref[...].astype(jnp.float32)
    peer = peer_ref[...].astype(jnp.float32)
    merged = w * own + (1.0 - w) * peer
    out = jnp.where(success > 0.5, merged, own)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _merge_pallas(own, peer, w_own, success, *, interpret: bool):
    shape = own.shape
    flat = own.reshape(-1)
    pflat = peer.reshape(-1)
    n = flat.shape[0]
    nb = -(-n // BLK)
    pad = nb * BLK - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
        pflat = jnp.pad(pflat, (0, pad))
    scalars = jnp.stack([
        jnp.asarray(w_own, jnp.float32),
        jnp.asarray(success, jnp.float32),
    ])

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((BLK,), lambda i, s: (i,)),
                pl.BlockSpec((BLK,), lambda i, s: (i,)),
            ],
            out_specs=pl.BlockSpec((BLK,), lambda i, s: (i,)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb * BLK,), own.dtype),
        interpret=interpret,
        name="gossip_merge",
    )(scalars, flat, pflat)
    return out[:n].reshape(shape)


def gossip_merge(own, peer, w_own, success, *, interpret: bool | None = None):
    """``success ? w_own*own + (1-w_own)*peer : own`` (fp32 accumulate).

    ``own``/``peer``: any-shape arrays (same shape/dtype); ``w_own``,
    ``success``: scalars. ``interpret=None`` dispatches: compiled kernel
    on TPU, the bit-identical ``jnp`` reference elsewhere; pass
    ``True``/``False`` to force the Pallas path (tests / TPU overrides).
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _merge_pallas(own, peer, w_own, success, interpret=False)
        from repro.kernels.ref import gossip_merge_ref

        return gossip_merge_ref(
            own, peer, jnp.asarray(w_own, jnp.float32),
            jnp.asarray(success, jnp.float32) > 0.5,
        )
    return _merge_pallas(own, peer, w_own, success, interpret=interpret)


def _rows_kernel(w_ref, s_ref, own_ref, peer_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)        # (BLK_ROWS, 1)
    s = s_ref[...].astype(jnp.float32)        # (BLK_ROWS, 1)
    own = own_ref[...].astype(jnp.float32)    # (BLK_ROWS, Dp)
    peer = peer_ref[...].astype(jnp.float32)
    merged = w * own + (1.0 - w) * peer
    out_ref[...] = jnp.where(s > 0.5, merged, own).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rows_pallas(own, peer, w_own, success, *, interpret: bool):
    n, d = own.shape
    nb = -(-n // BLK_ROWS)
    dp = -(-d // LANE) * LANE
    pad_n, pad_d = nb * BLK_ROWS - n, dp - d
    if pad_n or pad_d:
        own = jnp.pad(own, ((0, pad_n), (0, pad_d)))
        peer = jnp.pad(peer, ((0, pad_n), (0, pad_d)))
    w = jnp.pad(jnp.asarray(w_own, jnp.float32), (0, pad_n))[:, None]
    s = jnp.pad(
        jnp.asarray(success, jnp.float32), (0, pad_n)
    )[:, None]

    out = pl.pallas_call(
        _rows_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLK_ROWS, dp), lambda i: (i, 0)),
            pl.BlockSpec((BLK_ROWS, dp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLK_ROWS, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * BLK_ROWS, dp), own.dtype),
        interpret=interpret,
        name="gossip_merge_rows",
    )(w, s, own, peer)
    return out[:n, :d]


def gossip_merge_rows(own, peer, w_own, success, *,
                      interpret: bool | None = None):
    """Row-wise merge: ``out[i] = success[i] ? w[i]*own[i] + (1-w[i])*peer[i]
    : own[i]`` in fp32 accumulation.

    ``own``/``peer``: ``(N, D)``; ``w_own``: ``(N,)`` float;
    ``success``: ``(N,)`` bool/float. Same dispatch rule as
    :func:`gossip_merge`.
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _rows_pallas(own, peer, w_own, success, interpret=False)
        from repro.kernels.ref import gossip_merge_rows_ref

        return gossip_merge_rows_ref(own, peer, w_own, success)
    return _rows_pallas(own, peer, w_own, success, interpret=interpret)


def _rows_scaled_kernel(w_ref, c_ref, s_ref, own_ref, peer_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)        # (BLK_ROWS, 1)
    c = c_ref[...].astype(jnp.float32)        # (BLK_ROWS, 1) peer scale
    s = s_ref[...].astype(jnp.float32)        # (BLK_ROWS, 1)
    own = own_ref[...].astype(jnp.float32)    # (BLK_ROWS, Dp)
    peer = peer_ref[...].astype(jnp.float32)
    merged = w * own + (1.0 - w) * (c * peer)
    out_ref[...] = jnp.where(s > 0.5, merged, own).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rows_scaled_pallas(own, peer, w_own, scale, success, *, interpret: bool):
    n, d = own.shape
    nb = -(-n // BLK_ROWS)
    dp = -(-d // LANE) * LANE
    pad_n, pad_d = nb * BLK_ROWS - n, dp - d
    if pad_n or pad_d:
        own = jnp.pad(own, ((0, pad_n), (0, pad_d)))
        peer = jnp.pad(peer, ((0, pad_n), (0, pad_d)))
    w = jnp.pad(jnp.asarray(w_own, jnp.float32), (0, pad_n))[:, None]
    c = jnp.pad(jnp.asarray(scale, jnp.float32), (0, pad_n))[:, None]
    s = jnp.pad(
        jnp.asarray(success, jnp.float32), (0, pad_n)
    )[:, None]

    out = pl.pallas_call(
        _rows_scaled_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BLK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((BLK_ROWS, dp), lambda i: (i, 0)),
            pl.BlockSpec((BLK_ROWS, dp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BLK_ROWS, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * BLK_ROWS, dp), own.dtype),
        interpret=interpret,
        name="gossip_merge_rows_scaled",
    )(w, c, s, own, peer)
    return out[:n, :d]


def gossip_merge_rows_scaled(own, peer, w_own, scale, success, *,
                             interpret: bool | None = None):
    """Defended row-wise merge: ``out[i] = success[i] ? w[i]*own[i] +
    (1-w[i])*(scale[i]*peer[i]) : own[i]`` in fp32 accumulation.

    ``scale`` (N,) is the norm-clip down-scaling factor
    (``repro.core.merge.norm_clip_factors``); fusing it here keeps the
    defended merge a single pass over the parameter buffer. Same dispatch
    rule as :func:`gossip_merge`.
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _rows_scaled_pallas(
                own, peer, w_own, scale, success, interpret=False
            )
        from repro.kernels.ref import gossip_merge_rows_scaled_ref

        return gossip_merge_rows_scaled_ref(own, peer, w_own, scale, success)
    return _rows_scaled_pallas(
        own, peer, w_own, scale, success, interpret=interpret
    )
