"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` compiles the kernel on a TPU backend. Elsewhere the
attention and SSD wrappers fall back to interpret mode, which only the
tests use (they pin the kernels against their references); no simulator
path calls them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.gossip_merge import gossip_merge
from repro.kernels.ssd_scan import ssd_scan

__all__ = [
    "attention_op", "ssd_op", "gossip_merge_op", "default_interpret",
]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def attention_op(q, k, v, *, causal=True, window=None, blk_q=256, blk_k=512,
                 interpret=None):
    """GQA-aware wrapper. q: (B,S,H,D); k/v: (B,S,Hkv,D) with H % Hkv == 0.

    KV heads are logically repeated by reshaping q into (Hkv, group) — each
    kernel instance still reads each KV block once.
    """
    interpret = default_interpret() if interpret is None else interpret
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    # flatten to (B * H, S, D); repeat kv heads to match (gather, not copy,
    # under XLA when rep == 1)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, Skv, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, Skv, D)
    out = flash_attention(
        qf, kf, vf, causal=causal, window=window, blk_q=blk_q, blk_k=blk_k,
        interpret=interpret,
    )
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def ssd_op(x, dt, A, B_, C_, D, *, chunk=128, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return ssd_scan(x, dt, A, B_, C_, D, chunk=chunk, interpret=interpret)


def gossip_merge_op(own_tree, peer_tree, w_own, success, *, interpret=None):
    """Leafwise fused merge. ``interpret=None`` defers to ``gossip_merge``'s
    own backend dispatch (compiled kernel on TPU, the bit-identical jnp
    reference elsewhere — interpret mode is reserved for tests)."""
    return jax.tree.map(
        lambda a, b: gossip_merge(a, b, w_own, success, interpret=interpret),
        own_tree, peer_tree,
    )
