"""Train-step factories: synchronous all-reduce DP (baseline) and Floating
Gossip mode (the paper's technique as a first-class training mode).

All-reduce mode ("centralized" in the paper's framing):
  params replicated over (pod, data), sharded over model; grads mean-reduced
  by GSPMD; AdamW moments ZeRO-1-sharded over the full mesh.

Gossip mode (Floating Gossip):
  every (pod, data) index is an FG *node* holding its own full replica
  (leading replica axis R on params/opt-state); each step the node trains on
  its private observation shard (vmapped local AdamW), then runs a gossip
  round — pairwise ppermute exchange + weighted merge, gated by the
  mean-field success/busy/churn probabilities (repro.core.gossip).
  Optimizer moments are per-node and are NOT gossiped (the paper merges
  model coefficients only).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.gossip import GossipConfig, build_gossip_round
from repro.models.transformer import abstract_lm, init_lm, lm_loss
from repro.optim.optimizers import Optimizer
from repro.sharding.logical import (
    DEFAULT_RULES, Lx, ShardingRules, tree_specs,
)

__all__ = [
    "TrainMode", "make_allreduce_step", "make_gossip_step", "train_shardings",
]

TrainMode = str  # "allreduce" | "gossip"


def _batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def train_shardings(cfg: ArchConfig, mesh: Mesh, *, mode: str,
                    optimizer: Optimizer, rules: ShardingRules = DEFAULT_RULES):
    """(abstract state, specs) for the chosen mode."""
    abstract, logical = abstract_lm(cfg)
    if mode == "gossip":
        R = 1
        for a in _batch_axes(mesh):
            R *= mesh.shape[a]
        abstract = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((R,) + l.shape, l.dtype), abstract
        )
        logical = jax.tree.map(lambda l: Lx("replica", *l.axes), logical)
    param_specs = tree_specs(mesh, abstract, logical, rules)
    opt_abstract = jax.eval_shape(optimizer.init, abstract)
    # Moment subtrees mirror the param tree; flattened (ZeRO) leaves get the
    # full-mesh sharding instead (see _opt_specs).
    opt_specs = _opt_specs(opt_abstract, param_specs, mesh)
    return abstract, param_specs, opt_abstract, opt_specs, logical


def _opt_specs(opt_abstract, param_specs, mesh: Mesh):
    """Specs for optimizer state: per-leaf — match param spec if same rank,
    else (flattened ZeRO leaf) shard over the whole mesh."""
    total = 1
    for a in mesh.axis_names:
        total *= mesh.shape[a]
    full = P(tuple(mesh.axis_names))

    def one_subtree(sub):
        return jax.tree.map(
            lambda sl, ps: ps if len(sl.shape) == len(ps) else (
                full if sl.shape[0] % total == 0 else P()
            ),
            sub, param_specs,
        )

    return {k: one_subtree(v) for k, v in opt_abstract.items()}


def _accum_grads(loss_fn, params, batch, accum: int):
    """Microbatch gradient accumulation: scan over `accum` slices of the
    leading batch dim, averaging loss/grads. Peak activation memory drops
    ~accum x; grads are held once (f32-free: same dtype as params' grads)."""
    def slice_batch(b, i, n):
        def sl(x):
            m = x.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(x, i * m, m, axis=0)
        return {k: sl(v) for k, v in b.items()}

    def body(carry, i):
        g_acc, loss_acc, ce_acc, aux_acc = carry
        (loss, (ce, aux)), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, slice_batch(batch, i, accum)
        )
        g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
        return (g_acc, loss_acc + loss, ce_acc + ce, aux_acc + aux), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    z = jnp.asarray(0.0, jnp.float32)
    (g, loss, ce, aux), _ = jax.lax.scan(
        body, (g0, z, z, z), jnp.arange(accum)
    )
    inv = 1.0 / accum
    g = jax.tree.map(lambda x: x * inv, g)
    return g, loss * inv, ce * inv, aux * inv


def make_allreduce_step(cfg: ArchConfig, optimizer: Optimizer, *,
                        has_encoder: bool, chunk: int = 1024,
                        act_spec=None, ce_chunk: int | None = None,
                        accum: int = 1):
    """step(params, opt_state, batch, step_idx) -> (params, opt_state, metrics).

    ``act_spec``/``ce_chunk``/``accum``: sequence parallelism + chunked
    cross-entropy + microbatch accumulation (§Perf memory optimizations).
    """

    def loss_fn(p, b):
        return lm_loss(
            cfg, p, b["tokens"], b["labels"],
            enc_embeds=b.get("enc_embeds"), chunk=chunk,
            act_spec=act_spec, ce_chunk=ce_chunk,
        )

    def step(params, opt_state, batch, step_idx):
        if accum > 1:
            grads, loss, ce, aux = _accum_grads(loss_fn, params, batch, accum)
        else:
            (loss, (ce, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        params, opt_state = optimizer.update(grads, opt_state, params, step_idx)
        return params, opt_state, dict(loss=loss, ce=ce, aux=aux)

    return step


def make_gossip_step(cfg: ArchConfig, optimizer: Optimizer, mesh: Mesh,
                     param_specs, gcfg: GossipConfig, *,
                     has_encoder: bool, chunk: int = 1024,
                     act_spec=None, ce_chunk: int | None = None,
                     accum: int = 1):
    """Floating Gossip train step over the replica axis.

    step(params_R, opt_R, gstate, default_params_R, batch_R, step_idx)
      -> (params_R, opt_R, gstate, metrics)

    ``batch_R`` leaves have leading (R, per_replica, ...) axes.
    """
    round_fn, R = build_gossip_round(mesh, param_specs, gcfg)

    def local_update(p, s, tok, lab, enc, step_idx):
        def loss_fn(pp, b):
            return lm_loss(cfg, pp, b["tokens"], b["labels"],
                           enc_embeds=b.get("enc_embeds"), chunk=chunk,
                           act_spec=act_spec, ce_chunk=ce_chunk)
        b = dict(tokens=tok, labels=lab)
        if enc is not None:
            b["enc_embeds"] = enc
        if accum > 1:
            grads, loss, _, _ = _accum_grads(loss_fn, p, b, accum)
        else:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        p, s = optimizer.update(grads, s, p, step_idx)
        return p, s, loss

    def step(params, opt_state, gstate, default_params, batch, step_idx):
        enc = batch.get("enc_embeds")
        vm = jax.vmap(
            lambda p, s, t, l, e: local_update(p, s, t, l, e, step_idx),
            in_axes=(0, 0, 0, 0, 0 if enc is not None else None),
        )
        params, opt_state, losses = vm(
            params, opt_state, batch["tokens"], batch["labels"], enc
        )
        gstate = dict(count=gstate["count"] + 1.0, age=gstate["age"])

        if gcfg.period <= 1:
            params, gstate = round_fn(
                params, gstate, default_params, step_idx
            )
        else:
            def do(ops):
                p, g = round_fn(ops[0], ops[1], default_params, step_idx)
                return p, g
            params, gstate = jax.lax.cond(
                step_idx % gcfg.period == 0,
                lambda ops: do(ops),
                lambda ops: (ops[0], ops[1]),
                (params, gstate),
            )
        metrics = dict(
            loss=jnp.mean(losses), loss_max=jnp.max(losses),
            loss_min=jnp.min(losses),
        )
        return params, opt_state, gstate, metrics

    return step, R
