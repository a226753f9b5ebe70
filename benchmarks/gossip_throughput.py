"""Gossip-vs-allreduce trainer microbenchmark (host CPU, 8 fake devices).

Measures wall time per step and final loss for a tiny transformer trained
with (a) synchronous all-reduce DP, (b) Floating Gossip with mean-field
gates — the datacenter analogue of the paper's centralized-vs-FG comparison.
Runs in a subprocess so the 8-device override never leaks into the caller.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

from benchmarks.common import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BODY = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, time
sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs.base import ArchConfig, LayerSpec
from repro.core.gossip import GossipConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.transformer import init_lm, abstract_lm
from repro.optim import adamw
from repro.sharding.logical import DEFAULT_RULES, Lx, tree_specs
from repro.train.trainer import make_allreduce_step, make_gossip_step, train_shardings

mesh = jax.make_mesh((8, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = ArchConfig(name="bench-tiny", n_layers=2, d_model=128, n_heads=4,
                 n_kv_heads=2, d_ff=256, vocab_size=512, vocab_pad_multiple=128,
                 dtype="float32", pattern=(LayerSpec(),), remat=False)
data = SyntheticLM(DataConfig(vocab_size=512, seq_len=64, global_batch=32, seed=0))
opt = adamw(3e-3)
key = jax.random.PRNGKey(0)
out = {}

with jax.set_mesh(mesh):
    # ---- all-reduce baseline ----
    params, _ = init_lm(cfg, key)
    state = opt.init(params)
    step_fn = jax.jit(make_allreduce_step(cfg, opt, has_encoder=False))
    losses = []
    t0 = time.time()
    for s in range(40):
        tok, lab = data.global_arrays(s, mesh)
        params, state, m = step_fn(params, state, dict(tokens=tok, labels=lab),
                                   jnp.asarray(s))
        losses.append(float(m["loss"]))
    out["allreduce"] = dict(t=time.time() - t0, loss0=losses[0], lossN=losses[-1])

    # ---- Floating Gossip ----
    abstract, pspecs, opt_abs, ospecs, _ = train_shardings(
        cfg, mesh, mode="gossip", optimizer=opt)
    R = 8
    def rep_init(k):
        ps = [init_lm(cfg, kk)[0] for kk in jax.random.split(k, R)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
    params = jax.device_put(rep_init(key),
                            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs))
    default = jax.tree.map(jnp.zeros_like, params)
    state = jax.vmap(opt.init)(params)
    gstate = dict(count=jnp.zeros((R,)), age=jnp.zeros((R,)))
    gcfg = GossipConfig(axis_names=("data",), matching="random",
                        success_prob=0.95, busy_prob=0.02, churn_prob=0.0,
                        merge_policy="obs_count")
    gstep, _ = make_gossip_step(cfg, opt, mesh, pspecs, gcfg, has_encoder=False)
    gstep = jax.jit(gstep)
    losses = []
    t0 = time.time()
    for s in range(40):
        tok, lab = data.global_arrays(s, mesh)
        batch = dict(tokens=tok.reshape(R, 4, 64), labels=lab.reshape(R, 4, 64))
        params, state, gstate, m = gstep(params, state, gstate, default,
                                         batch, jnp.asarray(s))
        losses.append(float(m["loss"]))
    out["gossip"] = dict(t=time.time() - t0, loss0=losses[0], lossN=losses[-1])

print(json.dumps(out))
"""


def run(quick: bool = False) -> list[dict]:
    code = _BODY % os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    res = json.loads(r.stdout.strip().splitlines()[-1])
    rows = []
    for mode, d in res.items():
        rows.append(dict(mode=mode, wall_s=round(d["t"], 2),
                         loss_first=round(d["loss0"], 3),
                         loss_last=round(d["lossN"], 3)))
    return rows


def main(quick: bool = False) -> None:
    t0 = time.time()
    rows = run(quick)
    g = next(r for r in rows if r["mode"] == "gossip")
    a = next(r for r in rows if r["mode"] == "allreduce")
    emit("gossip_throughput", rows, t0,
         f"gossip_final={g['loss_last']};allreduce_final={a['loss_last']}")


if __name__ == "__main__":
    main()
