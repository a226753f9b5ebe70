"""End-to-end behaviour tests for the framework.

1. training actually learns: tiny LM on the synthetic Markov stream reduces
   loss well below the uniform-vocab entropy;
2. the serving engine generates deterministically with a consistent cache;
3. gossip-mode training on multiple fake devices converges (subprocess —
   see test_gossip_protocol.py for the protocol-level properties);
4. checkpoint round-trips a training state.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs.base import ArchConfig, LayerSpec
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.transformer import init_lm
from repro.optim import adamw
from repro.serve.engine import ServeEngine
from repro.train.trainer import make_allreduce_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ArchConfig(
    name="sys-tiny", n_layers=2, d_model=96, n_heads=4, n_kv_heads=2,
    d_ff=192, vocab_size=256, vocab_pad_multiple=128, dtype="float32",
    pattern=(LayerSpec(),), remat=False,
)


def test_training_learns_markov_stream():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    data = SyntheticLM(DataConfig(vocab_size=256, seq_len=64, global_batch=8,
                                  seed=3, markov_states=16))
    params, _ = init_lm(TINY, jax.random.PRNGKey(0))
    opt = adamw(3e-3)
    state = opt.init(params)
    step_fn = jax.jit(make_allreduce_step(TINY, opt, has_encoder=False))
    losses = []
    with jax.set_mesh(mesh):
        for s in range(80):
            tok, lab = data.global_arrays(s, mesh)
            params, state, m = step_fn(
                params, state, dict(tokens=tok, labels=lab), jnp.asarray(s))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.4, (losses[0], losses[-1])
    # below the uniform-vocab entropy ln(256)=5.55 => learned structure
    assert losses[-1] < 5.5


def test_serve_engine_generates():
    params, _ = init_lm(TINY, jax.random.PRNGKey(1))
    engine = ServeEngine(cfg=TINY, params=params, max_len=32)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (3, 5), 0, 256)
    out1 = engine.generate(prompts, 8)
    out2 = engine.generate(prompts, 8)
    assert out1.shape == (3, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(out1.max()) < TINY.vocab_size


def test_checkpoint_roundtrips_train_state(tmp_path):
    params, _ = init_lm(TINY, jax.random.PRNGKey(0))
    opt = adamw(1e-3)
    state = opt.init(params)
    path = save_checkpoint(str(tmp_path), 7, {"p": params, "opt": state})
    like = jax.tree.map(jnp.zeros_like, {"p": params, "opt": state})
    restored, step = restore_checkpoint(path, like)
    assert step == 7
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(restored["p"])):
        np.testing.assert_array_equal(
            np.asarray(x, np.float32), np.asarray(y, np.float32))


def test_gossip_training_converges_multidevice():
    """Full gossip train loop on 8 fake devices (subprocess)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.configs.base import ArchConfig, LayerSpec
        from repro.core.gossip import GossipConfig
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.models.transformer import init_lm
        from repro.optim import adamw
        from repro.train.trainer import make_gossip_step, train_shardings

        mesh = jax.make_mesh((8, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = ArchConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                         n_kv_heads=2, d_ff=128, vocab_size=256,
                         vocab_pad_multiple=128, dtype="float32",
                         pattern=(LayerSpec(),), remat=False)
        data = SyntheticLM(DataConfig(vocab_size=256, seq_len=32,
                                      global_batch=32, seed=0,
                                      markov_states=16))
        opt = adamw(3e-3)
        abstract, pspecs, *_ = train_shardings(cfg, mesh, mode="gossip",
                                               optimizer=opt)
        R = 8
        reps = [init_lm(cfg, k)[0] for k in jax.random.split(jax.random.PRNGKey(0), R)]
        params = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
        with jax.set_mesh(mesh):
            params = jax.tree.map(
                lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
                params, pspecs)
            default = jax.tree.map(jnp.zeros_like, params)
            state = jax.vmap(opt.init)(params)
            gstate = dict(count=jnp.zeros((R,)), age=jnp.zeros((R,)))
            gcfg = GossipConfig(axis_names=("data",), matching="random",
                                success_prob=0.9, busy_prob=0.05,
                                merge_policy="obs_count")
            step, _ = make_gossip_step(cfg, opt, mesh, pspecs, gcfg,
                                       has_encoder=False)
            step = jax.jit(step)
            losses = []
            for s in range(50):
                tok, lab = data.global_arrays(s, mesh)
                batch = dict(tokens=tok.reshape(R, 4, 32),
                             labels=lab.reshape(R, 4, 32))
                params, state, gstate, m = step(params, state, gstate,
                                                default, batch, jnp.asarray(s))
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.35, (losses[0], losses[-1])
        print("OK", losses[0], losses[-1])
    """ % os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    assert "OK" in r.stdout
