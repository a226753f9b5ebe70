"""Gossip protocol tests. These need >1 device, so they run in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process keeps the default single device, as the dry-run contract requires).
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.gossip import (GossipConfig, init_gossip_state,
                                       build_gossip_round, hypercube_matchings,
                                       random_matchings)
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        R = 8
        def put(t, s):
            return jax.device_put(t, NamedSharding(mesh, s))
    """ % os.path.join(ROOT, "src")) + textwrap.dedent(body)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_matchings_are_involutions():
    from repro.core.gossip import hypercube_matchings, random_matchings
    for m in hypercube_matchings(16) + random_matchings(16, 4, 0):
        perm = {s: d for s, d in m}
        assert len(perm) == 16
        for s, d in m:
            assert perm[d] == s, "pairing must be symmetric"
            assert s != d


def test_uniform_hypercube_reaches_consensus():
    _run("""
        params = {"w": put(jnp.arange(R, dtype=jnp.float32)[:, None] *
                           jnp.ones((1, 4)), P("data", None))}
        default = jax.tree.map(jnp.zeros_like, params)
        specs = {"w": P("data", None)}
        cfg = GossipConfig(axis_names=("data",), matching="hypercube",
                           merge_policy="uniform")
        fn, _ = build_gossip_round(mesh, specs, cfg)
        st = jax.tree.map(lambda x: put(x, P("data")), init_gossip_state(R))
        with jax.set_mesh(mesh):
            for r in range(3):   # log2(8) rounds -> exact consensus
                params, st = fn(params, st, default, r)
        w = np.asarray(params["w"])
        assert np.allclose(w, w[0], atol=1e-5), w[:,0]
        assert abs(w[0,0] - (R-1)/2) < 1e-5   # preserved mean
        print("consensus OK")
    """)


def test_gossip_preserves_mean_and_reduces_variance():
    _run("""
        key = jax.random.PRNGKey(0)
        params = {"w": put(jax.random.normal(key, (R, 16)), P("data", None))}
        default = jax.tree.map(jnp.zeros_like, params)
        specs = {"w": P("data", None)}
        cfg = GossipConfig(axis_names=("data",), matching="random",
                           merge_policy="uniform", n_random_matchings=8, seed=1)
        fn, _ = build_gossip_round(mesh, specs, cfg)
        st = jax.tree.map(lambda x: put(x, P("data")), init_gossip_state(R))
        w0 = np.asarray(params["w"])
        with jax.set_mesh(mesh):
            for r in range(6):
                params, st = fn(params, st, default, r)
        w = np.asarray(params["w"])
        np.testing.assert_allclose(w.mean(0), w0.mean(0), atol=1e-5)
        assert w.std(0).mean() < 0.25 * w0.std(0).mean()
        print("mean/variance OK")
    """)


def test_busy_and_failure_gates_block_merging():
    _run("""
        params = {"w": put(jnp.arange(R, dtype=jnp.float32)[:, None] *
                           jnp.ones((1, 4)), P("data", None))}
        default = jax.tree.map(jnp.zeros_like, params)
        specs = {"w": P("data", None)}
        # success_prob 0 -> no exchange ever happens
        cfg = GossipConfig(axis_names=("data",), matching="hypercube",
                           merge_policy="uniform", success_prob=0.0)
        fn, _ = build_gossip_round(mesh, specs, cfg)
        st = jax.tree.map(lambda x: put(x, P("data")), init_gossip_state(R))
        w0 = np.asarray(params["w"])
        with jax.set_mesh(mesh):
            for r in range(4):
                params, st = fn(params, st, default, r)
        np.testing.assert_allclose(np.asarray(params["w"]), w0)
        print("gating OK")
    """)


def test_churn_resets_to_default():
    _run("""
        params = {"w": put(jnp.ones((R, 4)) * 7.0, P("data", None))}
        default = {"w": put(jnp.zeros((R, 4)), P("data", None))}
        specs = {"w": P("data", None)}
        cfg = GossipConfig(axis_names=("data",), matching="hypercube",
                           merge_policy="uniform", success_prob=0.0,
                           churn_prob=1.0)   # every replica churns
        fn, _ = build_gossip_round(mesh, specs, cfg)
        st = jax.tree.map(lambda x: put(x, P("data")), init_gossip_state(R))
        with jax.set_mesh(mesh):
            params, st = fn(params, st, default, 0)
        assert np.allclose(np.asarray(params["w"]), 0.0)
        assert np.allclose(np.asarray(st["count"]), 0.0)
        print("churn OK")
    """)


def test_segmented_gossip_touches_only_one_segment():
    _run("""
        params = {"w": put(jnp.arange(R, dtype=jnp.float32)[:, None] *
                           jnp.ones((1, 12)), P("data", None))}
        default = jax.tree.map(jnp.zeros_like, params)
        specs = {"w": P("data", None)}
        cfg = GossipConfig(axis_names=("data",), matching="hypercube",
                           merge_policy="uniform", segments=3)
        fn, _ = build_gossip_round(mesh, specs, cfg)
        st = jax.tree.map(lambda x: put(x, P("data")), init_gossip_state(R))
        w0 = np.asarray(params["w"])
        with jax.set_mesh(mesh):
            params, st = fn(params, st, default, 0)  # round 0 -> segment 0
        w = np.asarray(params["w"])
        # per-replica leaf is 12 long -> segment = 4 elements
        assert not np.allclose(w[:, :4], w0[:, :4])   # merged
        np.testing.assert_allclose(w[:, 4:], w0[:, 4:])  # untouched
        print("segments OK")
    """)


def test_gossip_training_beats_no_communication():
    """Integration: gossip training on a shared quadratic converges to the
    global optimum; isolated training does not (paper's core claim that
    model exchange incorporates remote observations)."""
    _run("""
        # each replica sees a quadratic centred at c_r; global optimum = mean(c)
        key = jax.random.PRNGKey(0)
        centers = put(jax.random.normal(key, (R, 8)) * 3.0, P("data", None))
        params = {"w": put(jnp.zeros((R, 8)), P("data", None))}
        default = jax.tree.map(jnp.zeros_like, params)
        specs = {"w": P("data", None)}
        cfg = GossipConfig(axis_names=("data",), matching="random",
                           merge_policy="uniform", n_random_matchings=8, seed=2)
        fn, _ = build_gossip_round(mesh, specs, cfg)
        st = jax.tree.map(lambda x: put(x, P("data")), init_gossip_state(R))

        @jax.jit
        def local_step(w, c):
            g = jax.vmap(jax.grad(lambda wi, ci: jnp.sum((wi - ci) ** 2)))(w, c)
            return w - 0.2 * g

        w_iso = params["w"]
        with jax.set_mesh(mesh):
            for r in range(30):
                params = {"w": local_step(params["w"], centers)}
                w_iso = local_step(w_iso, centers)
                params, st = fn(params, st, default, r)
        gopt = np.asarray(centers).mean(0)
        err_gossip = np.abs(np.asarray(params["w"]) - gopt).mean()
        err_iso = np.abs(np.asarray(w_iso) - gopt).mean()
        print("gossip err", err_gossip, "isolated err", err_iso)
        assert err_gossip < 0.5 * err_iso
    """)


def test_odd_matchings_are_involutions_with_self_pair():
    """Regression: odd-R random matchings used to point the leftover node
    at node 0 (a non-involution — node 0 disagreed about its partner).
    Now the leftover self-pairs, which the round treats as 'no contact'."""
    from repro.core.gossip import random_matchings
    for m in random_matchings(9, 6, 3):
        perm = {s: d for s, d in m}
        assert len(perm) == 9
        self_paired = [s for s, d in m if s == d]
        assert len(self_paired) == 1          # exactly one leftover
        for s, d in m:
            assert perm[d] == s, "pairing must be symmetric"


def test_zero_count_obs_merge_is_symmetric_average():
    """Regression (obs_count zero-count fallback): two never-trained
    replicas must merge 0.5/0.5 — the old w_own = 0/1 = 0 replaced the
    receiving replica with its peer's wholesale."""
    _run("""
        params = {"w": put(jnp.arange(R, dtype=jnp.float32)[:, None] *
                           jnp.ones((1, 4)), P("data", None))}
        default = jax.tree.map(jnp.zeros_like, params)
        specs = {"w": P("data", None)}
        cfg = GossipConfig(axis_names=("data",), matching="hypercube",
                           merge_policy="obs_count")
        fn, _ = build_gossip_round(mesh, specs, cfg)
        # all counts zero: the obs_count weights must fall back to 0.5
        st = jax.tree.map(lambda x: put(x, P("data")), init_gossip_state(R))
        w0 = np.asarray(params["w"])
        with jax.set_mesh(mesh):
            params, st = fn(params, st, default, 0)
        w = np.asarray(params["w"])
        # round 0 of the hypercube pairs i <-> i^1: exact 0.5/0.5 average
        pair = w0[np.arange(R) ^ 1]
        np.testing.assert_allclose(w, 0.5 * w0 + 0.5 * pair, atol=1e-6)
        print("zero-count merge OK")
    """)
