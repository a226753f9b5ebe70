"""Engine-level guarantees of the modular simulator (``repro.sim``):

1. the engine reproduces the plain reference of the benchmark
   (``bench/reference.py``: boolean masks, no kernels) *bit for bit* at
   the sample points (same PRNG schedule, same arithmetic);
2. ``simulate_batch`` agrees with the single-run path pointwise, so a
   batched sweep is a drop-in replacement for a serial loop;
3. the non-RDM mobility models drive the full protocol end to end.
"""

import jax
import numpy as np
import pytest

from bench import reference
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, simulate, simulate_batch
from repro.sim.engine import _run_single, dynamic_params

CFG = SimConfig(n_nodes=60, n_slots=300, sample_every=4)
TRACES = ("availability", "busy_frac", "stored", "obs_birth",
          "obs_holders", "model_holders", "n_in_rz")


@pytest.mark.parametrize("cfg, p, seed", [
    (CFG, paper_params(lam=0.2, M=3, Lam=2), 7),
    # K not a multiple of 32 (live pad bits in the last mask word) and
    # several models: the packed word algebra must not leak into or read
    # from the pad region
    (SimConfig(n_nodes=40, n_slots=240, sample_every=4, k_obs=40),
     paper_params(lam=0.3, M=2, Lam=2), 11),
], ids=["three_models", "pad_bits"])
def test_engine_matches_the_reference_bitwise(cfg, p, seed):
    shape = reference.Shape(
        n_nodes=cfg.n_nodes, area_side=cfg.area_side,
        rz_radius=cfg.rz_radius, r_tx=cfg.r_tx, speed=cfg.speed,
        dir_change_rate=cfg.dir_change_rate, dt=cfg.dt,
        n_slots=cfg.n_slots, k_obs=cfg.k_obs, q_train=cfg.q_train,
        q_merge=cfg.q_merge, M=int(p.M), Lam=int(p.Lam))
    p_ref = {k: np.float32(getattr(p, k))[None]
             for k in ("t0", "T_T", "T_M", "tau_l", "lam")}
    p_ref["T_L"] = np.float32(2.0 * p.L / p.C)[None]
    key = jax.random.PRNGKey(seed)
    want = reference.run_many(key[None], p_ref, shape)
    new = _run_single(key, dynamic_params(p), cfg, int(p.M))
    # the reference emits every slot; the engine emits at the sample
    # points (slot s-1, 2s-1, ...): the values there must agree bit for bit
    pts = reference.sample_points(cfg.n_slots, cfg.sample_every)
    for k in TRACES:
        np.testing.assert_array_equal(
            np.asarray(want[k][0])[pts], np.asarray(new[k]), err_msg=k)


def test_batch_matches_single_runs():
    ps = [paper_params(lam=0.1, M=1), paper_params(lam=0.3, M=1, T_T=0.5)]
    seeds = [0, 3]
    batch = simulate_batch(ps, CFG, seeds=seeds)
    assert batch.availability.shape[:2] == (len(ps), len(seeds))
    for i, p in enumerate(ps):
        for j, seed in enumerate(seeds):
            single = simulate(p, CFG, seed=seed)
            point = batch.point(i, j)
            np.testing.assert_allclose(
                point.availability, single.availability, atol=1e-6
            )
            np.testing.assert_allclose(
                point.stored_info, single.stored_info, atol=1e-5
            )
            np.testing.assert_array_equal(point.n_in_rz, single.n_in_rz)


def test_batch_rejects_mixed_model_counts():
    with pytest.raises(ValueError, match="one model count"):
        simulate_batch(
            [paper_params(M=1), paper_params(M=2)], CFG, seeds=[0]
        )


def test_w_below_m_rejected():
    with pytest.raises(NotImplementedError):
        simulate(paper_params(M=4, W=2), CFG)


@pytest.mark.parametrize("mobility", ["rwp", "manhattan"])
def test_alternative_mobility_runs_protocol(mobility):
    cfg = SimConfig(n_nodes=60, n_slots=400, sample_every=8, mobility=mobility)
    out = simulate(paper_params(lam=0.2, M=1), cfg, seed=1)
    assert np.all(out.availability >= 0) and np.all(out.availability <= 1)
    assert np.all(out.n_in_rz > 0)
    # the protocol actually ran: someone trained/merged a model by the end
    assert out.model_holders[-len(out.t) // 3:].sum() > 0


def test_sharded_batch_matches_single_device():
    """simulate_batch sharded across 2 forced CPU devices — with a scenario
    count that needs padding (3 % 2 != 0) — equals the single-device run
    bitwise. Runs in a subprocess because the device count is fixed at jax
    init."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        import numpy as np
        from repro.configs.fg_paper import paper_params
        from repro.sim import SimConfig, simulate_batch
        from repro.sim.engine import _run_batch, _check_params, \\
            stack_dynamic_params
        import jax.numpy as jnp

        assert len(jax.devices()) == 2
        cfg = SimConfig(n_nodes=40, n_slots=160, sample_every=8)
        ps = [paper_params(lam=l, M=1) for l in (0.1, 0.2, 0.3)]  # pads to 4
        batch = simulate_batch(ps, cfg, seeds=[0, 1])             # sharded
        keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray([0, 1], jnp.uint32))
        single = _run_batch(keys, stack_dynamic_params(ps), cfg,
                            _check_params(ps))                    # one device
        np.testing.assert_array_equal(
            batch.availability, np.asarray(single["availability"]))
        np.testing.assert_array_equal(
            batch.stored_info, np.asarray(single["stored"]))
        print("SHARDED-OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    ) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "SHARDED-OK" in out.stdout, out.stdout + out.stderr


def test_lambda_is_sweepable_in_one_batch():
    """Λ is traced (rank-threshold observer selection): one compiled sweep
    can vary it, and more simultaneous observers store more information."""
    ps = [paper_params(lam=0.3, M=1, Lam=1, W=4),
          paper_params(lam=0.3, M=1, Lam=4, W=4)]
    cfg = SimConfig(n_nodes=80, n_slots=1200, sample_every=8)
    batch = simulate_batch(ps, cfg, seeds=[0, 1])
    s0 = batch.stored_info.shape[-1] // 2
    low = batch.stored_info[0, :, s0:].mean()
    high = batch.stored_info[1, :, s0:].mean()
    assert high > low


@pytest.mark.parametrize("dense", [True, False], ids=["compare", "sort"])
def test_observer_ranks_break_score_ties_by_node_id(monkeypatch, dense):
    """Both rank forms give each node its position in the (score, id)
    order, so under f32 score ties ``rank < Λ`` picks exactly the first Λ
    of a stable argsort (the reference's and the legacy step's rule)."""
    import jax.numpy as jnp

    from repro.sim import observations

    if not dense:
        monkeypatch.setattr(observations, "RANK_DENSE_MAX_N", 0)
    scores = jnp.asarray([[0.5, 0.25, 0.25, 1e3, 0.25, 0.125, 1e3],
                          [0.75, 0.75, 0.75, 0.75, 0.5, 0.5, 0.0]],
                         jnp.float32)
    rank = np.asarray(observations._observer_ranks(scores))
    want = np.argsort(np.argsort(np.asarray(scores), axis=-1,
                                 kind="stable"), axis=-1, kind="stable")
    np.testing.assert_array_equal(rank, want)
    for lam in range(1, 8):
        assert ((rank < lam).sum(axis=-1) == lam).all()
