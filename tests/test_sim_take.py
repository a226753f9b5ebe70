"""``compute.take_nodes``: the engine step's per-node table reads.

Up to ``TAKE_SELECT_MAX`` rows a read is a one-hot select, above it the
indexed gather. Pinned here: the select returns bitwise what
``table[idx]`` returns for every dtype and index the engine passes
(negative sentinels, clipped partners, NaN / ``-0.0`` / ``-inf`` floats),
the path follows the table length alone, the cells backend reads its
per-node tables by gather, and the engine's outputs do not depend on
which path ran.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fg_learn import logreg_task
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, compute, engine
from repro.sim.faults import FaultClass, FaultConfig

LENGTHS = (1, 200, 1024, 1025)
N_READERS, N_RUNS = 96, 3
#: free riders, link failures, setup aborts and signflip attackers: every
#: fault-layer read and the learning path's ``snap_poison`` read
MIXED_FAULTS = FaultConfig(
    classes=(FaultClass(frac=0.7, name="on"),
             FaultClass(frac=0.15, free_rider=True, name="fr"),
             FaultClass(frac=0.15, adv_mode="signflip", name="flip")),
    link_fail_rate=0.05, p_abort=0.1)


def _table(kind: str, n: int, rng) -> np.ndarray:
    if kind == "f32":
        t = rng.standard_normal(n).astype(np.float32)
        special = np.array([np.nan, -0.0, -np.inf, np.inf, 0.0], np.float32)
        t[: min(n, special.size)] = special[: min(n, special.size)]
        return rng.permutation(t)
    if kind == "u32":
        return rng.integers(0, 2**32, n, dtype=np.uint32)
    if kind == "s32":
        return rng.integers(-2**31, 2**31, n, dtype=np.int32)
    if kind == "bool":
        return rng.random(n) < 0.5
    # (N, W) rows of float bits, NaN payloads and -0.0 among them
    t = rng.integers(0, 2**32, (n, 3), dtype=np.uint32)
    t[0, 0] = 0x80000000
    return t.view(np.float32)


def _indices(n: int, rng) -> np.ndarray:
    """Raw partners in [-1, n) (``mutualize``'s sentinel included) and
    the same clipped to [0, n) (``pidx``), one row per run."""
    raw = rng.integers(-1, n, (N_RUNS, N_READERS), dtype=np.int32)
    raw[:, 0] = -1
    raw[:, 1] = n - 1
    return np.concatenate([raw, np.clip(raw, 0, n - 1)])


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kind", ["f32", "u32", "s32", "bool", "rows"])
def test_take_nodes_is_bitwise_table_index_under_vmap(kind, n):
    rng = np.random.default_rng([n, len(kind)])
    idx = jnp.asarray(_indices(n, rng))
    table = _table(kind, n, rng)
    tables = jnp.asarray(np.stack([table, rng.permutation(table)] * 3))

    def want(t, i):
        return t[i]

    shared = jax.vmap(compute.take_nodes, in_axes=(None, 0))
    np.testing.assert_array_equal(
        _bits(shared(jnp.asarray(table), idx)),
        _bits(jax.vmap(want, in_axes=(None, 0))(jnp.asarray(table), idx)))
    per_run = jax.vmap(compute.take_nodes)
    np.testing.assert_array_equal(_bits(per_run(tables, idx)),
                                  _bits(jax.vmap(want)(tables, idx)))


@pytest.mark.parametrize("n", LENGTHS)
def test_take_nodes_reads_a_tuple_of_tables_in_one_select(n):
    rng = np.random.default_rng([n, 7])
    idx = jnp.asarray(_indices(n, rng))
    tables = tuple(jnp.asarray(_table(k, n, rng))
                   for k in ("f32", "bool", "rows", "s32"))
    got = jax.vmap(compute.take_nodes, in_axes=(None, 0))(tables, idx)
    want = jax.vmap(lambda ts, i: tuple(t[i] for t in ts),
                    in_axes=(None, 0))(tables, idx)
    assert len(got) == len(tables)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("n", LENGTHS)
def test_take_path_follows_the_table_length(n):
    table = jnp.zeros((n,), jnp.float32)
    idx = jnp.zeros((N_READERS,), jnp.int32)
    jaxpr = jax.make_jaxpr(compute.take_nodes)(table, idx)
    gather = jax.make_jaxpr(lambda t, i: t[i])(table, idx)
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    if n <= compute.TAKE_SELECT_MAX:
        assert compute.take_path(n) == "onehot"
        assert "gather" not in prims and "reduce_or" in prims
    else:
        assert compute.take_path(n) == "index"
        assert str(jaxpr) == str(gather)
    assert (n <= 1024) == (compute.take_path(n) == "onehot")


def test_cells_backend_reads_its_node_tables_by_gather(monkeypatch):
    """At city scale every per-node read, fault and learning sites
    included, traces the indexed gather, so the cells backend stays O(N)
    per read; only the observation ring (``k_obs`` rows) is selected."""
    selected = []
    real = compute._take_select

    def spy(tables, idx):
        selected.extend(t.shape for t in tables)
        return real(tables, idx)

    monkeypatch.setattr(compute, "_take_select", spy)
    cfg = SimConfig(n_nodes=2048, area_side=640.0, rz_radius=320.0,
                    n_slots=16, sample_every=8, contact_backend="cells",
                    learn=logreg_task(), faults=MIXED_FAULTS)
    p = engine.dynamic_params(paper_params(lam=0.05, M=1))
    jax.eval_shape(lambda k: engine._run(k, p, cfg, 1),
                   jax.random.PRNGKey(0))
    assert selected and {s[0] for s in selected} == {cfg.k_obs}


@pytest.mark.parametrize("case", ["learn", "faults"])
def test_engine_outputs_match_the_gather_path(case, monkeypatch):
    """N = 200 with learning on (obs_count merges), and with free riders,
    link failures, setup aborts and signflip attackers: the select path
    and the forced gather path give bit-identical outputs."""
    kw = dict(learn=logreg_task())
    if case == "faults":
        kw["faults"] = MIXED_FAULTS
    cfg = SimConfig(n_nodes=200, n_slots=64, sample_every=8, **kw)
    p = engine.dynamic_params(paper_params(lam=0.5, Lam=10.0, M=1))
    key = jax.random.PRNGKey(3)

    def run():
        # a fresh function each time: the jit cache must not hand the
        # first path's program to the second
        return jax.jit(lambda k: engine._run(k, p, cfg, 1))(key)

    select = run()
    monkeypatch.setattr(compute, "TAKE_SELECT_MAX", 0)
    assert compute.take_path(200) == "index"
    gather = run()
    assert select.keys() == gather.keys()
    for k in select:
        np.testing.assert_array_equal(_bits(select[k]), _bits(gather[k]),
                                      err_msg=k)
    assert float(np.max(select["learn_obs"])) > 0.0
