"""``compute.take_nodes``: the engine step's per-node table reads.

Above ``TAKE_SELECT_MAX`` rows a read is the indexed gather; up to it a
one-hot select for rows narrower than ``TAKE_MXU_MIN_WORDS`` words, and a
one-hot MXU product over byte planes for wider rows. Pinned here: both
one-hot reads return bitwise what ``table[idx]`` returns for every dtype
and index the engine passes (negative indices, clipped partners, NaN
payloads / ``-0.0`` / ``±inf`` / denormal floats), the path follows the
table's rows and words per row, ``engine.take_reads`` lists the widths of
every read of the step, the cells backend reads its per-node tables by
gather, and the engine's outputs do not depend on which path ran.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fg_learn import logreg_task
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, compute, engine
from repro.sim.faults import FaultClass, FaultConfig

LENGTHS = (1, 64, 200, 1024, 1025)
N_READERS, N_RUNS = 96, 3
#: free riders, link failures, setup aborts and signflip attackers: every
#: fault-layer read and the learning path's ``snap_poison`` read
MIXED_FAULTS = FaultConfig(
    classes=(FaultClass(frac=0.7, name="on"),
             FaultClass(frac=0.15, free_rider=True, name="fr"),
             FaultClass(frac=0.15, adv_mode="signflip", name="flip")),
    link_fail_rate=0.05, p_abort=0.1)


#: the widest special floats: NaN payloads (quiet and signalling, both
#: signs), ``±0.0``, ``±inf``, the smallest and largest denormals
SPECIAL_BITS = np.array([0x7FC00000, 0xFFC00001, 0x7F800001, 0xFFBFFFFF,
                         0x80000000, 0x00000000, 0x7F800000, 0xFF800000,
                         0x00000001, 0x807FFFFF], np.uint32)
#: row shapes of the wide kinds: at least ``TAKE_MXU_MIN_WORDS`` words
WIDE = {"wide_u32": (76,), "wide_f32": (25,), "wide_s32": (2, 3),
        "wide_bool": (25,), "obs_birth": (25,)}


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
    if kind == "rows":
        # (N, W) rows of float bits, NaN payloads and -0.0 among them
        t = rng.integers(0, 2**32, (n, 3), dtype=np.uint32)
        t[0, 0] = 0x80000000
        return t.view(np.float32)
    shape = (n,) + WIDE[kind]
    if kind == "wide_u32":
        return rng.integers(0, 2**32, shape, dtype=np.uint32)
    if kind == "wide_s32":
        return rng.integers(-2**31, 2**31, shape, dtype=np.int32)
    if kind == "wide_bool":
        return rng.random(shape) < 0.5
    if kind == "wide_f32":
        t = rng.integers(0, 2**32, shape, dtype=np.uint32)
        t.flat[rng.permutation(t.size)[:SPECIAL_BITS.size]] = \
            SPECIAL_BITS[: min(t.size, SPECIAL_BITS.size)]
        return t.view(np.float32)
    # obs_birth.T: a ring slot's birth time per model, -inf where empty
    t = rng.uniform(0.0, 2000.0, shape).astype(np.float32)
    t[rng.random(shape) < 0.4] = -np.inf
    return t


def _indices(n: int, rng) -> np.ndarray:
    """Raw partners in [-1, n) (``mutualize``'s sentinel included), the
    same clipped to [0, n) (``pidx``), and indices over the whole
    [-n, n) that the wrap covers, one row per run."""
    raw = rng.integers(-1, n, (N_RUNS, N_READERS), dtype=np.int32)
    raw[:, 0] = -1
    raw[:, 1] = n - 1
    wrap = rng.integers(-n, n, (N_RUNS, N_READERS), dtype=np.int32)
    wrap[:, 0] = -n
    return np.concatenate([raw, np.clip(raw, 0, n - 1), wrap])


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _words(tables) -> int:
    return sum(int(np.prod(t.shape[1:])) for t in tables)


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kind", ["f32", "u32", "s32", "bool", "rows",
                                  *WIDE])
def test_take_nodes_is_bitwise_table_index_under_vmap(kind, n):
    rng = np.random.default_rng([n, len(kind)])
    idx = jnp.asarray(_indices(n, rng))
    table = _table(kind, n, rng)
    tables = jnp.asarray(np.stack(
        [table, rng.permutation(table), rng.permutation(table)] * 3))
    if kind in WIDE and n <= compute.TAKE_SELECT_MAX:
        assert compute.take_path(n, _words((table,))) == "mxu"

    def want(t, i):
        return t[i]

    shared = jax.vmap(compute.take_nodes, in_axes=(None, 0))
    np.testing.assert_array_equal(
        _bits(shared(jnp.asarray(table), idx)),
        _bits(jax.vmap(want, in_axes=(None, 0))(jnp.asarray(table), idx)))
    per_run = jax.vmap(compute.take_nodes)
    np.testing.assert_array_equal(_bits(per_run(tables, idx)),
                                  _bits(jax.vmap(want)(tables, idx)))


#: tuples read through one select or product: narrow rows (3 words),
#: mixed kinds (6 words), and the M = 25 step's ``(order_seed, snap_has,
#: snap)`` (1 + 25 + 50 words)
TUPLES = {"narrow": ("f32", "bool", "s32"),
          "mixed": ("f32", "bool", "rows", "s32"),
          "deliveries": ("u32", "wide_bool", "snap")}


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("kinds", list(TUPLES))
def test_take_nodes_reads_a_tuple_of_tables_in_one_select(kinds, n):
    rng = np.random.default_rng([n, 7])
    idx = jnp.asarray(_indices(n, rng))
    tables = tuple(
        jnp.asarray(rng.integers(0, 2**32, (n, 25, 2), dtype=np.uint32)
                    if k == "snap" else _table(k, n, rng))
        for k in TUPLES[kinds])
    words = _words(tables)
    assert words == {"narrow": 3, "mixed": 6, "deliveries": 76}[kinds]
    got = jax.vmap(compute.take_nodes, in_axes=(None, 0))(tables, idx)
    want = jax.vmap(lambda ts, i: tuple(t[i] for t in ts),
                    in_axes=(None, 0))(tables, idx)
    assert len(got) == len(tables)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("words", [1, 2, 3, 4, 25, 76])
def test_take_path_follows_the_table_length(words, n):
    """The path follows the rows and the words per row: the gather above
    1024 rows, else the select up to 3 words (every read of the M = 1
    step) and the MXU product from ``TAKE_MXU_MIN_WORDS`` words."""
    assert compute.TAKE_MXU_MIN_WORDS >= 4
    table = jnp.zeros((n,) if words == 1 else (n, words), jnp.float32)
    idx = jnp.zeros((N_READERS,), jnp.int32)
    jaxpr = jax.make_jaxpr(compute.take_nodes)(table, idx)
    gather = jax.make_jaxpr(lambda t, i: t[i])(table, idx)
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    path = compute.take_path(n, words)
    if n > 1024:
        assert path == "index"
        assert str(jaxpr) == str(gather)
        return
    if words <= 3:
        assert path == "onehot"
    if words >= compute.TAKE_MXU_MIN_WORDS:
        assert path == "mxu"
        assert "gather" not in prims and "dot_general" in prims
    else:
        assert path == "onehot"
        assert "gather" not in prims and "reduce_or" in prims
        assert "dot_general" not in prims


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


#: configurations whose step reads differ: M = 1 and M = 25, learning
#: on, learning with faults and signflip attackers, the cells backend
READ_CASES = {
    "m1": (dict(), 1),
    "m25": (dict(), 25),
    "m25_tpu": (dict(), 25),
    "learn": (dict(learn=logreg_task()), 1),
    "learn_faults": (dict(learn=logreg_task(), faults=MIXED_FAULTS), 1),
    "cells": (dict(n_nodes=2048, area_side=640.0, rz_radius=320.0,
                   contact_backend="cells"), 1),
}


@pytest.mark.parametrize("case", list(READ_CASES))
def test_take_reads_lists_every_read_of_the_step(case, monkeypatch):
    """``engine.take_reads`` gives the ``(rows, words)`` of every read the
    traced slot step makes, so the ``fg.sweep`` span's ``take`` attr names
    the paths the program takes."""
    kw, M = READ_CASES[case]
    traced = []
    real = compute.take_path

    def spy(rows, words):
        traced.append((rows, words))
        return real(rows, words)

    monkeypatch.setattr(compute, "take_path", spy)
    if case.endswith("_tpu"):
        # the TPU branch: the fused contact kernel, whose step reads the
        # partner's position and zone word instead of a matrix bit
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = SimConfig(n_slots=16, sample_every=8, **kw)
    p = engine.dynamic_params(paper_params(lam=0.05, M=M))
    jax.eval_shape(lambda k: engine._run(k, p, cfg, M),
                   jax.random.PRNGKey(0))
    reads = set(traced)
    assert reads == set(engine.take_reads(cfg, M))
    want = {"m25": "onehot+mxu", "m25_tpu": "onehot+mxu",
            "cells": "index+onehot"}.get(case, "onehot")
    assert compute.take_paths(reads) == want


@pytest.mark.parametrize("case", ["learn", "faults", "models"])
def test_engine_outputs_match_the_gather_path(case, monkeypatch):
    """N = 200 with learning on (obs_count merges), with free riders,
    link failures, setup aborts and signflip attackers, and at M = 25
    (whose widest reads take the MXU product): the one-hot paths and the
    forced gather path give bit-identical outputs."""
    kw, pkw = dict(learn=logreg_task()), dict(lam=0.5, Lam=10.0, M=1)
    if case == "faults":
        kw["faults"] = MIXED_FAULTS
    if case == "models":
        # Fig. 4's M = W = 25 with Fig. 1's fast timers
        kw, pkw = {}, dict(lam=2.0, M=25, T_T=0.5, T_M=0.25)
    M = pkw["M"]
    cfg = SimConfig(n_nodes=200, n_slots=64, sample_every=8, **kw)
    p = engine.dynamic_params(paper_params(**pkw))
    key = jax.random.PRNGKey(3)
    products = []
    real = compute._take_mxu

    def spy(tables, idx):
        products.append(_words(tables))
        return real(tables, idx)

    monkeypatch.setattr(compute, "_take_mxu", spy)

    def run():
        # a fresh function each time: the jit cache must not hand the
        # first path's program to the second
        return jax.jit(lambda k: engine._run(k, p, cfg, M))(key)

    select = run()
    assert sorted(products) == ([25, 76] if M == 25 else [])
    monkeypatch.setattr(compute, "TAKE_SELECT_MAX", 0)
    assert compute.take_path(200, 76) == "index"
    gather = run()
    assert select.keys() == gather.keys()
    for k in select:
        np.testing.assert_array_equal(_bits(select[k]), _bits(gather[k]),
                                      err_msg=k)
    if M == 1:
        assert float(np.max(select["learn_obs"])) > 0.0
    else:
        assert float(np.max(select["availability"])) > 0.0
