"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test compiles one kernel for a chip that is described
(``v5e:2x2``) and not attached, which is where the TPU lowering refuses
block shapes, layouts and reductions that interpret mode accepts. The
topology is described inside a fixture — never while a module is
imported — and the persistent compilation cache is off around the
compiles (an entry compiled for a described chip cannot be read back).
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gossip_merge as gm
from repro.kernels.contacts import cell_close_words, pairwise_contacts


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles_to_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [200, 4096])
def test_pairwise_contacts_compiles(one_chip, n):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compiles_to_kernel(
        lambda pos, rz, el, pw: pairwise_contacts(pos, rz, el, pw, 25.0),
        s((n, 2), jnp.float32), s((n,), jnp.bool_), s((n,), jnp.bool_),
        s((n, (n + 31) // 32), jnp.uint32),
    )


def test_cell_close_words_compiles_on_a_city_grid_slice(one_chip):
    # 64 grid rows of the N = 32768 paper-density grid (511 x 511 cells of
    # 5 m, 9 node slots each)
    ncx, ncy, cap = 64, 511, 9
    shape = ((ncx + 2) * (ncy + 2), cap)

    def s(dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compiles_to_kernel(
        lambda x, y, z, i: cell_close_words(x, y, z, i, ncx, ncy, 25.0),
        s(jnp.float32), s(jnp.float32), s(jnp.uint32), s(jnp.int32),
    )


@pytest.mark.parametrize("scaled", [False, True])
def test_gossip_merge_rows_compile(one_chip, scaled):
    from repro.configs.fg_learn import logreg_task

    n, d = 200, logreg_task().param_dim   # the paper's N, the logreg payload

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, vec = s((n, d)), s((n,))
    if scaled:
        _compiles_to_kernel(
            lambda a, b, w, c, ok: gm._rows_scaled_pallas(
                a, b, w, c, ok, interpret=False),
            rows, rows, vec, vec, s((n,), jnp.bool_))
    else:
        _compiles_to_kernel(
            lambda a, b, w, ok: gm._rows_pallas(a, b, w, ok,
                                                interpret=False),
            rows, rows, vec, s((n,), jnp.bool_))


def test_sharded_sweep_program_compiles_for_four_chips(topo, monkeypatch):
    """The sweep's chunk program with the dense contact kernel inside,
    sharded over four chips. The compiler cannot partition a Mosaic
    kernel, so this compiles only because the program runs under
    ``shard_map``; the engine is steered onto its TPU branch and the sweep
    mesh onto the described chips."""
    from repro.configs.fg_paper import paper_params
    from repro.sim import SimConfig, sweep

    make_mesh = jax.make_mesh

    def described_mesh(shape, names, axis_types=None):
        return make_mesh(shape, names, axis_types=axis_types,
                         devices=topo.devices[:math.prod(shape)])

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "make_mesh", described_mesh)
    ps = [paper_params(lam=lam, M=1) for lam in (0.05, 0.1, 0.2, 0.3)]
    setup = sweep._prepare(ps, SimConfig(n_slots=16), (0, 1), "mean", None,
                           None, (), None, 4)
    keys = jax.ShapeDtypeStruct((2, 2), jnp.uint32)
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in setup.chunk_params(0).items()}
    try:
        text = setup.worker().lower(keys, params).compile().as_text()
    finally:
        sweep._chunk_worker.cache_clear()  # drop the described-chip mesh
    assert setup.plan.mesh_shape == (4, 1)
    assert "tpu_custom_call" in text


def test_city_grid_slice_is_the_real_grid():
    """The grid slice above is cut from the grid ``make_grid`` builds for
    the N = 32768 paper-density deployment."""
    from repro.configs.fg_paper import DENSITY
    from repro.sim import SimConfig
    from repro.sim.cells import make_grid

    side = math.sqrt(32768 / DENSITY)
    grid = make_grid(SimConfig(n_nodes=32768, area_side=side))
    assert (grid.ncy, grid.cap_cell) == (511, 9)
