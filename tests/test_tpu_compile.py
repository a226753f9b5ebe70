"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test compiles one kernel for a chip that is described
(``v5e:2x2``) and not attached, which is where the TPU lowering refuses
block shapes, layouts and reductions that interpret mode accepts. The
topology is described inside a fixture — never while a module is
imported — and the persistent compilation cache is off around the
compiles (an entry compiled for a described chip cannot be read back).
"""

import ast
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gossip_merge as gm
from repro.kernels.contacts import cell_close_words, pairwise_contacts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?%([A-Za-z0-9_.\-]+?)(?:\.\d+)? = .*custom-call\(.*"
    r'custom_call_target="tpu_custom_call"')


def _reader_names(metric: str) -> tuple:
    """The kernel names the benchmark's reader ``metric`` accepts (its
    ``NAMES``), read from the reader's file."""
    with open(os.path.join(ROOT, "bench", "layers", metric + ".py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and node.targets[0].id == "NAMES":
            return ast.literal_eval(node.value)
    raise LookupError(f"{metric} has no NAMES")


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


def _kernel_names(text: str) -> set:
    """Base names of the compiled program's kernel instructions: what a
    device trace names the kernel's events after."""
    return {m.group(1) for m in map(_CUSTOM_CALL.match, text.splitlines())
            if m}


def _compiles_to_kernel(fn, *shapes) -> set:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return _kernel_names(text)


@pytest.mark.parametrize("n", [200, 4096])
def test_pairwise_contacts_compiles(one_chip, n):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    names = _compiles_to_kernel(
        lambda pos, rz, el, pw: pairwise_contacts(pos, rz, el, pw, 25.0),
        s((n, 2), jnp.float32), s((n,), jnp.bool_), s((n,), jnp.bool_),
        s((n, (n + 31) // 32), jnp.uint32),
    )
    assert names == {"pairwise_contacts"}
    for metric in ("pairwise_contacts_roofline", "pairwise_contacts_share"):
        assert names <= set(_reader_names(metric)), metric


def test_cell_close_words_compiles_on_a_city_grid_slice(one_chip):
    # 64 grid rows of the N = 32768 paper-density grid (511 x 511 cells of
    # 5 m, 9 node slots each)
    ncx, ncy, cap = 64, 511, 9
    shape = ((ncx + 2) * (ncy + 2), cap)

    def s(dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    names = _compiles_to_kernel(
        lambda x, y, z, i: cell_close_words(x, y, z, i, ncx, ncy, 25.0),
        s(jnp.float32), s(jnp.float32), s(jnp.uint32), s(jnp.int32),
    )
    assert names == {"cell_close_words"}


@pytest.mark.parametrize("scaled", [False, True])
def test_gossip_merge_rows_compile(one_chip, scaled):
    from repro.configs.fg_learn import logreg_task

    n, d = 200, logreg_task().param_dim   # the paper's N, the logreg payload

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, vec = s((n, d)), s((n,))
    if scaled:
        names = _compiles_to_kernel(
            lambda a, b, w, c, ok: gm._rows_scaled_pallas(
                a, b, w, c, ok, interpret=False),
            rows, rows, vec, vec, s((n,), jnp.bool_))
        assert names == {"gossip_merge_rows_scaled"}
    else:
        names = _compiles_to_kernel(
            lambda a, b, w, ok: gm._rows_pallas(a, b, w, ok,
                                                interpret=False),
            rows, rows, vec, s((n,), jnp.bool_))
        assert names == {"gossip_merge_rows"}
        assert names <= set(_reader_names("gossip_merge_rows_roofline"))


def test_gossip_merge_compiles(one_chip):
    from repro.configs.fg_learn import logreg_task

    d = logreg_task().param_dim

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    names = _compiles_to_kernel(
        lambda a, b, w, ok: gm._merge_pallas(a, b, w, ok, interpret=False),
        s((d,)), s((d,)), s(()), s(()))
    assert names == {"gossip_merge"}


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
    assert _kernel_names(text) == {"pairwise_contacts"}


_GATHER = re.compile(r'^\s*(?:ROOT )?%\S+ = \S+ gather\(.*op_name="([^"]*)"')


@pytest.mark.parametrize("learn", [False, True], ids=["protocol", "learning"])
def test_dense_engine_step_reads_node_tables_without_gathers(
        one_chip, monkeypatch, learn):
    """The dense slot step (N = 200, M = 1) reads its per-node tables by
    one-hot select: its compiled program holds no gather, and with
    learning on only the row gather of the parameter snapshots
    (``theta_snap[pidx]`` in ``fg.learn.merge``). The program scans a
    few slots: a single slot from the initial state would fold its
    partner reads into constants."""
    from repro.configs.fg_learn import logreg_task
    from repro.configs.fg_paper import paper_params
    from repro.sim import SimConfig, engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = SimConfig(n_slots=16, sample_every=8,
                    learn=logreg_task() if learn else None)
    params = {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
              for k in engine.dynamic_params(paper_params())}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(lambda k, p: engine._run(k, p, cfg, 1)).lower(
        key, params).compile().as_text()
    scopes = [[c for c in m.group(1).split("/") if c.startswith("fg.")][-1]
              for m in map(_GATHER.match, text.splitlines()) if m]
    assert scopes == (["fg.learn.merge"] if learn else [])


_SORT = re.compile(r'^\s*(?:ROOT )?%([A-Za-z0-9_.\-]+?)(?:\.\d+)? = .*\ssort\('
                   r'.*op_name="([^"]*)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_DIM_76 = re.compile(r"[\[,]76[\],]")


def _last_scope(op_name: str) -> str:
    return [c for c in op_name.split("/") if c.startswith("fg.")][-1]


@pytest.fixture(scope="module")
def step_text(one_chip):
    """The compiled slot step (N = 200, a few slots) at model count M,
    compiled once per M for the tests below."""
    from repro.configs.fg_paper import paper_params
    from repro.sim import SimConfig, engine

    texts = {}

    def compiled(M):
        if M not in texts:
            cfg = SimConfig(n_slots=16, sample_every=8)
            params = {k: jax.ShapeDtypeStruct((), jnp.float32,
                                              sharding=one_chip)
                      for k in engine.dynamic_params(paper_params(M=M))}
            key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                texts[M] = jax.jit(
                    lambda k, p: engine._run(k, p, cfg, M)).lower(
                        key, params).compile().as_text()
        return texts[M]

    return compiled


@pytest.mark.parametrize("M", [1, 25])
def test_engine_step_sorts_only_the_send_order(step_text, M):
    """The slot step at M = 25 holds the sorts of the per-connection send
    order (the double argsort of ``contacts._deliveries_general``), named
    as ``send_order_share`` reads them and scoped ``fg.deliveries.order``;
    the M = 1 step holds no sort."""
    text = step_text(M)
    sorts = [m.groups() for m in map(_SORT.match, text.splitlines()) if m]
    if M == 1:
        assert sorts == []
        return
    assert len(sorts) == 2
    names = set(_reader_names("send_order_share"))
    for base, op_name in sorts:
        assert base in names, base
        assert _last_scope(op_name) == "fg.deliveries.order"


@pytest.mark.parametrize("M", [1, 25])
def test_engine_step_reads_wide_rows_by_a_dot(step_text, M):
    """At M = 25 the slot step reads the peer's 76-word rows
    (``order_seed``, ``snap_has``, ``snap``: 4 + 25 + 200 byte planes)
    and ``obs_birth.T``'s 25-word rows (100 planes) by one-hot products
    under ``fg.take.mxu``, which the TPU compiles as convolutions, and no
    select of ``fg.take`` spans 76 words. Every read of the M = 1 step is
    of at most 3 words: it holds no product under ``fg.take``."""
    dots, wide_selects = [], []
    for ln in step_text(M).splitlines():
        m = _OP_NAME.search(ln)
        if not m or "fg.take" not in m.group(1):
            continue
        head = ln.split("metadata=", 1)[0]
        if " convolution(" in head or " dot(" in head:
            dots.append((_last_scope(m.group(1)), head.split(" = ")[1]))
        elif (_last_scope(m.group(1)) == "fg.take"
              and _DIM_76.search(head)):
            wide_selects.append(head)
    if M == 1:
        assert dots == []
        return
    assert wide_selects == []
    assert {scope for scope, _ in dots} == {"fg.take.mxu"}
    planes = sorted(int(re.match(r"f32\[200,(\d+)\]", shape).group(1))
                    for _, shape in dots)
    assert planes == [100, 229]


def test_city_grid_slice_is_the_real_grid():
    """The grid slice above is cut from the grid ``make_grid`` builds for
    the N = 32768 paper-density deployment."""
    from repro.configs.fg_paper import DENSITY
    from repro.sim import SimConfig
    from repro.sim.cells import make_grid

    side = math.sqrt(32768 / DENSITY)
    grid = make_grid(SimConfig(n_nodes=32768, area_side=side))
    assert (grid.ncy, grid.cap_cell) == (511, 9)
