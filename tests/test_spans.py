"""``repro.spans``: the program's in-memory spans and compile counters, the
span tree ``sweep.run`` records, the spans' place on the profiler's clock,
and the engine's stage scopes in the op metadata."""

import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, sweep

CFG = SimConfig(n_nodes=40, n_slots=160, sample_every=8)
PS = [paper_params(lam=lam, M=1) for lam in (0.1, 0.2)]


def _tree_names(root):
    kids = [s for s in spans.tree(root) if s.parent == root.id]
    return [s.name for s in sorted(kids, key=lambda s: s.t0_ns)]


def test_nesting_parent_and_root_ids():
    with spans.span("fg.t.outer", k=1) as outer:
        with spans.span("fg.t.mid") as mid:
            with spans.span("fg.t.inner") as inner:
                pass
        with spans.span("fg.t.sibling") as sib:
            pass
    assert outer.parent is None and outer.root == outer.id
    assert (mid.parent, mid.root) == (outer.id, outer.id)
    assert (inner.parent, inner.root) == (mid.id, outer.id)
    assert (sib.parent, sib.root) == (outer.id, outer.id)
    assert outer.attrs == {"k": 1}
    # completed records, newest last: a span closes after its children
    assert [s.id for s in spans.tree(outer)] == [inner.id, mid.id, sib.id,
                                                outer.id]
    assert spans.recent("fg.t.outer")[-1] is outer
    assert [c for c in spans.tree(outer) if c.parent == outer.id] == [
        mid, sib]
    with spans.span("fg.t.next") as nxt:
        pass
    assert nxt.root == nxt.id != outer.id


def test_self_time_leaves_out_the_children():
    with spans.span("fg.t.parent") as parent:
        with spans.span("fg.t.a") as a:
            sum(range(20000))
        sum(range(20000))
        with spans.span("fg.t.b") as b:
            sum(range(20000))
    assert 0 < a.ns and 0 < b.ns
    assert spans.self_ns(parent) == parent.ns - a.ns - b.ns
    assert 0 < spans.self_ns(parent) < parent.ns
    assert spans.self_ns(a) == a.ns


def test_ring_keeps_the_newest_records():
    with spans.span("fg.t.first") as first:
        pass
    for i in range(spans.RING_SIZE):
        with spans.span("fg.t.fill", i=i):
            pass
    ring = spans.recent()
    assert len(ring) == spans.RING_SIZE
    assert first not in ring
    assert ring[-1].attrs == {"i": spans.RING_SIZE - 1}
    assert ring[0].name == "fg.t.fill" and ring[0].attrs == {"i": 0}


def test_a_failed_body_is_marked_failed_and_raises():
    with pytest.raises(ValueError, match="boom"):
        with spans.span("fg.t.ok_parent") as parent:
            with spans.span("fg.t.bad") as bad:
                raise ValueError("boom")
    assert bad.failed and parent.failed
    assert bad.t1_ns >= bad.t0_ns and bad in spans.recent("fg.t.bad")
    with spans.span("fg.t.after") as after:
        pass
    assert not after.failed and after.parent is None


def test_a_fresh_jit_counts_on_the_span_that_traced_it():
    x = jnp.arange(7.0)

    @jax.jit
    def fresh(v):
        return jnp.sin(v) * 3.0 + 1.0

    with spans.span("fg.t.caller") as caller:
        with spans.span("fg.t.compiles") as inner:
            fresh(x).block_until_ready()
    for key in ("trace", "lower", "compile"):
        count, seconds = inner.counters[key]
        assert count >= 1 and seconds > 0.0, key
    assert "compile" not in caller.counters
    assert "trace" not in caller.counters
    assert spans.compile_seconds([inner]) >= inner.counters["compile"][1]
    with spans.span("fg.t.warm") as warm:
        fresh(x).block_until_ready()
    assert "compile" not in warm.counters and "trace" not in warm.counters


def test_sweep_records_one_tree_per_call_in_chunks():
    out = sweep.run(PS, CFG, (0, 1), reduce="mean", chunk_size=1)
    root = spans.recent("fg.sweep")[-1]
    assert root.parent is None and not root.failed
    assert root.attrs == {"reduce": "mean", "slots": 160, "runs": 4,
                          "chunks": 2, "take": "onehot", "models": 1}
    assert out.plan.n_chunks == 2
    # double buffered: chunk 1 is dispatched before chunk 0 is pulled
    assert _tree_names(root) == [
        "fg.sweep.prepare", "fg.sweep.keys", "fg.sweep.dispatch",
        "fg.sweep.dispatch", "fg.sweep.pull", "fg.sweep.pull",
        "fg.sweep.finalize"]
    tree = spans.tree(root)
    assert {s.root for s in tree} == {root.id}
    assert [s.attrs["chunk"] for s in tree
            if s.name == "fg.sweep.pull"] == [0, 1]
    # the first call of this shape compiled its chunk program on dispatch
    assert root.ns >= sum(s.ns for s in tree if s.parent == root.id)


@pytest.mark.parametrize("M", [1, 3, 25])
def test_sweep_root_records_the_model_count(M):
    """The root records M, and in ``take`` the engine step's read paths:
    the select alone at M = 1 (no row is wider than 3 words), the select
    and the MXU product once M widens the peer's rows."""
    ps = [paper_params(lam=0.1, M=M, T_T=0.5, T_M=0.25)]
    sweep.run(ps, CFG, (0,), reduce="mean")
    attrs = spans.recent("fg.sweep")[-1].attrs
    assert attrs["models"] == M
    assert attrs["take"] == ("onehot" if M == 1 else "onehot+mxu")


def test_checkpointed_sweep_tree_and_latency_from_spans(tmp_path):
    out = sweep.run(PS, CFG, (0, 1), reduce="mean", chunk_size=1,
                    checkpoint_dir=str(tmp_path))
    root = spans.recent("fg.sweep")[-1]
    assert _tree_names(root) == [
        "fg.sweep.prepare", "fg.sweep.keys",
        "fg.sweep.dispatch", "fg.sweep.pull", "fg.sweep.checkpoint",
        "fg.sweep.dispatch", "fg.sweep.pull", "fg.sweep.checkpoint",
        "fg.sweep.finalize"]
    tree = spans.tree(root)
    for c in (0, 1):
        d, p = (next(s for s in tree if s.name == n and s.attrs["chunk"] == c)
                for n in ("fg.sweep.dispatch", "fg.sweep.pull"))
        assert out.telemetry["chunks"][c] == {
            "attempts": 1, "latency_s": (p.t1_ns - d.t0_ns) * 1e-9}
    again = sweep.run(PS, CFG, (0, 1), reduce="mean", chunk_size=1,
                      checkpoint_dir=str(tmp_path), resume=True)
    root = spans.recent("fg.sweep")[-1]
    assert _tree_names(root) == [
        "fg.sweep.prepare", "fg.sweep.keys", "fg.sweep.checkpoint",
        "fg.sweep.finalize"]
    for k in out.stats:
        assert np.array_equal(out.stats[k], again.stats[k], equal_nan=True)


def test_a_failed_attempt_is_a_failed_dispatch_span(tmp_path, monkeypatch):
    orig = sweep._chunk_worker

    def patched(*args, **kwargs):
        worker = orig(*args, **kwargs)

        def flaky(keys, p_chunk):
            flaky.n += 1
            if flaky.n == 1:
                raise RuntimeError("injected dispatch failure")
            return worker(keys, p_chunk)

        flaky.n = 0
        return flaky

    monkeypatch.setattr(sweep, "_chunk_worker", patched)
    with pytest.warns(UserWarning, match="attempt 1/2"):
        out = sweep.run(PS[:1], CFG, (0,), reduce="mean",
                        checkpoint_dir=str(tmp_path))
    root = spans.recent("fg.sweep")[-1]
    assert not root.failed
    d = [s for s in spans.tree(root) if s.name == "fg.sweep.dispatch"]
    assert [(s.failed, s.attrs["attempt"]) for s in d] == [(True, 0),
                                                           (False, 1)]
    pull = next(s for s in spans.tree(root) if s.name == "fg.sweep.pull")
    assert out.telemetry["chunks"][0] == {
        "attempts": 2, "latency_s": (pull.t1_ns - d[0].t0_ns) * 1e-9}


def test_spans_share_the_profilers_clock(tmp_path):
    """Every ring record of a traced sweep is an ``fg.*`` host event of the
    profiler's trace, of the same duration."""
    sweep.run(PS, CFG, (0, 1), reduce="mean", chunk_size=1)   # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        sweep.run(PS, CFG, (0, 1), reduce="mean", chunk_size=1)
    finally:
        jax.profiler.stop_trace()
    root = spans.recent("fg.sweep")[-1]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    events = collections.defaultdict(list)
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("fg."):
                        events[ev.name].append(ev.duration_ns)
    tree = spans.tree(root)
    assert len(tree) == 8     # the root and its seven children
    for s in tree:
        gap = min(abs(d - s.ns) for d in events[s.name])
        assert gap < 100_000, (s.name, gap)


STAGES = ("fg.faults", "fg.mobility", "fg.contacts", "fg.deliveries",
          "fg.learn.merge", "fg.matching", "fg.learn.snapshot",
          "fg.observations", "fg.compute", "fg.learn.train", "fg.outputs",
          "fg.reduce")


def test_every_stage_scope_is_in_the_op_metadata():
    """The sweep's chunk program with faults and learning on carries each
    stage's scope in its ``op_name`` metadata."""
    from repro.configs.fg_faults import harsh
    from repro.configs.fg_learn import logreg_task

    cfg = SimConfig(n_nodes=40, n_slots=16, sample_every=8, faults=harsh(),
                    learn=logreg_task())
    setup = sweep._prepare(PS, cfg, (0,), "mean", None, None, (), None, 1)
    fn = sweep._worker_fn(cfg, setup.M, "mean", setup.key_s0, (), ())
    text = jax.jit(fn).lower(setup.keys(), setup.chunk_params(0)).as_text(
        dialect="hlo", debug_info=True)
    scopes = {part for ln in text.splitlines() if 'op_name="' in ln
              for part in ln.split('op_name="', 1)[1].split('"', 1)[0]
              .split("/")}
    for stage in STAGES:
        assert stage in scopes, stage
