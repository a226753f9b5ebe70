"""CPU tests of the per-layer readers that read the program's spans:
``sweep_host_ms.study``, ``sweep_compile_ms.study`` and
``slot_wait_us.study``, over hand-built span records."""

from __future__ import annotations

import collections
import sys

import pytest

from bench import harness
from repro import spans

METRICS = [{"name": "sweep_host_ms.study", "unit": "ms"},
           {"name": "sweep_compile_ms.study", "unit": "ms"},
           {"name": "slot_wait_us.study", "unit": "us"}]
MS = 1_000_000          # ns


def _call(first_id: int, t0: int, *, pulls=(), compile_s=0.0,
          failed=False, total=0):
    """One ``sweep.run`` call's records: a root of ``total`` ns starting at
    ``t0`` and a pull child per ``(start offset, ns)`` in ``pulls``; the
    dispatch child carries ``compile_s`` of backend compilation."""
    rid = first_id
    recs = [spans.Span(id=rid + 1, parent=rid, root=rid,
                       name="fg.sweep.dispatch", t0_ns=t0, t1_ns=t0 + MS,
                       counters={"compile": [1, compile_s]}
                       if compile_s else {})]
    for k, (off, ns) in enumerate(pulls):
        recs.append(spans.Span(id=rid + 2 + k, parent=rid, root=rid,
                               name="fg.sweep.pull", t0_ns=t0 + off,
                               t1_ns=t0 + off + ns))
    recs.append(spans.Span(id=rid, parent=None, root=rid, name="fg.sweep",
                           t0_ns=t0, t1_ns=t0 + total, failed=failed,
                           counters={"trace": [1, 0.002]}))
    return recs


def _ctx(calls: int, slots: int = 1000):
    return harness.LayerContext(
        trace=None, config={"n_slots": slots}, traffic={},
        device_kind="TPU v5 lite", calls=calls, slots_per_call=slots,
        runs_per_call=8)


@pytest.fixture
def ring(monkeypatch):
    def fill(*calls):
        recs = [r for c in calls for r in c]
        monkeypatch.setattr(spans, "_RING", collections.deque(recs))
    return fill


def _read(ctx):
    return {k: v["value"] for k, v in harness.read_layers(METRICS,
                                                          ctx).items()}


def test_arithmetic_of_the_three_metrics(ring):
    ring(_call(10, 0, pulls=[(2 * MS, 40 * MS)], total=50 * MS),
         _call(20, 60 * MS, pulls=[(3 * MS, 30 * MS), (40 * MS, 5 * MS)],
               compile_s=0.5, total=70 * MS))
    got = _read(_ctx(2, slots=1000))
    # host: (50 - 40) and (70 - 35) ms; compile: 2 ms of tracing per
    # call plus 500 ms on the second; wait: 75 ms over 2000 slots
    assert got["sweep_host_ms.study"] == pytest.approx((10 + 35) / 2)
    assert got["sweep_compile_ms.study"] == pytest.approx(
        (2.0 + 502.0) / 2)
    assert got["slot_wait_us.study"] == pytest.approx(75_000 / 2000)


def test_the_warm_up_call_is_left_out(ring):
    warm = _call(1, 0, pulls=[(0, 900 * MS)], compile_s=20.0,
                 total=990 * MS)
    ring(warm, _call(10, 1000 * MS, pulls=[(0, 40 * MS)], total=42 * MS))
    got = _read(_ctx(1, slots=400))
    assert got["sweep_host_ms.study"] == pytest.approx(2.0)
    assert got["sweep_compile_ms.study"] == pytest.approx(2.0)
    assert got["slot_wait_us.study"] == pytest.approx(100.0)


def test_a_failed_call_is_skipped(ring):
    ring(_call(1, 0, pulls=[(0, 9 * MS)], total=10 * MS),
         _call(10, 20 * MS, pulls=[(0, 40 * MS)], total=42 * MS),
         _call(20, 70 * MS, pulls=[(0, 1 * MS)], total=500 * MS,
               failed=True),
         _call(30, 600 * MS, pulls=[(0, 40 * MS)], total=44 * MS))
    got = _read(_ctx(2, slots=400))
    assert got["sweep_host_ms.study"] == pytest.approx(3.0)
    assert got["slot_wait_us.study"] == pytest.approx(100.0)


def test_too_few_roots_read_nothing(ring):
    ring(_call(1, 0, pulls=[(0, 9 * MS)], total=10 * MS),
         _call(10, 20 * MS, pulls=[(0, 40 * MS)], total=42 * MS))
    assert _read(_ctx(3)) == {}
    assert _read(_ctx(0)) == {}


def test_a_program_without_spans_reads_nothing(ring, monkeypatch):
    ring(_call(1, 0, pulls=[(0, 9 * MS)], total=10 * MS))
    import repro

    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert _read(_ctx(1)) == {}
