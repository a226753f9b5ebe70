"""CPU tests of the many-model cell ``s6_m25_fig4``: the program against the
plain reference at M = W = 25, the cell's check at a small size with its
control and planted faults, its traffic's reach into the protocol, and the
stability of every point it runs.

These run on the CPU at small sizes; the numbers the cell reports come only
from the chip.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench import drivers, harness
from bench.test_bench import (FAULTS, _bitwise_single_run, _run, _tiny,
                              fresh_programs)  # noqa: F401 — a fixture

CELL = "s6_m25_fig4"


def test_reference_matches_the_program_bitwise_with_25_models():
    """The any-M paths (per-connection send order, the (N, M, Q) queues,
    per-model observations) follow the reference exactly."""
    _bitwise_single_run({"lam": 0.3, "M": 25, "W": 25, "T_T": 0.5,
                         "T_M": 0.25})


def test_sound_run_is_correct_and_control_is_not(fresh_programs):
    p = _tiny(CELL)
    r = _run(p)
    assert r["correct"], r["checks"]
    drv = drivers.make(p["config"], p["traffic"], 11)
    drv.call(0)
    assert drv.shape.M == 25
    sound, control = drv.check(), drv.check(control=True)
    limit = p["traffic"]["limits"]["protocol_gap"]
    assert sound["protocol_gap"] <= limit, sound
    assert control["protocol_gap"] > limit, control


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_timed_path_is_not_correct(fault, monkeypatch,
                                          fresh_programs):
    FAULTS[fault](monkeypatch)
    r = _run(_tiny(CELL))
    assert r["correct"] is False, r["checks"]


def test_traffic_points_set_the_protocol():
    p = harness.plan(CELL)
    drv = drivers.make(p["config"], p["traffic"], 3)
    lams = [pt["lam"] for pt in p["traffic"]["points"]]
    np.testing.assert_array_equal(lams, np.geomspace(0.01, 2.0, 10))
    assert [q.lam for q in drv.params] == lams
    for q in drv.params:
        assert (q.M, q.W, q.T_T, q.T_M) == (25, 25, 0.5, 0.25)
    rp = drivers.ref_params(drv.cfg, drv.points)
    np.testing.assert_array_equal(rp["lam"], np.float32(lams))
    np.testing.assert_array_equal(rp["T_T"], np.float32(0.5))
    np.testing.assert_array_equal(rp["T_M"], np.float32(0.25))
    assert drv.shape.M == 25
    assert drv.runs_per_call == 20


def test_every_point_is_stable_by_eq3():
    """Eq. (3) holds at every point of the traffic, and would hold at none
    with Sec. VI's default timers (the configuration's ``assumed``)."""
    from repro.configs.fg_paper import paper_contact_model
    from repro.core.meanfield import solve_fixed_point_batch

    p = harness.plan(CELL)
    ps = drivers.make(p["config"], p["traffic"], 3).params
    cm = paper_contact_model()
    lhs = np.asarray(solve_fixed_point_batch(ps, cm).stability)
    assert np.all(lhs <= 1.0), lhs
    slow = [dataclasses.replace(q, T_T=5.0, T_M=2.5) for q in ps]
    assert np.all(np.asarray(solve_fixed_point_batch(slow, cm).stability)
                  > 1.0)
