"""CPU tests of the benchmark's yardstick: the trace reduction, the kernel
costs, the reference against the program, the control, and a run with the
timed path broken underneath.

These run on the CPU at small sizes; the numbers a cell reports come only
from the chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import costs, drivers, harness, reference, trace_reduce

# ---------------------------------------------------------------- trace


def _xspace(devices, spans) -> str:
    """A text-proto trace: ``devices`` is a list of per-chip op lists
    ``(name, start_ns, dur_ns)``, ``spans`` the host spans."""
    planes = []

    def plane(pid, name, line, events):
        names = sorted({e[0] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "\n".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }}" for n, s, d in events)
        meta = "\n".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')

    for k, ops in enumerate(devices):
        planes.append(plane(k + 1, f"/device:TPU:{k}", "XLA Ops", ops))
    planes.append(plane(99, "/host:CPU", "python", spans))
    return "\n".join(planes)


KERNEL = "%pairwise_contacts.11 = (s32[8,16,256,8]) custom-call(f32[1] %a)"
FUSION = "%fusion.230 = f32[25600]{0:T(1024)} fusion(f32[16] %b)"
LOOP = "%while.44 = (s32[], f32[16]) while(s32[] %c)"


def _summary():
    # window 0..1000 ns; chip 0 busy 100..400 and 600..900 (a loop holding
    # a kernel and a fusion), chip 1 busy 100..300; one op straddles the
    # window's end and is clipped
    dev0 = [(LOOP, 100, 300), (KERNEL, 150, 100), (FUSION, 260, 140),
            (FUSION, 600, 300)]
    dev1 = [(KERNEL, 100, 200), (FUSION, 950, 100)]
    spans = [("bench.window", 0, 1000), ("bench.call", 50, 400),
             ("bench.call", 560, 430)]
    pd = jax.profiler.ProfileData.from_text_proto(_xspace([dev0, dev1],
                                                          spans))
    return trace_reduce.reduce_profile(pd)


def test_trace_busy_idle_and_kernel_time():
    s = _summary()
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx([600e-9, 250e-9])
    assert s.n_devices == 2
    assert s.mean_busy_s == pytest.approx(425e-9)
    # the loop container holds time of its own ops: left out of op times
    assert "while" not in s.base_s
    assert s.kernel_s(("pairwise_contacts",)) == pytest.approx(300e-9)
    assert s.base_s["fusion"] == pytest.approx((140 + 300 + 50) * 1e-9)


def test_trace_idle_gaps_named_by_host_span():
    s = _summary()
    # calls at 50..450 and 560..990: chip 0 idles 0..100 (first call),
    # 400..600 (midpoint 500: between the calls), 900..1000 (second call);
    # chip 1 idles 0..100 and 300..950 (midpoint 625: second call)
    assert s.gaps[0] == ("bench.call", pytest.approx(650e-9))
    assert ("bench.window", pytest.approx(200e-9)) in s.gaps
    b = s.breakdown(top=2)
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2
    assert b["device_ops"][0][0] == "%fusion.230 = f32[25600] fusion(f32[16])"


def test_trace_without_window_or_device_is_refused():
    pd = jax.profiler.ProfileData.from_text_proto(
        _xspace([[(KERNEL, 0, 10)]], [("bench.call", 0, 10)]))
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(pd)
    pd = jax.profiler.ProfileData.from_text_proto(
        _xspace([], [("bench.window", 0, 10)]))
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_profile(pd)


def test_instruction_names():
    assert trace_reduce.instruction(KERNEL) == ("pairwise_contacts",
                                                "custom-call")
    assert trace_reduce.instruction(LOOP) == ("while", "while")
    assert trace_reduce.instruction("%copy-start.28 = (f32[8]) "
                                    "copy-start(f32[8] %x)") == (
        "copy-start", "copy-start")
    assert trace_reduce.instruction("not hlo") == ("not hlo", "")
    gather = ("%fusion.217 = u32[25600,2]{1,0:T(8,128)S(1)} fusion(u32[8,16,"
              "200,2]{3,2,1,0:T(8,128)S(1)} %bitcast.289, s32[25600]{0:T(1024)"
              "S(1)} %reshape.1336), kind=kCustom, calls=%fused_computation.8")
    assert trace_reduce._label(gather) == (
        "%fusion.217 = u32[25600,2] fusion(u32[8,16,200,2], s32[25600])")


# ---------------------------------------------------------------- costs


def test_kernel_costs_at_the_cells_shapes():
    # s6_study / s6_learn: 200 x 200 pairs, 7 close words per node
    assert costs.pairwise_contacts(200) == (7 * 200 * 200,
                                            4 * (4 * 200 + 2 * 1400
                                                 + 2 * 200))
    # s6_learn: 200 replicas of 34 parameters
    assert costs.gossip_merge_rows(200, 34) == (4 * 200 * 34,
                                                4 * (3 * 200 * 34 + 400))


def test_peaks_table():
    pk = costs.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
    # 1 s of compute or of memory, whichever is larger, in 2 s
    assert costs.roofline_share(197e12, 1.0, 2.0,
                                "TPU v5 lite") == pytest.approx(50.0)
    assert costs.roofline_share(1.0, 819e9, 4.0,
                                "TPU v5 lite") == pytest.approx(25.0)


# ---------------------------------------------------------------- cells


def _tiny(workload: str) -> dict:
    """The cell's plan at a size the CPU runs in seconds: fewer nodes at
    the same density, two of its points with more observations, two
    seeds, 64 slots."""
    p = harness.plan(workload)
    cfg, tr = dict(p["config"]), dict(p["traffic"])
    n = 40
    side = cfg["area_side"] * math.sqrt(n / cfg["n_nodes"])
    cfg.update(n_nodes=n, area_side=side, rz_radius=side / 2)
    tr.update(points=[{**pt, "lam": 0.3} for pt in tr["points"][-2:]],
              seeds_per_call=2, check_runs=4,
              run={**tr["run"], "n_slots": 64,
                   "sample_every": min(tr["run"]["sample_every"], 8)})
    p.update(config=cfg, traffic=tr)
    return p


@pytest.fixture
def fresh_programs(monkeypatch):
    """No compiled program carries over between runs (a fault planted in
    the program must be traced), and the run leaves the process's
    compilation-cache settings alone."""
    from repro.sim import sweep

    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    jax.clear_caches()
    sweep._chunk_worker.cache_clear()
    yield
    jax.clear_caches()
    sweep._chunk_worker.cache_clear()


def _run(p, seed=2**31 + 5):
    import time

    return harness.run_cell(p, seed, 0.0, False, t_start=time.perf_counter(),
                            require_tpu=False)


CELLS = ("s6_study", "s6_learn")


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_control_is_not(workload, fresh_programs):
    p = _tiny(workload)
    r = _run(p)
    assert r["correct"], r["checks"]
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert set(r["metrics"]) == {m["name"] for m in p["end_to_end"]}

    drv = drivers.make(p["config"], p["traffic"], 11)
    drv.call(0)
    sound, control = drv.check(), drv.check(control=True)
    limits = p["traffic"]["limits"]
    assert all(sound[k] <= limits[k] for k in sound), sound
    assert any(control[k] > limits[k] for k in control), control


def _unchanged_state(monkeypatch):
    """Every slot step returns the state it was given."""
    from repro.sim.state import SimState

    monkeypatch.setattr(SimState, "replace", lambda self, **kw: self)


def _half_the_samples(monkeypatch):
    """The on-device mean is taken over half of the samples."""
    from repro.sim import sweep

    real = sweep._reduce_outs

    def half(outs, reduce, s0, qs, tau, t):
        n = next(iter(outs.values())).shape[2]
        keep = s0 + (n - s0) // 2
        outs = {k: v[:, :, :keep] if v.ndim > 2 and v.shape[2] == n else v
                for k, v in outs.items()}
        return real(outs, reduce, s0, qs, tau, t)

    monkeypatch.setattr(sweep, "_reduce_outs", half)


def _altered_answer(monkeypatch):
    """The busy fraction is off by 1% where the engine produces it."""
    from repro.sim import engine, observations

    real = observations.slot_outputs

    def altered(**kw):
        out = real(**kw)
        out["busy_frac"] = out["busy_frac"] * 1.01
        return out

    monkeypatch.setattr(engine.observations, "slot_outputs", altered)


FAULTS = {"state_unchanged": _unchanged_state,
          "half_the_samples": _half_the_samples,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in FAULTS])
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch,
                                          fresh_programs):
    FAULTS[fault](monkeypatch)
    r = _run(_tiny(workload))
    assert r["correct"] is False, r["checks"]


# ------------------------------------------------------------ reference


#: Per-sample traces of a single run and the reference output of each.
TRACES = {
    "availability": "availability", "busy_frac": "busy_frac",
    "stored_info": "stored", "model_holders": "model_holders",
    "n_in_rz": "n_in_rz", "obs_birth": "obs_birth",
    "obs_holders": "obs_holders", "availability_z": "availability",
    "stored_info_z": "stored", "n_in_rz_z": "n_in_rz",
}


def _bitwise_single_run(point):
    """The plain reference and the engine give the same per-sample traces
    of one run at ``point`` (small N)."""
    from repro.sim import SimConfig, simulate

    cfg = harness.load_json(f"{harness.BENCH}/configs/fg_paper_s6.json")
    side = 200.0 * math.sqrt(60 / 200)
    cfg.update(n_nodes=60, area_side=side, rz_radius=side / 2, n_slots=200,
               sample_every=4, warmup_frac=0.5)
    sim = SimConfig(**{k: cfg[k] for k in drivers.DEPLOYMENT_KEYS
                       + drivers.RUN_KEYS})
    out = simulate(drivers.fg_params(cfg, point), sim, seed=7)
    want = reference.run_many(
        jax.random.PRNGKey(7)[None],
        {k: jnp.asarray(v) for k, v in drivers.ref_params(cfg,
                                                          [point]).items()},
        drivers.ref_shape(cfg, [point]))
    pts = reference.sample_points(200, 4)
    for key, rk in TRACES.items():
        w = np.asarray(want[rk][0])[pts]
        np.testing.assert_array_equal(
            np.asarray(getattr(out, key)).reshape(w.shape), w, err_msg=key)


def test_reference_matches_the_program_bitwise_on_a_small_run():
    """The comparison rests on this."""
    _bitwise_single_run({"lam": 0.3})


@pytest.mark.parametrize("point", [
    {"lam": 0.3, "T_T": 0.5, "T_M": 0.25, "L": 500000.0},
    {"lam": 0.3, "T_T": 15.0, "Lam": 10},
], ids=["short_timers_large_model", "ten_observers"])
def test_reference_matches_the_program_at_the_studies_points(point):
    """The fields the studies' points set reach the program and the
    reference alike."""
    _bitwise_single_run(point)


@pytest.mark.parametrize("workload", CELLS)
def test_traffic_points_set_the_protocol(workload):
    p = harness.plan(workload)
    drv = drivers.make(p["config"], p["traffic"], 3)
    rp = drivers.ref_params(drv.cfg, drv.points)
    for i, (pt, fg) in enumerate(zip(drv.points, drv.params)):
        for k, v in pt.items():
            assert getattr(fg, k) == v, (k, v)
        assert rp["T_L"][i] == np.float32(fg.T_L)
        assert rp["T_T"][i] == np.float32(fg.T_T)
        assert rp["lam"][i] == np.float32(fg.lam)
    assert drv.shape.Lam == int(drv.params[0].Lam)
    assert drv.sim_cfg.n_slots == p["traffic"]["run"]["n_slots"]
    assert drv.runs_per_call == (len(p["traffic"]["points"])
                                 * p["traffic"]["seeds_per_call"])


def test_points_that_differ_in_static_sizes_are_refused():
    cfg = harness.load_json(f"{harness.BENCH}/configs/fg_paper_s6.json")
    cfg.update(n_slots=64)
    with pytest.raises(ValueError, match="Lam"):
        drivers.ref_shape(cfg, [{"Lam": 1}, {"Lam": 10}])
    with pytest.raises(KeyError, match="unknown"):
        drivers.protocol(cfg, {"lambda": 0.1})


def test_gap():
    assert drivers.gap([1.0, -np.inf], [1.0, -np.inf]) == 0.0
    assert drivers.gap([1.0, 2.2], [1.0, 2.0]) == pytest.approx(0.1)
    assert drivers.gap([np.nan], [1.0]) == np.inf
    assert drivers.gap([1e-3], [0.0]) > 1e6


def test_call_seeds_follow_the_run_seed():
    a = drivers.call_seeds(2**40 + 3, 0, 16)
    assert a == drivers.call_seeds(2**40 + 3, 0, 16)
    assert a != drivers.call_seeds(2**40 + 3, 1, 16)
    assert a != drivers.call_seeds(2**40 + 4, 0, 16)
    assert all(0 <= s < 2**31 for s in a)
