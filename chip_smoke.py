#!/usr/bin/env python3
"""Smoke test of the Floating Gossip simulator's main path on a TPU.

Run from the root of the checkout, in a process of its own (it holds the
chip):

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # the phase (a) study sharded over
                                       # four chips against one device

Phases, in order, all through the entry points a user calls:

  (a) paper study: the §VI deployment (``SimConfig()``: 200 nodes in
      200 m x 200 m, RZ radius 100 m, RDM at 1 m/s, dt = 0.25 s, 8000
      slots) at ``paper_params(M=1)``, 8 values of λ x 16 seeds through
      ``sweep.run(reduce="mean")``; the simulated availability at each λ
      against the Lemma-1 fixed point (``solve_fixed_point``), within 15%.
  (b) the compiled contact kernels against their jnp references on seeded
      positions: ``pairwise_contacts`` at N = 200 and 4096,
      ``cell_close_words`` on the city grid of phase (c); every output
      equal.
  (c) city-scale run: ``simulate`` with the cells contact backend at
      N = 32768 at the paper's density, a few hundred slots; no neighbour
      list may overflow.
  (d) learning on: ``sweep.run`` with ``SimConfig(learn=logreg_task())``
      at the paper geometry; holder test accuracy must rise.

Each phase prints one JSON line (device, compile and wall seconds, what it
compared, the result). The kernels must run compiled: each phase checks
that its kernel was traced with ``interpret=False`` on the TPU backend. A
failure in any phase makes the script exit non-zero without the final
line; so does a machine whose first JAX device is not a TPU. The last line
of a passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: Phase (a) operating points: λ over the paper's Fig. 1 range.
LAMS = (0.02, 0.03, 0.05, 0.08, 0.1, 0.15, 0.2, 0.3)
SEEDS = tuple(range(16))
AVAIL_RTOL = 0.15          # tests/test_sim_vs_meanfield.py
CITY_N = 32768
CITY_SLOTS = 400


@contextlib.contextmanager
def _kernel_calls(module, name: str, calls: list):
    """Record the ``interpret`` flag of every trace-time call of
    ``module.name`` (the kernel entry the engine looks up at call time)."""
    real = getattr(module, name)

    def wrapper(*args, **kwargs):
        calls.append(kwargs.get("interpret", False))
        return real(*args, **kwargs)

    setattr(module, name, wrapper)
    try:
        yield
    finally:
        setattr(module, name, real)


def _compiled_kernel(calls: list) -> bool:
    return bool(calls) and not any(calls)


def phase_paper_study(n_devices=None):
    """(a): availability of the §VI study against Lemma 1."""
    import numpy as np

    from repro.configs.fg_paper import paper_contact_model, paper_params
    from repro.core.meanfield import solve_fixed_point
    from repro.kernels import contacts
    from repro.sim import SimConfig, sweep

    ps = [paper_params(lam=lam, M=1) for lam in LAMS]
    calls: list = []
    with _kernel_calls(contacts, "pairwise_contacts", calls):
        out = sweep.run(ps, SimConfig(), SEEDS, reduce="mean",
                        n_devices=n_devices)
    cm = paper_contact_model()
    a_sim = np.asarray(out.stats["availability"], np.float64).reshape(
        len(LAMS), len(SEEDS)).mean(axis=1)
    a_mf = np.asarray([float(solve_fixed_point(p, cm).a) for p in ps])
    rel = np.abs(a_mf - a_sim) / a_sim
    ok = bool(np.all(rel < AVAIL_RTOL)) and _compiled_kernel(calls)
    detail = dict(
        lam=list(LAMS), a_sim=a_sim.tolist(), a_lemma1=a_mf.tolist(),
        rel_err=rel.tolist(), rtol=AVAIL_RTOL,
        devices_used=out.devices_used, kernel_compiled=_compiled_kernel(calls),
    )
    return ok, "availability vs Lemma 1 at each lambda", detail, out


def _mismatches(got, want) -> int:
    import numpy as np

    return int(np.sum(np.asarray(got) != np.asarray(want)))


def phase_kernels():
    """(b): compiled contact kernels against their references."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.contacts import (cell_close_words,
                                        cell_close_words_ref,
                                        pairwise_contacts,
                                        pairwise_contacts_ref, zone_words)
    from repro.sim.cells import bin_nodes, make_grid
    from repro.sim.compute import pack_mask

    r_tx2 = 25.0
    counts = {}
    # the references run jitted: eagerly, each of their ops would be a
    # compilation of its own
    pair_ref = jax.jit(pairwise_contacts_ref, static_argnums=(4,))
    cell_ref = jax.jit(cell_close_words_ref, static_argnums=(4, 5, 6))
    for n in (200, 4096):
        # about four radio neighbours per node
        side = math.sqrt(n * math.pi * r_tx2 / 4.0)
        ks = jax.random.split(jax.random.PRNGKey(n), 4)
        pos = jax.random.uniform(ks[0], (n, 2), maxval=side)
        in_rz = jax.random.uniform(ks[1], (n,)) < 0.8
        elig = jax.random.uniform(ks[2], (n,)) < 0.7
        prev = jax.random.uniform(ks[3], (n, n)) < 0.002
        prevw = pack_mask(prev | prev.T)
        got = pairwise_contacts(pos, in_rz, elig, prevw, r_tx2,
                                interpret=False)
        want = pair_ref(pos, in_rz, elig, prevw, r_tx2)
        for name, g, w in zip(("closew", "best_j", "has"), got, want):
            counts[f"pairwise_n{n}_{name}"] = _mismatches(g, w)
        counts[f"pairwise_n{n}_contacts"] = int(
            jnp.sum(jax.lax.population_count(got[0])))

    cfg = _city_config()
    grid = make_grid(cfg)

    @jax.jit
    def planes(key):
        # seeded positions binned into the cell-major planes the engine
        # builds (repro.sim.cells.neighbor_lists)
        k1, k2 = jax.random.split(key)
        pos = jax.random.uniform(k1, (cfg.n_nodes, 2), maxval=cfg.area_side)
        zonew = zone_words(jax.random.uniform(k2, (cfg.n_nodes,)) < 0.9)
        cellbuf, _, _, _ = bin_nodes(pos, grid)
        safe = jnp.clip(cellbuf, 0, cfg.n_nodes - 1)
        empty = cellbuf < 0
        return (jnp.where(empty, jnp.float32(1e9), pos[safe, 0]),
                jnp.where(empty, jnp.float32(1e9), pos[safe, 1]),
                jnp.where(empty, jnp.uint32(0), zonew[safe]), cellbuf)

    xc, yc, zc, idc = planes(jax.random.PRNGKey(7))
    r2 = cfg.r_tx ** 2
    got = cell_close_words(xc, yc, zc, idc, grid.ncx, grid.ncy, r2,
                           interpret=False)
    want = cell_ref(xc, yc, zc, idc, grid.ncx, grid.ncy, r2)
    counts["cell_words"] = _mismatches(got, want)
    counts["cell_contacts"] = int(jnp.sum(jax.lax.population_count(got)))
    ok = all(v == 0 for k, v in counts.items() if not k.endswith("contacts"))
    return ok, "compiled kernel == jnp reference, every output", counts, None


def _city_config(n_slots=CITY_SLOTS):
    from repro.configs.fg_paper import DENSITY
    from repro.sim import SimConfig

    side = math.sqrt(CITY_N / DENSITY)
    return SimConfig(n_nodes=CITY_N, area_side=side, rz_radius=side / 2.0,
                     contact_backend="cells", n_slots=n_slots,
                     sample_every=8, overflow_mode="strict")


def phase_city():
    """(c): one city-scale run on the cells backend."""
    import numpy as np

    from repro.configs.fg_paper import paper_params
    from repro.kernels import contacts
    from repro.sim import simulate

    calls: list = []
    with _kernel_calls(contacts, "cell_close_words", calls):
        out = simulate(paper_params(lam=0.05, M=1), _city_config(), seed=0)
    ovf = int(np.max(np.asarray(out.nbr_overflow)))
    avail = np.asarray(out.availability)
    ok = (ovf == 0 and bool(np.all(np.isfinite(avail)))
          and _compiled_kernel(calls))
    detail = dict(n_nodes=CITY_N, n_slots=CITY_SLOTS, nbr_overflow=ovf,
                  availability_last=avail[-1].tolist(),
                  n_in_rz_mean=float(np.mean(np.asarray(out.n_in_rz))),
                  kernel_compiled=_compiled_kernel(calls))
    return ok, "nbr_overflow == 0 on the Pallas cell path", detail, None


def phase_learning():
    """(d): gossip learning through the compiled row-merge kernel."""
    import numpy as np

    from repro.configs.fg_learn import logreg_task
    from repro.configs.fg_paper import paper_params
    from repro.kernels import gossip_merge
    from repro.sim import SimConfig, sweep

    cfg = SimConfig(learn=logreg_task(), n_slots=4000, sample_every=40)
    calls: list = []
    with _kernel_calls(gossip_merge, "_rows_pallas", calls):
        out = sweep.run([paper_params(lam=0.05, M=1)], cfg, tuple(range(4)),
                        reduce="trace")
    acc = np.asarray(out.test_acc_holders, np.float64)[0]   # (seeds, samples)
    k = max(acc.shape[1] // 10, 1)
    start, end = float(np.mean(acc[:, :k])), float(np.mean(acc[:, -k:]))
    ok = end > start and bool(np.all(np.isfinite(acc))) \
        and _compiled_kernel(calls)
    detail = dict(acc_start=start, acc_end=end, samples=acc.shape[1],
                  kernel_compiled=_compiled_kernel(calls))
    return ok, "holder test accuracy, first vs last tenth", detail, None


def _run_phase(name, fn, device, failures, *args):
    """Run one phase as a span of its own: its compile seconds are the
    backend compilations counted on the phase's spans (tracing and
    lowering stay in the wall time: their events nest, compilations do
    not)."""
    from repro import spans

    t0 = time.perf_counter()
    with spans.span("fg.smoke", phase=name) as phase:
        try:
            ok, compared, detail, extra = fn(*args)
        except Exception:  # noqa: BLE001 — report, then fail the run
            traceback.print_exc()
            ok, compared, detail, extra = False, "raised", {}, None
    compile_s = sum(s.counters.get("compile", (0, 0.0))[1]
                    for s in spans.tree(phase))
    line = dict(phase=name, device=device, compile_s=compile_s,
                wall_s=time.perf_counter() - t0,
                compared=compared, ok=ok, **detail)
    print(json.dumps(line), flush=True)
    if not ok:
        failures.append(name)
    return extra


def _four_chips(device, failures):
    """The phase (a) study sharded over four chips vs one device."""
    import numpy as np

    outs = [
        _run_phase(f"a_paper_study_{n}dev", phase_paper_study, device,
                   failures, n)
        for n in (4, 1)
    ]
    if None in outs:
        failures.append("four_chips")
        return
    four, one = outs
    diff = {k: int(np.sum(~((np.asarray(four.stats[k])
                              == np.asarray(one.stats[k]))
                             | (np.isnan(np.asarray(four.stats[k], float))
                                & np.isnan(np.asarray(one.stats[k],
                                                      float))))))
            for k in one.stats}
    ok = four.devices_used == 4 and one.devices_used == 1 \
        and not any(diff.values())
    print(json.dumps(dict(
        phase="four_chips_vs_one", device=device, ok=ok,
        compared="sweep reductions, 4 devices vs 1, bitwise",
        devices_used=[four.devices_used, one.devices_used],
        mismatches=diff)), flush=True)
    if not ok:
        failures.append("four_chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the phase (a) study sharded over four "
                         "chips, compared bitwise with one device")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    device = f"{dev.platform}:{dev.device_kind}"
    failures: list = []
    if args.four_chips:
        _four_chips(device, failures)
    else:
        for name, fn in (("a_paper_study", phase_paper_study),
                         ("b_kernels_vs_ref", phase_kernels),
                         ("c_city_cells", phase_city),
                         ("d_learning", phase_learning)):
            _run_phase(name, fn, device, failures)
    if failures:
        print(f"chip_smoke: failed phases {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
