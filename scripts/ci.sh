#!/usr/bin/env bash
# CI entry point: the tier-1 test suite and the invariant gates below.
# Speed is measured on the chip by the benchmark (bench/, PERF.md), not
# here.
#
# The tier-1 suite runs twice: once with the default single XLA CPU
# device and once with 2 forced host devices, so both the single-device
# and the sharded sweep code paths (mesh planning, padding, SPMD
# dispatch) are exercised in-process — not only inside the dedicated
# subprocess tests.
#
# Tests run in two lanes, split by the `slow` pytest marker
# (registered in pytest.ini): the default tier-1 lane excludes
# slow-marked tests (the multi-thousand-slot simulation validations);
# --nightly runs the whole suite, slow tests included. Each pytest run
# ends with a TEST-SUMMARY line (test count + wall time), so collection
# regressions (tests silently dropping out of a lane) are visible in
# the log diff.
#
# Usage:  bash scripts/ci.sh [--chaos-smoke] [--adversarial-smoke]
#                            [--nightly] [extra pytest args...]
#
#   --adversarial-smoke  gate the Byzantine layer's two invariants:
#                   (a) a zero-rate adversarial config (honest classes,
#                   all-off defense knobs) is bitwise identical to
#                   faults=None/defense=None on BOTH contact backends
#                   (dense and cells) across every protocol and learning
#                   trace, and (b) at the 10% amplified-sign-flip preset
#                   the calibrated clipped defense recovers >= 90% of the
#                   clean holder accuracy while the undefended run
#                   degrades below it.
#   --chaos-smoke   gate the fault-tolerant dispatcher's core invariant:
#                   run a small sweep through the multi-process work
#                   queue under an injected chaos schedule (one worker
#                   SIGKILL + one heartbeat-stopped hang) and fail unless
#                   the reductions are bitwise identical to the fault-free
#                   in-process sweep with zero quarantined chunks — under
#                   both 1 and 2 forced host devices.
#   --nightly       run the full suite including `slow`-marked tests
#                   (the tier split: tier-1 excludes them). The slow lane
#                   includes the sim→mean-field convergence sweep
#                   (tests/test_sim_convergence.py: the availability
#                   error vs the Lemma 1-3 prediction must shrink from
#                   the paper-scale N to a cells-backend large-N point).
#                   Also runs the full fig_learning sweep and fails
#                   unless the measured Gossip-Learning accuracy ordering
#                   agrees with the Theorem 2 capacity ordering.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

CHAOS_SMOKE=0
ADV_SMOKE=0
NIGHTLY=0
ARGS=()
for a in "$@"; do
  case "$a" in
    --chaos-smoke)       CHAOS_SMOKE=1 ;;
    --adversarial-smoke) ADV_SMOKE=1 ;;
    --nightly)           NIGHTLY=1 ;;
    *)                   ARGS+=("$a") ;;
  esac
done

if [ "$NIGHTLY" = "1" ]; then
  LANE="nightly"
  MARKER=()
else
  LANE="tier1"
  MARKER=(-m "not slow")
fi

for DC in 1 2; do
  echo "=== $LANE: pytest (xla_force_host_platform_device_count=$DC) ==="
  T0=$(date +%s)
  XLA_FLAGS="--xla_force_host_platform_device_count=$DC" \
    python -m pytest -x -q "${MARKER[@]+"${MARKER[@]}"}" \
    "${ARGS[@]+"${ARGS[@]}"}" | tee /tmp/ci_pytest_$DC.log
  T1=$(date +%s)
  TAIL=$(grep -E "passed|failed|error" /tmp/ci_pytest_$DC.log | tail -1)
  echo "TEST-SUMMARY lane=$LANE devices=$DC wall_s=$((T1 - T0)) :: $TAIL"
  echo
done

echo "=== fault-determinism: same seed + FaultConfig -> identical traces ==="
# The fault layer adds four PRNG streams (duty, crash, link, abort) to the
# step; this gate pins that a faulted run is a pure function of (seed,
# FaultConfig) — bitwise across repeated runs in one process and across
# the 1- and 2-device sweep shardings.
for DC in 1 2; do
  XLA_FLAGS="--xla_force_host_platform_device_count=$DC" FAULT_DET_DC=$DC \
    python - <<'EOF'
import os

import numpy as np

from repro.configs.fg_faults import harsh
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, sweep

dc = os.environ["FAULT_DET_DC"]
cfg = SimConfig(n_nodes=60, n_slots=160, sample_every=8, faults=harsh())
ps = [paper_params(lam=l, M=1) for l in (0.1, 0.3)]
runs = [sweep.run(ps, cfg, seeds=(0, 1), reduce="trace") for _ in range(2)]
keys = ("availability", "availability_c", "on_frac_c", "fault_events")
for k in keys:
    a = np.asarray(getattr(runs[0], k))
    b = np.asarray(getattr(runs[1], k))
    assert np.array_equal(a, b), f"non-deterministic faulted trace: {k}"
np.savez(f"/tmp/fault_det_{dc}.npz",
         **{k: np.asarray(getattr(runs[0], k)) for k in keys})
print(f"devices={dc}: repeated faulted sweeps bitwise-identical")
EOF
done
python - <<'EOF'
import numpy as np

a = np.load("/tmp/fault_det_1.npz")
b = np.load("/tmp/fault_det_2.npz")
for k in a.files:
    assert np.array_equal(a[k], b[k]), \
        f"faulted trace differs across device counts: {k}"
print("1- and 2-device faulted sweeps bitwise-identical")
EOF

echo
echo "=== learning-smoke: end-to-end Gossip Learning on the sim substrate ==="
# The learning layer (repro.sim.learn) must (a) actually learn — holder
# test accuracy after warmup beats the untrained start — and (b) be a
# pure function of (seed, LearnConfig): two identical runs bitwise equal.
python - <<'EOF'
import numpy as np

from repro.configs.fg_learn import logreg_task
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, sweep

cfg = SimConfig(n_nodes=48, area_side=100.0, rz_radius=50.0, n_slots=480,
                sample_every=8, k_obs=32, learn=logreg_task())
p = paper_params(lam=0.05, Lam=10.0, M=1)
runs = [sweep.run([p], cfg, seeds=(0,), reduce="trace") for _ in range(2)]
for k in ("test_acc", "test_acc_holders", "learn_obs", "theta_var",
          "availability"):
    a, b = np.asarray(getattr(runs[0], k)), np.asarray(getattr(runs[1], k))
    assert np.array_equal(a, b), f"non-deterministic learning trace: {k}"
acc = np.asarray(runs[0].test_acc)[0, 0]
early, late = float(np.mean(acc[:3])), float(np.mean(acc[-3:]))
assert late > early + 0.05, f"no learning: acc {early:.3f} -> {late:.3f}"
print(f"learning smoke OK: acc {early:.3f} -> {late:.3f}, "
      "repeated runs bitwise-identical")
EOF

if [ "$CHAOS_SMOKE" = "1" ]; then
  echo
  echo "=== chaos-smoke: dispatched sweep under kill + hang ==="
  # The dispatcher's core invariant, gated under both device topologies:
  # any chaos schedule yields either reductions bitwise identical to the
  # fault-free in-process sweep, or a correctly-masked subset. Here the
  # schedule (one SIGKILL mid-task, one heartbeat-stopped hang) must
  # fully recover: bitwise equality AND zero quarantined chunks.
  for DC in 1 2; do
    XLA_FLAGS="--xla_force_host_platform_device_count=$DC" CHAOS_DC=$DC \
      python - <<'EOF'
import os
import tempfile
import warnings

import numpy as np

from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, sweep, dispatch

dc = os.environ["CHAOS_DC"]
cfg = SimConfig(n_nodes=40, n_slots=160, sample_every=8)
ps = [paper_params(lam=l, M=1) for l in (0.1, 0.2, 0.3)]
kw = dict(seeds=(0, 1), reduce="mean", chunk_size=1)

ref = sweep.run(ps, cfg, **kw)
chaos = [dispatch.chaos_directive(0, 0, "kill"),
         dispatch.chaos_directive(1, 0, "hang", seconds=60.0)]
policy = dispatch.RetryPolicy(max_attempts=3, lease_ttl_s=3.0,
                              heartbeat_s=0.3)
with warnings.catch_warnings(), tempfile.TemporaryDirectory() as qd:
    warnings.simplefilter("ignore")
    # chaos= only exists on the dispatcher entry point (sweep.run's
    # workers= path forwards here, minus fault injection)
    out = dispatch.run_dispatched(ps, cfg, kw["seeds"],
                                  reduce=kw["reduce"],
                                  chunk_size=kw["chunk_size"],
                                  queue_dir=qd,
                                  chaos=chaos, retry_policy=policy,
                                  workers=2)
for k in ref.stats:
    assert np.array_equal(np.asarray(ref.stats[k]),
                          np.asarray(out.stats[k]), equal_nan=True), \
        f"chaos run diverged from fault-free reductions: {k}"
assert out.coverage.all(), "chaos run left uncovered scenarios"
assert out.quarantined == (), f"chunks quarantined: {out.quarantined}"
tel = out.telemetry
assert tel["expired_leases"] >= 2, "chaos did not exercise lease expiry"
print(f"devices={dc}: chaos (kill+hang) recovered bitwise, "
      f"0 quarantined, {tel['expired_leases']} leases expired, "
      f"{tel['respawns']} workers respawned")
EOF
  done
  echo "OK"
fi

if [ "$ADV_SMOKE" = "1" ]; then
  echo
  echo "=== adversarial-smoke: zero-rate bitwise + defended recovery ==="
  # (a) A config that *names* the Byzantine machinery but arms none of it
  # (honest classes, every defense knob at its off default) must trace
  # the exact same program as faults=None/defense=None — gated on both
  # contact backends so neither merge path pays for the feature.
  python - <<'EOF'
import dataclasses

import numpy as np

from repro.configs.fg_adversarial import honest
from repro.configs.fg_learn import logreg_task
from repro.configs.fg_paper import paper_params
from repro.core.merge import DefenseConfig
from repro.sim import SimConfig, sweep

p = paper_params(lam=0.05, Lam=10.0, M=1)
kw = dict(n_nodes=48, area_side=100.0, rz_radius=50.0, n_slots=240,
          sample_every=8, k_obs=32)
keys = ("availability", "busy_frac", "stored_info", "n_in_rz",
        "test_acc", "test_acc_holders", "learn_obs", "theta_var",
        "merge_stats")
for backend in ("dense", "cells"):
    base_cfg = SimConfig(learn=logreg_task(), contact_backend=backend,
                         **kw)
    zero_cfg = SimConfig(
        learn=dataclasses.replace(logreg_task(), defense=DefenseConfig()),
        faults=honest(), contact_backend=backend, **kw)
    base = sweep.run([p], base_cfg, seeds=(0,), reduce="trace")
    zero = sweep.run([p], zero_cfg, seeds=(0,), reduce="trace")
    for k in keys:
        a, b = np.asarray(getattr(base, k)), np.asarray(getattr(zero, k))
        assert np.array_equal(a, b), \
            f"zero-rate adversarial config diverged ({backend}): {k}"
    assert zero.poisoned_frac is None, \
        "honest config must not carry contamination telemetry"
    print(f"backend={backend}: zero-rate adversarial bitwise-identical "
          "to faults=None/defense=None")
EOF

  # (b) The calibrated clipped defense must hold >= 90% of the clean
  # holder accuracy at the 10% amplified-sign-flip preset (and the
  # undefended run must actually degrade — otherwise the gate is vacuous).
  python - <<'EOF'
import dataclasses

import numpy as np

from repro.configs.fg_adversarial import robust_defense, signflip
from repro.configs.fg_learn import logreg_task
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, sweep
from repro.sim.learn import MS_ATTEMPT_POISON, MS_DISTREJ_POISON

p = paper_params(lam=0.05, Lam=10.0, M=1)
kw = dict(n_nodes=48, area_side=100.0, rz_radius=50.0, n_slots=960,
          sample_every=8, k_obs=32)


def acc_of(cfg):
    out = sweep.run([p], cfg, seeds=(0,), reduce="trace")
    acc = float(np.asarray(out.test_acc_holders)[0, 0, -20:].mean())
    ms = np.asarray(out.merge_stats)[0, 0, -1]
    return acc, ms


clean, _ = acc_of(SimConfig(learn=logreg_task(), **kw))
fc = signflip(frac=0.1)
undef, _ = acc_of(SimConfig(learn=logreg_task(), faults=fc, **kw))
lc_def = dataclasses.replace(logreg_task(), defense=robust_defense())
defended, ms = acc_of(SimConfig(learn=lc_def, faults=fc, **kw))
rej = int(ms[MS_DISTREJ_POISON])
att = int(ms[MS_ATTEMPT_POISON])
print(f"clean={clean:.4f} undefended={undef:.4f} defended={defended:.4f} "
      f"poison merges rejected {rej}/{att}")
assert undef < clean, "sign-flip attack did not degrade the undefended run"
assert defended >= 0.90 * clean, (
    f"defended accuracy {defended:.4f} below 90% of clean {clean:.4f}")
print("defended recovery OK (>= 90% of clean)")
EOF
  echo "OK"
fi

if [ "$NIGHTLY" = "1" ]; then
  echo
  echo "=== nightly: Gossip-Learning capacity-ordering sweep (fig_learning) ==="
  # Full (lambda, T_T) x merge-policy sweep: measured holder accuracy must
  # order the points the same way as the Theorem 2 stored-information
  # capacity. The benchmark's derived line carries ordering_ok; gate on it.
  python -m benchmarks.run --only fig_learning | tee /tmp/fig_learning.out
  grep -q "ordering_ok=True" /tmp/fig_learning.out \
    || { echo "FAIL: measured accuracy ordering disagrees with Theorem 2"; \
         exit 1; }
fi
