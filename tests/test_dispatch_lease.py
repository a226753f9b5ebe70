"""Lease-queue primitives of ``repro.sim.dispatch``.

The claim protocol is a single atomic ``os.rename`` — these tests pin its
two load-bearing guarantees without spinning up a full dispatched sweep:

1. of any number of *concurrent* claimers of one task, exactly one wins
   (the rest observe ``ENOENT`` and move on);
2. a lease stops being renewed the moment its owner stops running — a
   SIGSTOP'd worker process freezes its heartbeat thread with it, the
   lease's mtime age crosses ``lease_ttl_s``, and the coordinator-side
   release (remove + re-enqueue) makes the chunk claimable again.

Plus the :class:`~repro.sim.dispatch.RetryPolicy` backoff arithmetic:
deterministic jitter, exponential growth, hard cap.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from repro.sim import dispatch
from repro.sim.dispatch import RetryPolicy


def _mk_queue(tmp_path):
    qd = str(tmp_path / "queue")
    dispatch._init_queue(qd)
    return qd


# --------------------------------------------------------------------------
# claim atomicity
# --------------------------------------------------------------------------


def test_concurrent_claimers_exactly_one_wins(tmp_path):
    qd = _mk_queue(tmp_path)
    dispatch.enqueue_task(qd, chunk=7, attempt=1)

    n = 16
    barrier = threading.Barrier(n)
    wins: list[dict] = []
    lock = threading.Lock()

    def claim(i):
        barrier.wait()  # maximize rename contention
        got = dispatch.claim_task(qd, f"w{i}")
        if got is not None:
            with lock:
                wins.append(got)

    threads = [threading.Thread(target=claim, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert len(wins) == 1
    assert (wins[0]["chunk"], wins[0]["attempt"], wins[0]["dup"]) == (7, 1, 0)
    assert os.path.exists(wins[0]["lease"])
    assert os.listdir(os.path.join(qd, "todo")) == []


def test_claim_lowest_chunk_first_and_name_roundtrip(tmp_path):
    qd = _mk_queue(tmp_path)
    for c, a, d in [(3, 0, 0), (1, 2, 0), (2, 1, 3)]:
        dispatch.enqueue_task(qd, c, a, dup=d)
        name = dispatch._task_name(c, a, d) + ".task"
        assert dispatch._parse_task_name(name) == (c, a, d)
    # failure-record and sidecar names parse too
    assert dispatch._parse_task_name("chunk_00002.a1d3.json") == (2, 1, 3)
    assert dispatch._parse_task_name(
        "chunk_00001.a2.lease.owner.json") == (1, 2, 0)

    order = [dispatch.claim_task(qd, "w")["chunk"] for _ in range(3)]
    assert order == [1, 2, 3]
    assert dispatch.claim_task(qd, "w") is None


def test_fresh_claim_mtime_is_now_not_task_age(tmp_path):
    """Rename preserves mtime, so the claim stamps the lease: a lease
    claimed long after its task was enqueued must not look expired."""
    qd = _mk_queue(tmp_path)
    task = dispatch.enqueue_task(qd, 0, 0)
    stale = time.time() - 3600.0
    os.utime(task, (stale, stale))
    got = dispatch.claim_task(qd, "w")
    assert time.time() - os.stat(got["lease"]).st_mtime < 5.0


# --------------------------------------------------------------------------
# heartbeats and expiry
# --------------------------------------------------------------------------


def test_heartbeat_renews_until_paused(tmp_path):
    lease = str(tmp_path / "chunk_00000.a0.lease")
    open(lease, "w").close()
    old = time.time() - 100.0
    os.utime(lease, (old, old))

    hb = dispatch._Heartbeat(lease, interval=0.05)
    try:
        time.sleep(0.3)
        assert time.time() - os.stat(lease).st_mtime < 1.0  # renewed
        hb.pause()
        time.sleep(0.1)  # let an in-flight beat drain
        frozen = os.stat(lease).st_mtime
        time.sleep(0.3)
        assert os.stat(lease).st_mtime == frozen  # no renewals while paused
    finally:
        hb.stop()


_STOPPED_WORKER = r"""
import sys, time
from repro.sim import dispatch
qd = sys.argv[1]
task = dispatch.claim_task(qd, "stopme")
assert task is not None
hb = dispatch._Heartbeat(task["lease"], interval=0.05)
print("CLAIMED", flush=True)
time.sleep(600)
"""


@pytest.mark.skipif(not hasattr(signal, "SIGSTOP"), reason="needs SIGSTOP")
def test_sigstopped_worker_lease_expires_and_releases(tmp_path):
    """SIGSTOP freezes the whole process — heartbeat thread included —
    so the lease's mtime ages past the TTL and the coordinator-side
    release (remove lease + re-enqueue) makes the chunk claimable again."""
    qd = _mk_queue(tmp_path)
    dispatch.enqueue_task(qd, 0, 0)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in sys.path if p) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-c", _STOPPED_WORKER, qd],
        stdout=subprocess.PIPE, env=env, text=True,
    )
    try:
        assert proc.stdout.readline().strip() == "CLAIMED"
        lease = os.path.join(qd, "leases", "chunk_00000.a0.lease")
        assert os.path.exists(lease)

        os.kill(proc.pid, signal.SIGSTOP)
        ttl = 0.6
        time.sleep(3 * ttl)
        age = time.time() - os.stat(lease).st_mtime
        assert age > ttl, "frozen worker kept heartbeating?"

        # coordinator-side release: remove the expired lease, re-enqueue
        # the chunk at the next attempt — claimable by anyone again
        dispatch._remove_lease(lease)
        dispatch.enqueue_task(qd, 0, 1)
        got = dispatch.claim_task(qd, "w2")
        assert got is not None and (got["chunk"], got["attempt"]) == (0, 1)
    finally:
        try:
            os.kill(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=10)
        if proc.stdout is not None:
            proc.stdout.close()


# --------------------------------------------------------------------------
# retry policy
# --------------------------------------------------------------------------


def test_backoff_deterministic_monotone_capped():
    pol = RetryPolicy(max_attempts=8, backoff_base_s=0.25, backoff_mult=2.0,
                      backoff_max_s=2.0, jitter=0.5)
    delays = [pol.backoff(k, key="fp:3") for k in range(1, 9)]
    assert delays == [pol.backoff(k, key="fp:3") for k in range(1, 9)]
    bases = [min(0.25 * 2.0 ** (k - 1), 2.0) for k in range(1, 9)]
    for d, b in zip(delays, bases):
        assert b <= d < 1.5 * b  # jitter in [0, 0.5) of the base
    assert pol.backoff(1, key="a") != pol.backoff(1, key="b")
    nojit = RetryPolicy(jitter=0.0)
    assert nojit.backoff(3) == min(0.25 * 4.0, nojit.backoff_max_s)


def test_retry_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(heartbeat_s=2.0, lease_ttl_s=1.0)


# --------------------------------------------------------------------------
# the coordinator stays off the device
# --------------------------------------------------------------------------


_NO_BACKEND_COORDINATOR = r"""
import sys
from jax._src import xla_bridge
from repro.configs.fg_paper import paper_params
from repro.sim import SimConfig, dispatch

seen = []
spawn = dispatch._WorkerPool.spawn

def recording_spawn(self):
    seen.append(xla_bridge.backends_are_initialized())
    return spawn(self)

dispatch._WorkerPool.spawn = recording_spawn
out = dispatch.run_dispatched(
    [paper_params(lam=0.1, M=1), paper_params(lam=0.2, M=1)],
    SimConfig(n_nodes=40, n_slots=160, sample_every=8), (0, 1),
    reduce="mean", chunk_size=1, workers=1, queue_dir=sys.argv[1])
assert seen and not any(seen), seen
assert not xla_bridge.backends_are_initialized()
assert out.coverage.all() and out.devices_used == 1
print("NO-BACKEND-OK", out.stats["availability"].shape)
"""


def _src_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in sys.path if p) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_coordinator_spawns_workers_without_a_jax_backend(tmp_path):
    """On a TPU host the chip belongs to one process: the coordinator must
    not have initialized a JAX backend when it starts a worker (nor later —
    the workers publish the sweep setup it needs)."""
    out = subprocess.run(
        [sys.executable, "-c", _NO_BACKEND_COORDINATOR, str(tmp_path / "q")],
        env=_src_env(), capture_output=True, text=True, timeout=300,
    )
    assert "NO-BACKEND-OK (2, 2, 1)" in out.stdout, out.stdout + out.stderr


def test_tpu_host_refuses_a_second_worker_before_spawning(monkeypatch,
                                                          tmp_path):
    from repro.configs.fg_paper import paper_params
    from repro.sim import SimConfig

    assert dispatch._tpu_chips() == 0  # JAX_PLATFORMS=cpu hides any TPU
    monkeypatch.setattr(dispatch, "_tpu_chips", lambda: 4)

    def no_spawn(self):
        raise AssertionError("a worker was spawned")

    monkeypatch.setattr(dispatch._WorkerPool, "spawn", no_spawn)
    with pytest.raises(dispatch.DispatchError, match="one dispatch worker"):
        dispatch.run_dispatched(
            [paper_params(lam=0.1, M=1)], SimConfig(n_nodes=40, n_slots=160),
            (0,), reduce="mean", workers=2, queue_dir=str(tmp_path / "q"))


def test_worker_setup_failure_surfaces_in_the_coordinator(tmp_path):
    """A sweep setup that raises in the worker (here: a plan for more
    devices than the worker sees) fails the dispatch with the worker's
    error, instead of respawning workers until the budget runs out."""
    from repro.configs.fg_paper import paper_params
    from repro.sim import SimConfig

    with pytest.raises(dispatch.DispatchError,
                       match="sweep setup failed in a worker.*XLA devices"):
        dispatch.run_dispatched(
            [paper_params(lam=0.1, M=1)], SimConfig(n_nodes=40, n_slots=160),
            (0,), reduce="mean", workers=1, n_devices=64,
            queue_dir=str(tmp_path / "q"))
