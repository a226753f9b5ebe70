"""Fault-tolerant multi-process sweep dispatch: a lease-based work queue.

``sweep.run`` executes a parameter study as a stream of independent chunk
programs — but in one process: a hung or killed worker stalls the whole
study, and a failure is retried exactly once. This module gives the sweep
path the same resilience PR 6 gave the simulated protocol: a coordinator
decomposes the :class:`~repro.sim.sweep.SweepPlan` into chunk *tasks* and
drives N worker *processes* through a filesystem work queue, so the study
completes — degraded but correctly labeled — no matter which workers die.

Everything is plain files under one ``queue_dir``, so the design is
shared-directory multi-host by construction (workers on any machine that
mounts the directory can join; today the coordinator spawns them locally):

``spec.pkl``
    The pickled sweep definition (params, config, seeds, reduction knobs).
    Workers rebuild the *identical* :class:`~repro.sim.sweep._SweepSetup`
    from it, so every process compiles the same chunk program and chunk
    results are bitwise reproducible wherever they run.
``setup.pkl``
    The first worker to finish its setup publishes it here with the
    chunk result shapes (``setup_error.json`` instead when the setup
    raised). The coordinator never initializes a JAX backend — on a TPU
    host the chip belongs to one process, and that is the worker — so it
    takes the sweep plan from this file before it enqueues any task.
``todo/chunk_{c}.{tag}.task``
    One JSON task per pending chunk attempt. Claiming is a single atomic
    ``os.rename`` of the task file into ``leases/`` — exactly one of any
    number of concurrent claimers wins (the losers get ``ENOENT`` and move
    on); there is no lock server and no lock.
``leases/chunk_{c}.{tag}.lease``
    A claimed task. The owning worker renews the lease by touching its
    mtime every ``heartbeat_s`` (a daemon thread, so a busy chunk still
    heartbeats) and writes an ``.owner.json`` sidecar (worker id + pid).
    The coordinator expires a lease whose heartbeat is older than
    ``lease_ttl_s`` — or immediately when the owning worker process is
    known dead — re-enqueueing the chunk with exponential backoff +
    deterministic jitter under the :class:`RetryPolicy`.
``results/step_{c}.npz`` (+ ``.json``)
    Completed chunk reductions in the PR 6 ``checkpoint/ckpt.py`` format —
    the *same* on-disk schema ``sweep.run(checkpoint_dir=)`` writes and
    ``resume=`` reads, with per-array content hashes, the sweep
    fingerprint, and the attempt number in the manifest. The coordinator
    validates every result (hashes, fingerprint, shapes) before accepting
    it; a corrupt write is deleted, costs the chunk an attempt, and the
    chunk re-runs. Chunk programs are pure functions of (chunk, spec), so
    duplicate results are bitwise identical and **first-completed-wins** is
    deterministic.
``failures/chunk_{c}.{tag}.json``
    A worker-side exception record (traceback included). After
    ``max_attempts`` total failures the chunk is **quarantined**
    (``quarantine/chunk_{c}.json`` keeps the attempt history and the last
    traceback) and its rows are NaN/zero-filled and masked out of
    ``SweepSummary.coverage`` — a poison chunk degrades the study, never
    sinks it.
``DONE``
    The coordinator's shutdown marker; idle workers exit when they see it.

**Straggler re-dispatch.** Once ``straggler_min_done`` chunks have
completed, a lease older than ``straggler_factor`` times the
``straggler_quantile`` completion latency gets a *duplicate* task enqueued
(capped by ``max_duplicates``; no attempt is charged) — a slow-but-alive
worker can't stall the tail of the study, and whichever copy finishes
first supplies the (bitwise identical) result.

**Chaos harness.** ``chaos=`` takes a schedule of seeded fault injections
(:func:`chaos_directive`) matched on (chunk, attempt) inside the worker:
``kill`` (SIGKILL mid-task), ``hang`` (stop heartbeating and sleep),
``freeze`` (SIGSTOP self — the frozen-process case), ``slow`` (sleep,
heartbeats continue — the straggler case), ``corrupt`` (write garbage
bytes over the chunk result), ``raise`` (worker-side exception). The
invariant — proved by ``tests/test_dispatch_chaos.py`` and gated by
``scripts/ci.sh --chaos-smoke`` — is that any chaos schedule yields either
reductions bitwise identical to the fault-free single-process
``sweep.run``, or a correctly-masked subset (the uncovered chunks exactly
the quarantined ones).
"""

from __future__ import annotations

import dataclasses
import glob
import hashlib
import json
import os
import pickle
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import warnings

import numpy as np

__all__ = [
    "RetryPolicy", "DispatchError", "run_dispatched", "chaos_directive",
    "claim_task", "enqueue_task", "worker_main",
]


class DispatchError(RuntimeError):
    """The dispatcher could not complete the sweep (e.g. every worker died
    and the respawn budget is exhausted while chunks remain)."""


# --------------------------------------------------------------------------
# retry policy


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Retry/backoff/lease knobs for chunk execution.

    Replaces the historical hardcoded retry-once of ``sweep.run``: the
    default ``max_attempts=2`` preserves that behavior on the in-process
    checkpointed path, while the dispatcher is free to run with more.

    Backoff for attempt ``k`` (1-based count of *failures so far*) is
    ``backoff_base_s * backoff_mult**(k-1)`` capped at ``backoff_max_s``,
    plus a deterministic jitter in ``[0, jitter * backoff)`` derived from
    the (fingerprint, chunk, attempt) — no global RNG, so a re-run backs
    off identically and two chunks never thundering-herd in lockstep.
    """

    max_attempts: int = 2          # total attempts before quarantine
    backoff_base_s: float = 0.25   # first retry delay
    backoff_mult: float = 2.0      # exponential growth per attempt
    backoff_max_s: float = 30.0    # backoff ceiling
    jitter: float = 0.5            # jitter fraction of the backoff
    heartbeat_s: float = 0.5       # worker lease-renewal period
    lease_ttl_s: float = 5.0       # heartbeat age before a lease expires
    poll_s: float = 0.05           # coordinator/worker queue poll period
    straggler_quantile: float = 0.75   # completion-latency quantile ...
    straggler_factor: float = 4.0      # ... times this = re-dispatch age
    straggler_min_done: int = 3    # completions before stragglers re-dispatch
    max_duplicates: int = 1        # duplicate tasks per chunk (stragglers)
    max_respawns: int = 8          # replacement workers the pool may spawn
    stall_timeout_s: float = 60.0  # no progress + no live workers => fail

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if self.lease_ttl_s <= self.heartbeat_s:
            raise ValueError("lease_ttl_s must exceed heartbeat_s")

    def backoff(self, attempt: int, key: str = "") -> float:
        """Delay before re-enqueueing after the ``attempt``-th failure."""
        base = min(
            self.backoff_base_s * self.backoff_mult ** max(attempt - 1, 0),
            self.backoff_max_s,
        )
        if self.jitter <= 0.0:
            return base
        h = hashlib.sha256(f"{key}:{attempt}".encode()).digest()
        u = int.from_bytes(h[:8], "big") / 2.0 ** 64
        return base * (1.0 + self.jitter * u)


# --------------------------------------------------------------------------
# chaos schedule


_CHAOS_ACTIONS = ("kill", "hang", "freeze", "slow", "corrupt", "raise")


def chaos_directive(chunk: int, attempt: int, action: str,
                    seconds: float = 30.0) -> dict:
    """One seeded chaos injection: when a worker claims ``chunk`` at task
    ``attempt``, perform ``action`` (see module docstring). ``seconds``
    parameterizes ``hang``/``freeze``/``slow`` durations."""
    if action not in _CHAOS_ACTIONS:
        raise ValueError(f"unknown chaos action {action!r}; "
                         f"known: {_CHAOS_ACTIONS}")
    return {"chunk": int(chunk), "attempt": int(attempt),
            "action": action, "seconds": float(seconds)}


def _chaos_match(chaos: list[dict], chunk: int, attempt: int) -> dict | None:
    for d in chaos:
        if d["chunk"] == chunk and d["attempt"] == attempt:
            return d
    return None


# --------------------------------------------------------------------------
# queue primitives (plain files; every mutation is one atomic rename)


_DIRS = ("todo", "leases", "results", "failures", "quarantine")


def _q(queue_dir: str, *parts: str) -> str:
    return os.path.join(queue_dir, *parts)


def _init_queue(queue_dir: str) -> None:
    for d in _DIRS:
        os.makedirs(_q(queue_dir, d), exist_ok=True)


def _task_name(chunk: int, attempt: int, dup: int = 0) -> str:
    tag = f"a{attempt}" + (f"d{dup}" if dup else "")
    return f"chunk_{chunk:05d}.{tag}"


def _parse_task_name(name: str) -> tuple[int, int, int]:
    """``chunk_00003.a1d2.task`` -> (3, 1, 2). Tolerates any trailing
    extension (``.task``, ``.lease``, ``.json``, ...)."""
    chunk_s, tag = name.split(".")[:2]
    chunk = int(chunk_s.split("_")[1])
    if "d" in tag:
        a_s, d_s = tag[1:].split("d")
        return chunk, int(a_s), int(d_s)
    return chunk, int(tag[1:]), 0


def _publish(final: str, data: bytes) -> None:
    """Atomically create ``final`` (write temp + rename), so a reader never
    sees a half-written file."""
    tmp = final + f".tmp-{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, final)


def enqueue_task(queue_dir: str, chunk: int, attempt: int,
                 dup: int = 0) -> str:
    """Atomically publish a chunk task into ``todo/``."""
    name = _task_name(chunk, attempt, dup) + ".task"
    final = _q(queue_dir, "todo", name)
    _publish(final, json.dumps({"chunk": chunk, "attempt": attempt,
                                "dup": dup,
                                "enqueued_at": time.time()}).encode())
    return final


def claim_task(queue_dir: str, worker_id: str) -> dict | None:
    """Claim the lowest pending task via atomic rename into ``leases/``.

    The rename is the *entire* claim protocol: of any number of concurrent
    claimers of one task file, exactly one rename succeeds; the rest see
    ``FileNotFoundError`` and try the next task. Returns
    ``{chunk, attempt, dup, lease}`` or ``None`` when nothing is claimable.
    """
    todo = _q(queue_dir, "todo")
    try:
        names = sorted(os.listdir(todo))
    except FileNotFoundError:
        return None
    for name in names:
        if not name.endswith(".task"):
            continue
        lease = _q(queue_dir, "leases", name[:-len(".task")] + ".lease")
        try:
            os.rename(os.path.join(todo, name), lease)
        except FileNotFoundError:
            continue  # lost the race to another claimer — back off to next
        # rename preserves the *task* file's mtime — stamp the claim time
        # so the coordinator never sees a freshly claimed lease as stale
        os.utime(lease)
        chunk, attempt, dup = _parse_task_name(name)
        owner = {"worker": worker_id, "pid": os.getpid(),
                 "claimed_at": time.time()}
        with open(lease + ".owner.json", "w") as f:
            json.dump(owner, f)
        return {"chunk": chunk, "attempt": attempt, "dup": dup,
                "lease": lease}
    return None


def _lease_owner(lease: str) -> dict:
    try:
        with open(lease + ".owner.json") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _remove_lease(lease: str) -> None:
    for p in (lease, lease + ".owner.json"):
        try:
            os.remove(p)
        except FileNotFoundError:
            pass


class _Heartbeat:
    """Daemon thread renewing a lease's mtime every ``interval`` seconds.

    ``pause()`` stops renewals without stopping the thread — the chaos
    ``hang`` action uses it to simulate a worker that is alive but no
    longer making progress (exactly what the coordinator's lease-expiry
    detection must catch)."""

    def __init__(self, lease: str, interval: float):
        self._lease = lease
        self._interval = interval
        self._stop = threading.Event()
        self._paused = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            if not self._paused.is_set():
                try:
                    os.utime(self._lease)  # first beat lands immediately
                except OSError:
                    return  # lease gone (expired under us / task finished)
            if self._stop.wait(self._interval):
                return

    def pause(self):
        self._paused.set()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=2.0)


# --------------------------------------------------------------------------
# results and failure records


def _result_paths(results_dir: str, chunk: int) -> tuple[str, str]:
    base = os.path.join(results_dir, f"step_{chunk:08d}")
    return base + ".npz", base + ".json"


def _write_result(results_dir: str, chunk: int, tree: dict, fp: str,
                  attempt: int, worker_id: str) -> None:
    """Publish a chunk result in the sweep-checkpoint schema (atomic)."""
    from repro.checkpoint.ckpt import save_checkpoint
    from repro.sim.sweep import _fp_array

    save_checkpoint(
        results_dir, chunk,
        dict(tree, fingerprint=_fp_array(fp)),
        meta={"chunk": chunk, "attempt": attempt, "worker": worker_id,
              "fingerprint": fp, "schema": "sweep-chunk-v1"},
        integrity=True, atomic=True,
    )


def _validate_result(results_dir: str, chunk: int, fp: str,
                     expected: dict) -> tuple[dict | None, str | None]:
    """Load + fully validate a published chunk result.

    Returns ``(tree, None)`` on success or ``(None, reason)`` — the
    coordinator treats any reason as a failed attempt (the file is torn,
    corrupt, stale, or shape-drifted) and deletes the files."""
    from repro.checkpoint.ckpt import restore_checkpoint
    from repro.sim.sweep import _fp_array, _tree_mismatch

    npz, _ = _result_paths(results_dir, chunk)
    try:
        like = {k: 0 for k in np.load(npz).files}
        tree, step = restore_checkpoint(npz, like, verify=True)
    except Exception as e:
        return None, f"unreadable or corrupt ({e})"
    saved_fp = tree.pop("fingerprint", None)
    if saved_fp is None or not np.array_equal(saved_fp, _fp_array(fp)):
        return None, "fingerprint mismatch (different sweep)"
    if step != chunk:
        return None, f"chunk index mismatch (file says {step})"
    reason = _tree_mismatch(tree, expected)
    if reason is not None:
        return None, reason
    return tree, None


def _write_failure(queue_dir: str, chunk: int, attempt: int, dup: int,
                   worker_id: str, exc: BaseException) -> None:
    name = _task_name(chunk, attempt, dup) + ".json"
    _publish(_q(queue_dir, "failures", name), json.dumps({
        "chunk": chunk, "attempt": attempt, "dup": dup,
        "worker": worker_id, "time": time.time(),
        "error": repr(exc),
        "traceback": traceback.format_exc(),
    }, indent=1).encode())


# --------------------------------------------------------------------------
# worker process


def _load_spec(queue_dir: str) -> dict:
    with open(_q(queue_dir, "spec.pkl"), "rb") as f:
        return pickle.load(f)


def _setup_from_spec(spec: dict):
    from repro.sim import sweep

    return sweep._prepare(
        list(spec["ps"]), spec["cfg"], spec["seeds"], spec["reduce"],
        spec["warmup_frac"], spec["chunk_size"], spec["quantiles"],
        spec["tau_grid"], spec["n_devices"],
    )


def _prepare_worker(queue_dir: str, spec: dict):
    """Build the sweep setup in this worker and publish it (once) for the
    coordinator; a setup that raises is published as ``setup_error.json``
    and re-raised."""
    import jax

    try:
        setup = _setup_from_spec(spec)
        if len(jax.devices()) < setup.plan.n_devices:
            raise DispatchError(
                f"worker sees {len(jax.devices())} XLA devices but the "
                f"sweep plan needs {setup.plan.n_devices} — start workers "
                "with the same XLA_FLAGS/device topology"
            )
        if not os.path.exists(_q(queue_dir, "setup.pkl")):
            _publish(_q(queue_dir, "setup.pkl"), pickle.dumps(dict(
                setup=setup, expected=setup.expected_shapes())))
    except Exception as e:
        record = {"error": repr(e), "traceback": traceback.format_exc()}
        _publish(_q(queue_dir, "setup_error.json"),
                 json.dumps(record).encode())
        raise
    return setup


def worker_main(queue_dir: str, worker_id: str) -> int:
    """Claim-compute-publish loop of one worker process.

    Meant to run under ``python -m repro.sim.dispatch <queue_dir>`` in a
    process of its own (the coordinator spawns these); everything it needs
    travels through the queue directory, so a worker could equally start
    on another host that mounts it.
    """
    import jax

    from repro.launch.cache import enable_compile_cache
    from repro.sim import sweep

    spec = _load_spec(queue_dir)
    policy: RetryPolicy = spec["policy"]
    results_dir: str = spec.get("results_dir") or _q(queue_dir, "results")
    enable_compile_cache(spec.get("xla_cache_dir"))

    setup = _prepare_worker(queue_dir, spec)
    fp = sweep._setup_fingerprint(setup, spec["seeds"])
    keys = setup.keys()
    chaos: list[dict] = spec.get("chaos") or []
    worker_fn = None  # compile lazily on the first claimed task

    while True:
        if os.path.exists(_q(queue_dir, "DONE")):
            return 0
        task = claim_task(queue_dir, worker_id)
        if task is None:
            time.sleep(policy.poll_s)
            continue
        chunk, attempt, dup = task["chunk"], task["attempt"], task["dup"]
        hb = _Heartbeat(task["lease"], policy.heartbeat_s)
        directive = _chaos_match(chaos, chunk, attempt) if dup == 0 else None
        try:
            if directive is not None:
                act, secs = directive["action"], directive["seconds"]
                if act == "kill":
                    os.kill(os.getpid(), signal.SIGKILL)
                elif act == "freeze":
                    # stopped processes don't heartbeat: the thread is
                    # frozen with the rest of the process
                    os.kill(os.getpid(), signal.SIGSTOP)
                elif act == "hang":
                    hb.pause()
                    time.sleep(secs)
                elif act == "slow":
                    time.sleep(secs)
                elif act == "raise":
                    raise RuntimeError(
                        f"chaos: injected failure on chunk {chunk} "
                        f"attempt {attempt}"
                    )
            if worker_fn is None:
                worker_fn = setup.worker()
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                out = worker_fn(keys, setup.chunk_params(chunk))
            hc = jax.tree_util.tree_map(np.asarray, out)
            if directive is not None and directive["action"] == "corrupt":
                # a torn/garbage write at the exact publish point: the
                # npz name appears with trash bytes instead of a result
                npz, mpath = _result_paths(results_dir, chunk)
                with open(npz, "wb") as f:
                    f.write(b"\x00garbage-not-an-npz\xff" * 64)
                with open(mpath, "w") as f:
                    f.write("{not json")
            else:
                _write_result(results_dir, chunk, hc, fp, attempt,
                              worker_id)
            _remove_lease(task["lease"])
        except Exception as e:  # noqa: BLE001 — everything becomes a record
            _write_failure(queue_dir, chunk, attempt, dup, worker_id, e)
            _remove_lease(task["lease"])
        finally:
            hb.stop()


# --------------------------------------------------------------------------
# coordinator


class _WorkerPool:
    """Local worker processes + respawn accounting.

    The coordinator is deliberately ignorant of *how* workers run — it only
    reads the queue — but when it spawned them itself it can also reap
    exit codes, SIGKILL expired-lease owners, and respawn replacements."""

    def __init__(self, queue_dir: str, n_workers: int, policy: RetryPolicy,
                 env: dict):
        self.queue_dir = queue_dir
        self.policy = policy
        self.env = env
        self.procs: dict[str, subprocess.Popen] = {}
        self.respawns = 0
        self._next = 0
        for _ in range(n_workers):
            self.spawn()

    def spawn(self) -> str:
        wid = f"w{self._next}"
        self._next += 1
        self.procs[wid] = subprocess.Popen(
            [sys.executable, "-m", "repro.sim.dispatch", self.queue_dir,
             "--worker-id", wid],
            env=self.env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE,
        )
        return wid

    def reap_and_respawn(self) -> list[str]:
        """Collect exited workers; spawn replacements within the budget.
        Returns the ids of workers found dead this call."""
        dead = [wid for wid, p in self.procs.items() if p.poll() is not None]
        for wid in dead:
            p = self.procs.pop(wid)
            if p.returncode not in (0,):
                err = (p.stderr.read() or b"").decode(errors="replace")
                if err.strip():
                    warnings.warn(
                        f"dispatch worker {wid} died "
                        f"(exit {p.returncode}): ...{err.strip()[-500:]}"
                    )
            if (not os.path.exists(_q(self.queue_dir, "DONE"))
                    and self.respawns < self.policy.max_respawns):
                self.respawns += 1
                self.spawn()
        return dead

    def kill_owner(self, owner: dict) -> None:
        """SIGKILL the (local) process owning an expired lease, so a hung
        worker can't later double-publish or hold the CPU."""
        wid, pid = owner.get("worker"), owner.get("pid")
        p = self.procs.get(wid)
        if p is not None and p.pid == pid and p.poll() is None:
            p.kill()

    def alive(self) -> int:
        return sum(1 for p in self.procs.values() if p.poll() is None)

    def shutdown(self):
        # workers exit on DONE; anything still running (hung/frozen) is
        # killed — SIGKILL works on SIGSTOPped processes too
        deadline = time.time() + 2.0
        while time.time() < deadline and self.alive():
            time.sleep(0.02)
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            try:
                p.wait(timeout=5.0)
            except subprocess.TimeoutExpired:  # pragma: no cover
                pass
            if p.stderr is not None:
                p.stderr.close()


#: PCI device ids of TPU chips (Google's vendor id also covers its NICs).
_TPU_PCI_IDS = frozenset(
    {"0x0027", "0x0056", "0x005e", "0x0062", "0x0063", "0x006f", "0x0076"})


def _tpu_chips() -> int:
    """TPU chips attached to this host, counted from PCI (the probe JAX's
    own TPU detection uses) so the coordinator initializes no JAX backend.
    0 where ``JAX_PLATFORMS`` leaves the TPU out."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    n = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor) as f:
                if f.read().strip() != "0x1ae0":
                    continue
            with open(os.path.join(os.path.dirname(vendor), "device")) as f:
                n += f.read().strip() in _TPU_PCI_IDS
        except OSError:
            continue
    return n


def _await_setup(queue_dir: str, pool: _WorkerPool, policy: RetryPolicy):
    """Wait for a worker to publish the sweep setup: ``(setup, expected)``.

    Raises :class:`DispatchError` with the worker's traceback when the
    setup failed there, and when no worker can publish it."""
    t0 = time.monotonic()
    while True:
        err = _q(queue_dir, "setup_error.json")
        if os.path.exists(err):
            with open(err) as f:
                rec = json.load(f)
            raise DispatchError(
                f"sweep setup failed in a worker: {rec['error']}\n"
                f"{rec['traceback']}")
        try:
            with open(_q(queue_dir, "setup.pkl"), "rb") as f:
                got = pickle.load(f)
            return got["setup"], got["expected"]
        except FileNotFoundError:
            pass
        pool.reap_and_respawn()
        if pool.alive() == 0 and pool.respawns >= policy.max_respawns:
            raise DispatchError(
                "no live workers and respawn budget exhausted before any "
                "worker published the sweep setup")
        if time.monotonic() - t0 > policy.stall_timeout_s:
            raise DispatchError(
                f"no worker published the sweep setup within "
                f"{policy.stall_timeout_s}s")
        time.sleep(policy.poll_s)


def _spawn_env(n_devices: int | None) -> dict:
    """Worker environment: inherit, make ``repro`` importable by absolute
    path (the parent may run with a relative ``PYTHONPATH``), and pin the
    device topology so worker meshes match the coordinator's plan."""
    import repro

    env = dict(os.environ)
    # repro may be a namespace package (__file__ is None) — __path__ works
    # for both layouts
    pkg_dir = (os.path.dirname(repro.__file__) if repro.__file__
               else next(iter(repro.__path__)))
    pkg_root = os.path.dirname(os.path.abspath(pkg_dir))
    parts = [pkg_root] + [p for p in env.get("PYTHONPATH", "").split(":")
                          if p]
    env["PYTHONPATH"] = ":".join(dict.fromkeys(parts))
    return env


def run_dispatched(
    ps,
    cfg,
    seeds=(0,),
    *,
    reduce: str = "trace",
    warmup_frac: float | None = None,
    chunk_size: int | None = None,
    quantiles=(0.1, 0.5, 0.9),
    tau_grid=None,
    n_devices: int | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    retry_policy: RetryPolicy | None = None,
    workers: int = 2,
    queue_dir: str | None = None,
    chaos: list[dict] | None = None,
    xla_cache_dir: str | None = None,
):
    """Run a sweep through the lease-based multi-process dispatcher.

    Same contract and return types as :func:`repro.sim.sweep.run` (which
    forwards here for ``workers=``), plus:

    Args:
      workers:    worker processes to spawn (the pool respawns dead ones
                  up to ``retry_policy.max_respawns``).
      queue_dir:  work-queue directory (see module docstring for layout).
                  Defaults to ``checkpoint_dir`` when given — the
                  dispatcher's results *are* sweep chunk checkpoints, so
                  ``sweep.run(checkpoint_dir=..., resume=True)`` can
                  finish or reuse a dispatched study and vice versa — else
                  a fresh temp dir.
      resume:     reuse valid fingerprint-matching chunk results already
                  in the queue's ``results/`` dir (skipping their tasks).
      chaos:      fault-injection schedule (:func:`chaos_directive`) shipped
                  to the workers — the chaos harness. Directives match
                  non-duplicate tasks by (chunk, attempt).
      xla_cache_dir: persistent XLA compile-cache directory shared by the
                  workers (default: ``repro.launch.cache``'s — the
                  ``JAX_COMPILATION_CACHE_DIR`` variable where set, which
                  also overrides this argument, else ``.jax_cache/`` in
                  the checkout) — a respawned worker (or a second sweep
                  over the same config) skips recompilation, which is most
                  of a fresh process's cost.

    The coordinator (the calling process) initializes no JAX backend: the
    workers build the sweep setup and publish it (module docstring). A
    TPU host runs one worker, which shards its chunks over all the host's
    chips; more raise :class:`DispatchError` before anything starts.

    Returns:
      ``BatchSimOutputs`` / :class:`~repro.sim.sweep.SweepSummary` with
      ``coverage`` marking the scenario rows whose chunks completed,
      ``quarantined`` the poison chunks, and ``telemetry`` the per-chunk
      attempt/latency/requeue records plus pool-level counters.
    """
    from repro.sim import sweep

    policy = retry_policy if retry_policy is not None else RetryPolicy(
        max_attempts=3)
    if workers < 1:
        raise ValueError("workers must be >= 1")
    chips = _tpu_chips()
    if chips and workers > 1:
        raise DispatchError(
            f"workers={workers} on a host with {chips} TPU chip(s): a "
            "worker process opens every chip of its host and a chip "
            "belongs to one process, so a TPU host runs one dispatch "
            "worker (its plan shards over all of the host's chips)"
        )
    ps, _, _ = sweep._validate(ps, reduce, tau_grid)

    own_queue = queue_dir is None and checkpoint_dir is None
    if queue_dir is None:
        # queue bookkeeping under .queue/ keeps the checkpoint directory
        # itself in the plain sweep-resume layout (step_*.npz at the root)
        queue_dir = (os.path.join(checkpoint_dir, ".queue")
                     if checkpoint_dir is not None
                     else tempfile.mkdtemp(prefix="fg-dispatch-"))
    results_dir = (checkpoint_dir if checkpoint_dir is not None
                   else _q(queue_dir, "results"))
    _init_queue(queue_dir)
    os.makedirs(results_dir, exist_ok=True)
    done_marker = _q(queue_dir, "DONE")
    # drop stale queue state from a previous (killed) coordinator before
    # any worker starts: tasks, leases, failure records and the published
    # setup are per-run bookkeeping, results are not
    for name in ("DONE", "setup.pkl", "setup_error.json"):
        if os.path.exists(_q(queue_dir, name)):
            os.remove(_q(queue_dir, name))
    for d in ("todo", "leases", "failures"):
        for name in os.listdir(_q(queue_dir, d)):
            try:
                os.remove(_q(queue_dir, d, name))
            except FileNotFoundError:  # pragma: no cover
                pass

    # ---- publish the sweep spec, start the workers ------------------------
    spec = dict(
        ps=tuple(ps), cfg=cfg, seeds=tuple(seeds), reduce=reduce,
        warmup_frac=warmup_frac, chunk_size=chunk_size,
        quantiles=tuple(quantiles), tau_grid=tau_grid, n_devices=n_devices,
        policy=policy, chaos=list(chaos or ()),
        xla_cache_dir=xla_cache_dir, results_dir=results_dir,
    )
    _publish(_q(queue_dir, "spec.pkl"), pickle.dumps(spec))
    pool = _WorkerPool(queue_dir, workers, policy, _spawn_env(n_devices))
    try:
        setup, expected = _await_setup(queue_dir, pool, policy)
        plan = setup.plan
        fp = sweep._setup_fingerprint(setup, seeds)

        # ---- resume: accept pre-existing valid results ------------------
        results: dict[int, dict] = {}
        telemetry: dict = {
            "chunks": {c: {"attempts": 0, "requeues": 0, "duplicates": 0}
                       for c in range(plan.n_chunks)},
            "expired_leases": 0, "corrupt_results": 0,
            "worker_failures": 0, "respawns": 0, "quarantine": {},
        }
        if resume:
            for c, tree in sweep._load_chunks(
                    results_dir, fp, plan.n_chunks,
                    expected=expected).items():
                results[c] = tree
                telemetry["chunks"][c]["resumed"] = True

        attempts = {c: 0 for c in range(plan.n_chunks)}  # failures so far
        backoff_until: dict[int, float] = {}             # chunk -> mono time
        claim_t: dict[tuple[int, int, int], float] = {}  # task -> mono time
        first_enq: dict[int, float] = {}
        latencies: list[float] = []
        quarantined: dict[int, dict] = {}
        seen_failures: set[str] = set()
        invalid_results: set[int] = set()

        now = time.monotonic
        for c in range(plan.n_chunks):
            if c not in results:
                enqueue_task(queue_dir, c, 0)
                first_enq[c] = now()
        last_progress = now()

        def outstanding():
            return [c for c in range(plan.n_chunks)
                    if c not in results and c not in quarantined]

        def fail_attempt(c: int, reason: str, *, requeue_kind: str):
            """Charge the chunk an attempt; back off + re-enqueue or
            quarantine."""
            nonlocal last_progress
            attempts[c] += 1
            last_progress = now()
            if attempts[c] >= policy.max_attempts:
                record = {
                    "chunk": c, "attempts": attempts[c], "reason": reason,
                    "time": time.time(),
                }
                fail_file = None
                for name in sorted(os.listdir(_q(queue_dir, "failures")),
                                   reverse=True):
                    if name.startswith(f"chunk_{c:05d}."):
                        fail_file = _q(queue_dir, "failures", name)
                        break
                if fail_file is not None:
                    try:
                        with open(fail_file) as f:
                            record["last_failure"] = json.load(f)
                    except (OSError, ValueError):  # pragma: no cover
                        pass
                _publish(_q(queue_dir, "quarantine", f"chunk_{c:05d}.json"),
                         json.dumps(record, indent=1).encode())
                quarantined[c] = record
                telemetry["quarantine"][c] = record
                warnings.warn(
                    f"dispatch chunk {c} quarantined after {attempts[c]} "
                    f"attempts: {reason}"
                )
            else:
                delay = policy.backoff(attempts[c], key=f"{fp}:{c}")
                backoff_until[c] = now() + delay
                telemetry["chunks"][c]["requeues"] += 1

        while outstanding():
            progressed = False

            # 1. collect + validate published results
            for c in list(outstanding()):
                npz, _ = _result_paths(results_dir, c)
                if not os.path.exists(npz):
                    continue
                tree, reason = _validate_result(results_dir, c, fp, expected)
                if tree is not None:
                    results[c] = tree
                    tc = telemetry["chunks"][c]
                    tc["attempts"] = attempts[c] + 1
                    lat = now() - first_enq.get(c, now())
                    tc["latency_s"] = round(lat, 4)
                    latencies.append(lat)
                    backoff_until.pop(c, None)
                    invalid_results.discard(c)
                    progressed = True
                    continue
                if c in invalid_results:
                    continue  # already charged; waiting for the re-run
                invalid_results.add(c)
                telemetry["corrupt_results"] += 1
                for p in _result_paths(results_dir, c):
                    try:
                        os.remove(p)
                    except FileNotFoundError:
                        pass
                invalid_results.discard(c)
                warnings.warn(
                    f"dispatch chunk {c} published an invalid result "
                    f"({reason}); discarding and re-dispatching"
                )
                fail_attempt(c, f"invalid result: {reason}",
                             requeue_kind="corrupt")
                progressed = True

            # 2. worker-side failure records
            try:
                fail_names = sorted(os.listdir(_q(queue_dir, "failures")))
            except FileNotFoundError:  # pragma: no cover
                fail_names = []
            for name in fail_names:
                if name in seen_failures or not name.endswith(".json"):
                    continue
                seen_failures.add(name)
                c, attempt, dup = _parse_task_name(name)
                if c in results or c in quarantined:
                    continue
                try:
                    with open(_q(queue_dir, "failures", name)) as f:
                        rec = json.load(f)
                except (OSError, ValueError):
                    rec = {"error": "unreadable failure record"}
                telemetry["worker_failures"] += 1
                warnings.warn(
                    f"dispatch chunk {c} attempt {attempt} failed in "
                    f"worker {rec.get('worker')}: {rec.get('error')}"
                )
                claim_t.pop((c, attempt, dup), None)
                if dup == 0:
                    fail_attempt(c, rec.get("error", "worker failure"),
                                 requeue_kind="failure")
                progressed = True

            # 3. lease expiry (dead or stalled workers)
            dead_now = set(pool.reap_and_respawn())
            telemetry["respawns"] = pool.respawns
            try:
                lease_names = sorted(os.listdir(_q(queue_dir, "leases")))
            except FileNotFoundError:  # pragma: no cover
                lease_names = []
            for name in lease_names:
                if not name.endswith(".lease"):
                    continue
                lease = _q(queue_dir, "leases", name)
                c, attempt, dup = _parse_task_name(name)
                key = (c, attempt, dup)
                claim_t.setdefault(key, now())
                if c in results or c in quarantined:
                    _remove_lease(lease)
                    claim_t.pop(key, None)
                    continue
                owner = _lease_owner(lease)
                try:
                    age = time.time() - os.stat(lease).st_mtime
                except FileNotFoundError:
                    continue  # completed/failed between listing and stat
                # require the *observed* lease age (our own monotonic
                # clock, from first sighting) to exceed the TTL as well —
                # a just-claimed lease whose heartbeat hasn't landed yet
                # must never be expired on its inherited file mtime
                expired = (age > policy.lease_ttl_s
                           and now() - claim_t[key] > policy.lease_ttl_s)
                if owner.get("worker") in dead_now:
                    expired = True  # owner's exit observed: expire now
                if not expired:
                    continue
                telemetry["expired_leases"] += 1
                warnings.warn(
                    f"dispatch lease for chunk {c} (attempt {attempt}"
                    f"{', duplicate' if dup else ''}) expired — worker "
                    f"{owner.get('worker', '?')} dead or stalled; "
                    "re-dispatching"
                )
                pool.kill_owner(owner)
                _remove_lease(lease)
                claim_t.pop(key, None)
                if dup == 0:
                    fail_attempt(c, "lease expired (worker dead/stalled)",
                                 requeue_kind="expiry")
                progressed = True

            # 4. straggler re-dispatch: duplicate long-running leases
            if len(latencies) >= policy.straggler_min_done:
                q = float(np.quantile(np.asarray(latencies),
                                      policy.straggler_quantile))
                deadline = max(policy.straggler_factor * q,
                               4 * policy.heartbeat_s)
                for key, t0 in list(claim_t.items()):
                    c, attempt, dup = key
                    if (c in results or c in quarantined or dup > 0
                            or now() - t0 <= deadline):
                        continue
                    tc = telemetry["chunks"][c]
                    if tc["duplicates"] >= policy.max_duplicates:
                        continue
                    tc["duplicates"] += 1
                    enqueue_task(queue_dir, c, attempt,
                                 dup=tc["duplicates"])
                    warnings.warn(
                        f"dispatch chunk {c} is a straggler "
                        f"({now() - t0:.2f}s > {deadline:.2f}s); "
                        "re-dispatching a duplicate (first result wins)"
                    )

            # 5. release chunks whose backoff elapsed
            for c, t_ok in list(backoff_until.items()):
                if c in results or c in quarantined:
                    backoff_until.pop(c)
                    continue
                if now() >= t_ok:
                    backoff_until.pop(c)
                    enqueue_task(queue_dir, c, attempts[c])
                    first_enq.setdefault(c, now())

            if progressed:
                last_progress = now()
            elif (pool.alive() == 0
                  and pool.respawns >= policy.max_respawns):
                raise DispatchError(
                    f"no live workers and respawn budget exhausted with "
                    f"{len(outstanding())} chunk(s) outstanding"
                )
            elif now() - last_progress > policy.stall_timeout_s:
                raise DispatchError(
                    f"dispatch stalled: no progress in "
                    f"{policy.stall_timeout_s}s with "
                    f"{len(outstanding())} chunk(s) outstanding"
                )
            time.sleep(policy.poll_s)
    finally:
        _publish(done_marker, b"done")
        pool.shutdown()

    host_chunks = []
    for c in range(plan.n_chunks):
        host_chunks.append(results.get(c, sweep._fill_chunk(expected)))
    out = sweep._finalize(
        setup, host_chunks, devices_used=plan.n_devices,
        failed=sorted(quarantined), quarantined=sorted(quarantined),
        telemetry=telemetry,
    )
    if own_queue:
        import shutil

        shutil.rmtree(queue_dir, ignore_errors=True)
    return out


# --------------------------------------------------------------------------
# CLI: the worker entry point


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.sim.dispatch",
        description="Sweep-dispatch worker: claims chunk tasks from a "
                    "filesystem work queue (see repro.sim.dispatch).",
    )
    ap.add_argument("queue_dir")
    ap.add_argument("--worker-id", default=f"w-pid{os.getpid()}")
    args = ap.parse_args(argv)
    return worker_main(args.queue_dir, args.worker_id)


if __name__ == "__main__":
    sys.exit(_main())
