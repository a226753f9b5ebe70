#!/usr/bin/env python3
"""Where :func:`repro.sim.compute.take_nodes`'s MXU read overtakes its
select: a sweep over the words per row, on one TPU chip.

    python scripts/take_crossover.py [--reps 400] [--out FILE]

For each table length (200 rows, the §VI deployment's nodes; 64 rows, its
observation ring) and each width in ``WORDS`` it times the two one-hot
reads, ``compute._take_select`` and ``compute._take_mxu``, of uint32 rows
by 200 readers, batched under ``vmap`` over ``RUNS`` runs as in a sweep
chunk. Then the same for the two wide reads of the M = 25 slot step as
the engine makes them: ``(order_seed, snap_has, snap)`` (1 uint32, 25
bool and 50 uint32 words a row, per run) and ``obs_birth.T`` (64 x 25
float32, shared by the runs). Each timed program scans ``--reps`` reads
with fresh indices and a table that changes every step, so nothing is
hoisted out of the loop; a read's time is the program's over ``--reps``,
the best of ``TRIALS`` calls that end in ``block_until_ready``. Every
read is checked bitwise against ``table[idx]``.

Prints one JSON line per shape and a last line with the crossover: the
smallest width at which the MXU read is faster at every table length.
Exits 2 where JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.sim import compute  # noqa: E402

ROWS = (200, 64)
WORDS = (1, 2, 3, 4, 5, 6, 8, 12, 16, 25, 36, 50, 76)
READERS, RUNS, TRIALS = 200, 20, 5
PATHS = {"onehot": compute._take_select, "mxu": compute._take_mxu}


def _program(read, shared: bool, reps: int):
    """``reps`` reads of ``read`` over ``RUNS`` runs; the tables step by
    one each read, the XOR of every read comes out."""
    batched = jax.vmap(read, in_axes=(None if shared else 0, 0))

    def step(carry, idx):
        tables, acc = carry
        got = batched(tables, idx)
        acc = tuple(a ^ _u32(g) for a, g in zip(acc, got))
        tables = tuple(_bump(t) for t in tables)
        return (tables, acc), None

    def run(tables, idxs, acc):
        return jax.lax.scan(step, (tables, acc), idxs)[0]

    return jax.jit(run)


def _u32(x):
    return (x.astype(jnp.uint32) if x.dtype == jnp.bool_
            else jax.lax.bitcast_convert_type(x, jnp.uint32))


def _bump(t):
    if t.dtype == jnp.bool_:
        return ~t
    w = jax.lax.bitcast_convert_type(t, jnp.uint32) + jnp.uint32(1)
    return jax.lax.bitcast_convert_type(w, t.dtype)


def _time(read, tables, idxs, shared: bool) -> float:
    got = jax.vmap(read, in_axes=(None if shared else 0, 0))(tables, idxs[0])
    want = jax.vmap(lambda ts, i: tuple(t[i] for t in ts),
                    in_axes=(None if shared else 0, 0))(tables, idxs[0])
    for g, w in zip(got, want):
        if not np.array_equal(np.asarray(_u32(g)), np.asarray(_u32(w))):
            raise AssertionError(f"{read.__name__} is not table[idx]")
    fn = _program(read, shared, idxs.shape[0])
    acc = tuple(jnp.zeros((RUNS, READERS) + t.shape[1 + (not shared):],
                          jnp.uint32) for t in tables)
    jax.block_until_ready(fn(tables, idxs, acc))           # compile
    best = float("inf")
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(tables, idxs, acc))
        best = min(best, time.perf_counter() - t0)
    return best / idxs.shape[0]


def _indices(rng, rows: int, reps: int):
    return jnp.asarray(rng.integers(-rows, rows, (reps, RUNS, READERS),
                                    dtype=np.int32))


def _measure(name, tables, rows, words, shared, reps, rng) -> dict:
    idxs = _indices(rng, rows, reps)
    us = {p: 1e6 * _time(read, tables, idxs, shared)
          for p, read in PATHS.items()}
    line = {"shape": name, "rows": rows, "words": words, "runs": RUNS,
            "readers": READERS, "shared_table": shared,
            "onehot_us": us["onehot"], "mxu_us": us["mxu"],
            "mxu_over_onehot": us["mxu"] / us["onehot"]}
    print(json.dumps(line), flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=400)
    ap.add_argument("--out", default=None, help="also write the lines here")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the first device is {dev.platform}", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    lines = []
    for rows in ROWS:
        for words in WORDS:
            table = jnp.asarray(rng.integers(
                0, 2**32, (RUNS, rows, words), dtype=np.uint32))
            lines.append(_measure("u32", (table,), rows, words, False,
                                  args.reps, rng))
    m = 25
    deliveries = (
        jnp.asarray(rng.integers(0, 2**32, (RUNS, 200), dtype=np.uint32)),
        jnp.asarray(rng.random((RUNS, 200, m)) < 0.5),
        jnp.asarray(rng.integers(0, 2**32, (RUNS, 200, m, 2),
                                 dtype=np.uint32)))
    lines.append(_measure("deliveries_m25", deliveries, 200, 1 + m + 2 * m,
                          False, args.reps, rng))
    birth = rng.standard_normal((64, m)).astype(np.float32)
    birth[rng.random((64, m)) < 0.5] = -np.inf
    lines.append(_measure("obs_birth_m25", (jnp.asarray(birth),), 64, m,
                          True, args.reps, rng))
    faster = [w for w in WORDS
              if all(ln["mxu_us"] < ln["onehot_us"] for ln in lines
                     if ln["shape"] == "u32" and ln["words"] == w)]
    crossover = next((w for w in WORDS if all(v in faster for v in WORDS
                                              if v >= w)), None)
    verdict = {"crossover_words": crossover, "mxu_faster_at": faster,
               "device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": jax.device_count()}}
    print(json.dumps(verdict), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            for ln in lines + [verdict]:
                f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
