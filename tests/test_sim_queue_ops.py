"""The vectorized queue ops in ``repro.sim.compute`` reproduce the legacy
per-``M`` Python-loop semantics bit for bit: ascending-``m`` arrival order,
ascending free-slot fill, silent drops at capacity, FIFO service order, and
non-preemptive merge-over-train priority."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sim.compute import (
    advance_timers, enqueue_ascending, pack_mask, pick_next_jobs,
    shared_barrier, unpack_mask,
)


def test_shared_barrier_is_bitwise_identity_under_nested_vmap():
    """The barrier batches under vmap — per run, and over a per-seed value
    shared across scenarios (``in_axes=None``) — and returns its operands
    bit for bit, special floats included."""
    x = np.array(jax.random.normal(jax.random.PRNGKey(0), (3, 4, 6)))
    x[0, 0, :4] = [np.nan, -0.0, np.inf, -np.inf]
    words = np.asarray(
        jax.random.bits(jax.random.PRNGKey(1), (4, 5), jnp.uint32))

    def per_run(a, w):
        return shared_barrier((a, w))

    over_seeds = jax.vmap(per_run, in_axes=(0, 0))
    f = jax.jit(jax.vmap(over_seeds, in_axes=(0, None)))
    ya, yw = f(x, words)
    np.testing.assert_array_equal(np.asarray(ya).view(np.uint32),
                                  x.view(np.uint32))
    np.testing.assert_array_equal(np.asarray(yw),
                                  np.broadcast_to(words, (3, 4, 5)))


@pytest.mark.parametrize("k", [1, 31, 32, 33, 64, 100])
def test_pack_unpack_roundtrip(k):
    rng = np.random.default_rng(k)
    mask = rng.random((5, 3, k)) < 0.5
    words = pack_mask(jnp.asarray(mask))
    assert words.shape == (5, 3, (k + 31) // 32)
    np.testing.assert_array_equal(np.asarray(unpack_mask(words, k)), mask)


def test_packed_merge_payload_roundtrips_through_queue():
    """A mask enqueued packed comes back out of pick_next_jobs still packed
    and bit-identical (the payload never unpacks on the hot path)."""
    k = 64
    mask = (np.arange(k) % 3 == 0)
    queue = jnp.full((1, 2), -1, jnp.int32)
    store = jnp.zeros((1, 2, 2), jnp.uint32)
    want = jnp.asarray([[True]])
    src = pack_mask(jnp.asarray(mask)[None, None, :])
    new_q, new_store = enqueue_ascending(queue, want, (store, src))
    out = pick_next_jobs(
        serving=jnp.asarray([-1], jnp.int32), serv_left=jnp.zeros((1,)),
        serv_model=jnp.zeros((1,), jnp.int32),
        serv_mask=jnp.zeros((1, (k + 31) // 32), jnp.uint32),
        serv_slot=jnp.zeros((1,), jnp.int32),
        mq_model=new_q, mq_mask=new_store,
        tq_model=jnp.full((1, 2), -1, jnp.int32),
        tq_slot=jnp.zeros((1, 2), jnp.int32), T_M=2.5, T_T=5.0,
    )
    np.testing.assert_array_equal(
        np.asarray(unpack_mask(out["serv_mask"], k)[0]), mask
    )


def legacy_enqueue(queue, want, payload_pairs):
    """Reference: the pre-refactor per-model enqueue loop (numpy)."""
    queue = np.array(queue)
    dests = [np.array(d) for d, _ in payload_pairs]
    srcs = [np.asarray(s) for _, s in payload_pairs]
    n, m_count = want.shape
    for m in range(m_count):
        free = queue < 0
        first = free.argmax(axis=1)
        can = free.any(axis=1) & want[:, m]
        for i in range(n):
            if can[i]:
                queue[i, first[i]] = m
                for d, s in zip(dests, srcs):
                    d[i, first[i]] = s[i, m]
    return queue, dests


@pytest.mark.parametrize("seed", range(8))
def test_enqueue_matches_legacy_loop(seed):
    rng = np.random.default_rng(seed)
    n, q, m_count, k = 17, 5, 7, 3
    # random occupancy, including full and empty queues
    queue = np.where(rng.random((n, q)) < 0.55, rng.integers(0, m_count, (n, q)), -1)
    queue = queue.astype(np.int32)
    want = rng.random((n, m_count)) < 0.5
    mask_store = rng.random((n, q, k)) < 0.5
    mask_src = rng.random((n, m_count, k)) < 0.5
    slot_store = rng.integers(0, 64, (n, q)).astype(np.int32)
    slot_src = rng.integers(0, 64, (n, m_count)).astype(np.int32)

    ref_q, (ref_mask, ref_slot) = legacy_enqueue(
        queue, want, [(mask_store, mask_src), (slot_store, slot_src)]
    )
    got_q, got_mask, got_slot = enqueue_ascending(
        jnp.asarray(queue), jnp.asarray(want),
        (jnp.asarray(mask_store), jnp.asarray(mask_src)),
        (jnp.asarray(slot_store), jnp.asarray(slot_src)),
    )
    np.testing.assert_array_equal(np.asarray(got_q), ref_q)
    np.testing.assert_array_equal(np.asarray(got_mask), ref_mask)
    np.testing.assert_array_equal(np.asarray(got_slot), ref_slot)


def test_enqueue_drops_beyond_capacity():
    # one free slot, three wanted models -> only the lowest m gets in
    queue = jnp.asarray([[2, -1, 3]], dtype=jnp.int32)
    want = jnp.asarray([[True, True, True, True]])
    (got,) = enqueue_ascending(queue, want)
    np.testing.assert_array_equal(np.asarray(got), [[2, 0, 3]])


def test_enqueue_fills_free_slots_in_ascending_order():
    queue = jnp.asarray([[-1, 7, -1, -1]], dtype=jnp.int32)
    want = jnp.asarray([[False, True, True, False, True]])
    (got,) = enqueue_ascending(queue, want)
    # m=1 -> slot 0, m=2 -> slot 2, m=4 -> slot 3
    np.testing.assert_array_equal(np.asarray(got), [[1, 7, 2, 4]])


def _mk_server(n, qm=3, qt=3, k=2):
    return dict(
        serving=jnp.full((n,), -1, jnp.int32),
        serv_left=jnp.zeros((n,)),
        serv_model=jnp.zeros((n,), jnp.int32),
        serv_mask=jnp.zeros((n, (k + 31) // 32), jnp.uint32),
        serv_slot=jnp.zeros((n,), jnp.int32),
        mq_model=jnp.full((n, qm), -1, jnp.int32),
        mq_mask=jnp.zeros((n, qm, (k + 31) // 32), jnp.uint32),  # packed
        tq_model=jnp.full((n, qt), -1, jnp.int32),
        tq_slot=jnp.zeros((n, qt), jnp.int32),
    )


def test_merge_has_priority_over_train():
    s = _mk_server(1)
    s["mq_model"] = jnp.asarray([[4, -1, -1]], jnp.int32)
    s["tq_model"] = jnp.asarray([[2, -1, -1]], jnp.int32)
    out = pick_next_jobs(**s, T_M=2.5, T_T=5.0)
    assert int(out["serving"][0]) == 0          # merge class
    assert int(out["serv_model"][0]) == 4
    assert float(out["serv_left"][0]) == 2.5
    assert int(out["mq_model"][0, 0]) == -1     # dequeued
    assert int(out["tq_model"][0, 0]) == 2      # train job still queued


def test_fifo_service_order_within_queue():
    s = _mk_server(1)
    s["tq_model"] = jnp.asarray([[3, 1, 5]], jnp.int32)
    s["tq_slot"] = jnp.asarray([[7, 8, 9]], jnp.int32)
    order = []
    for _ in range(3):
        out = pick_next_jobs(**s, T_M=2.5, T_T=5.0)
        order.append((int(out["serv_model"][0]), int(out["serv_slot"][0])))
        s["tq_model"] = out["tq_model"]
        s["tq_slot"] = s["tq_slot"]  # payload store is not cleared on take
    assert order == [(3, 7), (1, 8), (5, 9)]    # arrival order, not sorted


def test_busy_server_is_not_preempted():
    s = _mk_server(1)
    s["serving"] = jnp.asarray([1], jnp.int32)   # mid-training
    s["serv_left"] = jnp.asarray([3.0])
    s["serv_model"] = jnp.asarray([6], jnp.int32)
    s["mq_model"] = jnp.asarray([[2, -1, -1]], jnp.int32)
    out = pick_next_jobs(**s, T_M=2.5, T_T=5.0)
    assert int(out["serving"][0]) == 1           # untouched
    assert int(out["serv_model"][0]) == 6
    assert int(out["mq_model"][0, 0]) == 2       # merge job stays queued


def test_advance_timers_classifies_completions():
    serving = jnp.asarray([-1, 0, 1, 0], jnp.int32)
    serv_left = jnp.asarray([0.0, 0.25, 0.25, 5.0])
    left, fin_m, fin_t = advance_timers(serving, serv_left, 0.25)
    np.testing.assert_array_equal(np.asarray(fin_m), [False, True, False, False])
    np.testing.assert_array_equal(np.asarray(fin_t), [False, False, True, False])
    assert float(left[0]) == 0.0                 # idle timer untouched
    assert float(left[3]) == 4.75
