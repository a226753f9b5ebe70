"""Plain reference of the Floating Gossip slot simulation (paper Sec. VI).

The benchmark judges the program's outputs against this module. It imports
nothing of the program: it is a straightforward dense implementation of the
same protocol, one slot at a time, with boolean masks instead of packed
words and no kernels.

Per slot (``dt`` seconds), in this order:

1. Random Direction mobility with specular reflection at the area border;
   Replication Zone (RZ) membership is the disc around the area centre.
2. Nodes that left the RZ drop all protocol state.
3. Contacts: two distinct in-RZ nodes within ``r_tx`` are close. A running
   exchange ends when it is done or its pair stopped being close; instances
   whose transfer finished before the end are delivered, and a delivery
   that adds information queues a merge job.
4. Matching: free in-RZ nodes pair with their nearest *new* contact when
   the choice is mutual; the pair snapshots its models.
5. Observations arrive per model at rate ``lam``; ``Lam`` in-RZ nodes,
   chosen at random, queue a training job.
6. Each node's compute server finishes its job, then takes the next one,
   merges first.

With learning on (``Learn``), every node also holds the parameters of a
logistic regression on a synthetic linear-teacher task. A node that leaves
the RZ restarts from zero; a delivery of model 0 averages the sender's
parameters, as they were when the exchange began, into the receiver's,
weighted by their observation counts; a finished training job on a fresh
observation takes one SGD step on a minibatch of the task's stream.

The pairwise distances are taken in row blocks, so that no ``(N, N)`` float
array exists at large N; the close matrix itself is kept as booleans.
``fdt`` sets the precision of positions, headings and timers: float32 is
the reference, and bfloat16 is the control that the comparison must fail.

The random draws follow the same key schedule as the program (one five-way
split per slot), so that the same seed gives the same trajectory and every
sampled output can be compared value by value.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: Rows of the pairwise distance pass at a time (at most).
ROW_BLOCK = 512


class Shape(NamedTuple):
    """The static sizes of one run (hashable: a jit static argument)."""

    n_nodes: int
    area_side: float
    rz_radius: float
    r_tx: float
    speed: float
    dir_change_rate: float
    dt: float
    n_slots: int
    k_obs: int
    q_train: int
    q_merge: int
    M: int
    Lam: int


class Learn(NamedTuple):
    """The learned model and its task (hashable: a jit static argument)."""

    n_features: int
    n_classes: int
    lr: float
    batch: int
    n_test: int
    label_noise: float
    data_seed: int


#: Observation counts saturate here when two replicas merge.
CNT_CAP = 1.0e12
_HI = jax.lax.Precision.HIGHEST


def _labels(key, lc: Learn, x, w_true):
    logits = jnp.matmul(x, w_true, precision=_HI)
    if lc.label_noise > 0.0:
        logits = logits + lc.label_noise * jax.random.normal(
            key, logits.shape, jnp.float32)
    return jnp.argmax(logits, axis=-1)


def _task(lc: Learn):
    """Teacher, test set and the key of the training stream."""
    k_teacher, _, k_test, k_ytest, k_stream = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(lc.data_seed), 0x7EAC), 5)
    w_true = jax.random.normal(
        k_teacher, (lc.n_features, lc.n_classes), jnp.float32)
    x_test = jax.random.normal(k_test, (lc.n_test, lc.n_features),
                               jnp.float32)
    return w_true, x_test, _labels(k_ytest, lc, x_test, w_true), k_stream


def _logits(lc: Learn, theta, x):
    """``theta`` (..., F*C + C): weights, then biases."""
    fc = lc.n_features * lc.n_classes
    w = theta[..., :fc].reshape(theta.shape[:-1]
                                + (lc.n_features, lc.n_classes))
    return (jnp.einsum("...bf,...fc->...bc", x, w, precision=_HI)
            + theta[..., None, fc:])


def _loss(lc: Learn, theta, x, y):
    logp = jax.nn.log_softmax(_logits(lc, theta, x), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def _row_block(n: int) -> int:
    if n <= ROW_BLOCK:
        return n
    if n % ROW_BLOCK:
        raise ValueError(f"n_nodes {n} is not a multiple of {ROW_BLOCK}")
    return ROW_BLOCK


def _close_rows(pos, in_rz, r2, blk):
    """(N, N) bool close matrix, built ``blk`` rows at a time."""
    n = pos.shape[0]
    ids = jnp.arange(n)

    def block(b):
        rows = b * blk + jnp.arange(blk)
        pi = jax.lax.dynamic_slice_in_dim(pos, b * blk, blk)
        zi = jax.lax.dynamic_slice_in_dim(in_rz, b * blk, blk)
        d2 = jnp.sum((pi[:, None, :] - pos[None, :, :]) ** 2, axis=-1)
        return ((d2 <= r2) & zi[:, None] & in_rz[None, :]
                & (rows[:, None] != ids[None, :]))

    return jax.lax.map(block, jnp.arange(n // blk)).reshape(n, n)


def _nearest_new(pos, close, prev_close, elig, blk):
    """Per node: the nearest eligible new contact (index, found)."""
    n = pos.shape[0]

    def block(b):
        pi = jax.lax.dynamic_slice_in_dim(pos, b * blk, blk)
        ci = jax.lax.dynamic_slice_in_dim(close, b * blk, blk)
        pci = jax.lax.dynamic_slice_in_dim(prev_close, b * blk, blk)
        ei = jax.lax.dynamic_slice_in_dim(elig, b * blk, blk)
        d2 = jnp.sum((pi[:, None, :] - pos[None, :, :]) ** 2, axis=-1)
        cand = ci & ~pci & ei[:, None] & elig[None, :]
        scores = jnp.where(cand, d2, jnp.inf)
        return (jnp.argmin(scores, axis=1),
                jnp.isfinite(jnp.min(scores, axis=1)))

    best, has = jax.lax.map(block, jnp.arange(n // blk))
    return best.reshape(n), has.reshape(n)


def _enqueue(q_model, q_payload, want, m, payload):
    """Put model ``m`` with ``payload`` into each wanting node's first free
    queue slot (nothing happens where the queue is full)."""
    n, q = q_model.shape
    free = q_model < 0
    first = jnp.argmax(free, axis=-1)
    can = jnp.any(free, axis=-1) & want
    sel = (jnp.arange(q)[None, :] == first[:, None]) & can[:, None]
    q_model = jnp.where(sel, m, q_model)
    sel_p = sel.reshape(sel.shape + (1,) * (q_payload.ndim - 2))
    q_payload = jnp.where(sel_p, payload, q_payload)
    return q_model, q_payload


def run(key, p: dict, shape: Shape, fdt=jnp.float32, learn: Learn | None = None):
    """One run: per-slot outputs, each with a leading slot axis.

    ``p`` holds the dynamic protocol parameters ``t0``, ``T_L``, ``T_T``,
    ``T_M``, ``lam`` and ``tau_l`` (scalars, traced)."""
    N, K, M = shape.n_nodes, shape.k_obs, shape.M
    QT, QM = shape.q_train, shape.q_merge
    dt, side = shape.dt, shape.area_side
    blk = _row_block(N)
    r2 = jnp.asarray(shape.r_tx ** 2, fdt)
    t0, T_L = p["t0"].astype(fdt), p["T_L"].astype(fdt)
    T_T, T_M = p["T_T"].astype(fdt), p["T_M"].astype(fdt)
    lam, tau_l = p["lam"], p["tau_l"]
    center = jnp.asarray([side / 2.0, side / 2.0], fdt)
    ids = jnp.arange(N)

    def rz_member(pos):
        return jnp.linalg.norm(pos - center, axis=-1) <= shape.rz_radius

    k_pos, k_dir, key = jax.random.split(key, 3)
    pos0 = jax.random.uniform(k_pos, (N, 2), maxval=side).astype(fdt)
    ang0 = jax.random.uniform(k_dir, (N,), maxval=2 * jnp.pi).astype(fdt)
    zero = jnp.zeros((N,), fdt)
    state = dict(
        pos=pos0, ang=ang0, in_rz=rz_member(pos0),
        partner=jnp.full((N,), -1, jnp.int32),
        exch_elapsed=zero, exch_total=zero,
        snap=jnp.zeros((N, M, K), bool), snap_has=jnp.zeros((N, M), bool),
        order_seed=jnp.zeros((N,), jnp.uint32),
        prev_close=jnp.zeros((N, N), bool),
        inc=jnp.zeros((N, M, K), bool), has_model=jnp.zeros((N, M), bool),
        obs_birth=jnp.full((M, K), -jnp.inf),
        obs_head=jnp.zeros((M,), jnp.int32),
        tq_model=jnp.full((N, QT), -1, jnp.int32),
        tq_slot=jnp.zeros((N, QT), jnp.int32),
        mq_model=jnp.full((N, QM), -1, jnp.int32),
        mq_mask=jnp.zeros((N, QM, K), bool),
        serving=jnp.full((N,), -1, jnp.int32), serv_left=zero,
        serv_model=jnp.zeros((N,), jnp.int32),
        serv_mask=jnp.zeros((N, K), bool),
        serv_slot=jnp.zeros((N,), jnp.int32),
    )
    if learn is not None:
        w_true, x_test, y_test, k_stream = _task(learn)
        dim = learn.n_features * learn.n_classes + learn.n_classes
        theta0 = jnp.zeros((N, dim), fdt)
        state.update(theta=theta0, theta_cnt=zero, theta_age=zero,
                     theta_snap=theta0, snap_cnt=zero, snap_age=zero,
                     n_merges=jnp.zeros((), jnp.int32))

    def step(carry, slot_idx):
        s, key = carry
        t_now = slot_idx.astype(jnp.float32) * dt
        key, k_renew, k_head, k_obs, k_who = jax.random.split(key, 5)

        # 1. mobility
        renew = jax.random.uniform(k_renew, (N,)) < shape.dir_change_rate * dt
        new_ang = jax.random.uniform(k_head, (N,), maxval=2 * jnp.pi)
        ang = jnp.where(renew, new_ang.astype(fdt), s["ang"])
        vel = shape.speed * jnp.stack([jnp.cos(ang), jnp.sin(ang)], axis=-1)
        pos = s["pos"] + vel * dt
        over, under = pos > side, pos < 0.0
        pos = jnp.where(over, 2 * side - pos, jnp.where(under, -pos, pos))
        vel = jnp.where(over | under, -vel, vel)
        ang = jnp.arctan2(vel[:, 1], vel[:, 0])
        in_rz = rz_member(pos)

        # 2. leaving the RZ drops the protocol state
        left = s["in_rz"] & ~in_rz
        inc = jnp.where(left[:, None, None], False, s["inc"])
        has_model = jnp.where(left[:, None], False, s["has_model"])
        tq_model = jnp.where(left[:, None], -1, s["tq_model"])
        mq_model = jnp.where(left[:, None], -1, s["mq_model"])
        serving = jnp.where(left, -1, s["serving"])
        serv_left = jnp.where(left, jnp.zeros((), fdt), s["serv_left"])
        if learn is not None:
            theta = jnp.where(left[:, None], jnp.zeros((), fdt), s["theta"])
            theta_cnt = jnp.where(left, jnp.zeros((), fdt), s["theta_cnt"])
            theta_age = jnp.where(left, jnp.zeros((), fdt), s["theta_age"])

        # 3. contacts, exchanges, deliveries
        close = _close_rows(pos, in_rz, r2, blk)
        partner = s["partner"]
        busy = partner >= 0
        pidx = jnp.clip(partner, 0, N - 1)
        still_close = close[ids, pidx] & busy
        elapsed = jnp.where(busy, s["exch_elapsed"] + dt, 0.0).astype(fdt)
        done = busy & (elapsed >= s["exch_total"])
        ending = done | (busy & ~still_close & ~done)
        eff_time = jnp.where(done, s["exch_total"],
                             jnp.maximum(elapsed - dt, 0.0))

        def deliveries(order_seed, sender_has, eff):
            rnd = jax.random.uniform(
                jax.random.fold_in(jax.random.PRNGKey(0), order_seed), (M,))
            rnd = jnp.where(sender_has, rnd, jnp.inf)
            rank = jnp.argsort(jnp.argsort(rnd))
            fin = t0 + (rank + 1).astype(fdt) * T_L
            return sender_has & (fin <= eff)

        delivered = jax.vmap(deliveries)(
            s["order_seed"][pidx], s["snap_has"][pidx], eff_time)
        delivered = delivered & ending[:, None]
        if learn is not None:
            # merge the sender's snapshot, weighted by observation counts
            got = delivered[:, 0]
            peer, peer_cnt = s["theta_snap"][pidx], s["snap_cnt"][pidx]
            peer_age = s["snap_age"][pidx]
            got = got & jnp.all(jnp.isfinite(peer), axis=-1) \
                & jnp.isfinite(peer_cnt) & jnp.isfinite(peer_age)
            tot = theta_cnt + peer_cnt
            w = jnp.where(tot > 0.0, theta_cnt / jnp.where(tot > 0.0, tot, 1.0),
                          0.5).astype(fdt)
            merged = w[:, None] * theta + (1.0 - w[:, None]) * peer
            theta = jnp.where(got[:, None], merged, theta)
            theta_cnt = jnp.where(got, jnp.minimum(tot, CNT_CAP), theta_cnt)
            theta_age = jnp.where(got, jnp.minimum(theta_age, peer_age),
                                  theta_age)
            n_merges = s["n_merges"] + jnp.sum(delivered[:, 0])
        sender_mask = s["snap"][pidx]                       # (N, M, K)
        adds = delivered & jnp.any(sender_mask & ~inc, axis=-1)
        mq_mask = s["mq_mask"]
        for m in range(M):
            mq_model, mq_mask = _enqueue(mq_model, mq_mask, adds[:, m], m,
                                         sender_mask[:, m][:, None, :])

        # 4. matching
        partner = jnp.where(ending, -1, partner)
        elig = (partner < 0) & in_rz
        best, has = _nearest_new(pos, close, s["prev_close"], elig, blk)
        mutual = (best[best] == ids) & has & has[best]
        match = jnp.where(mutual, best, -1)
        newly = match >= 0
        midx = jnp.clip(match, 0, N - 1)
        n_own = jnp.sum(has_model, axis=-1)
        total = t0 + (n_own + n_own[midx]).astype(fdt) * T_L
        partner = jnp.where(newly, match, partner)
        elapsed = jnp.where(newly, jnp.zeros((), fdt), elapsed)
        exch_total = jnp.where(newly, total, s["exch_total"])
        snap = jnp.where(newly[:, None, None], inc, s["snap"])
        snap_has = jnp.where(newly[:, None], has_model, s["snap_has"])
        order_seed = jnp.where(
            newly,
            slot_idx.astype(jnp.uint32) * jnp.uint32(2654435761)
            + jnp.arange(N, dtype=jnp.uint32),
            s["order_seed"])
        if learn is not None:
            theta_snap = jnp.where(newly[:, None], theta, s["theta_snap"])
            snap_cnt = jnp.where(newly, theta_cnt, s["snap_cnt"])
            snap_age = jnp.where(newly, theta_age, s["snap_age"])

        # 5. observations
        new_obs = jax.random.uniform(k_obs, (M,)) < lam * dt
        slot_of = s["obs_head"]
        at_head = jnp.arange(K)[None, :] == slot_of[:, None]      # (M, K)
        obs_birth = jnp.where(new_obs[:, None] & at_head, t_now,
                              s["obs_birth"])
        obs_head = jnp.where(new_obs, (slot_of + 1) % K, slot_of)
        inc = inc & ~(new_obs[:, None] & at_head)[None]
        who = jax.random.uniform(k_who, (M, N)) + (~in_rz)[None, :] * 1e3
        observers = jnp.argsort(who, axis=-1)[:, :shape.Lam]
        tq_slot = s["tq_slot"]
        for m in range(M):
            is_obs = (jnp.zeros((N,), bool).at[observers[m]].set(True)
                      & in_rz & new_obs[m])
            tq_model, tq_slot = _enqueue(tq_model, tq_slot, is_obs, m,
                                         slot_of[m])

        # 6. compute server: finish, then start the next job
        serv_left = jnp.where(serving >= 0, serv_left - dt, serv_left)
        fin = (serving >= 0) & (serv_left <= 0.0)
        fin_merge, fin_train = fin & (serving == 0), fin & (serving == 1)
        onehot_m = jax.nn.one_hot(s["serv_model"], M, dtype=bool)
        inc = inc | (fin_merge[:, None, None] & onehot_m[:, :, None]
                     & s["serv_mask"][:, None, :])
        has_model = has_model | (fin_merge[:, None] & onehot_m)
        onehot_k = jax.nn.one_hot(s["serv_slot"], K, dtype=bool)
        fresh = obs_birth[:, s["serv_slot"]].T > -jnp.inf          # (N, M)
        inc = inc | (fin_train[:, None, None] & onehot_m[:, :, None]
                     & onehot_k[:, None, :] & fresh[:, :, None])
        has_model = has_model | (fin_train[:, None] & onehot_m & fresh)
        serving = jnp.where(fin, -1, serving)
        if learn is not None:
            # one SGD step per finished training job on model 0
            trained = fin_train & (s["serv_model"] == 0) & fresh[:, 0]
            kx, ky = jax.random.split(jax.random.fold_in(k_stream, slot_idx))
            x = jax.random.normal(
                kx, (N, learn.batch, learn.n_features), jnp.float32)
            y = _labels(ky, learn, x, w_true)
            grads = jax.vmap(jax.grad(
                lambda th, xb, yb: _loss(learn, th, xb, yb)))(theta, x, y)
            theta = jnp.where(trained[:, None], theta - learn.lr * grads,
                              theta)
            theta_cnt = jnp.where(trained, theta_cnt + 1.0, theta_cnt)
            theta_age = jnp.where(trained, 0.0, theta_age + dt).astype(fdt)

        take_m = (serving < 0) & jnp.any(mq_model >= 0, axis=-1)
        m_first = jnp.argmax(mq_model >= 0, axis=-1)
        serv_model = jnp.where(take_m, mq_model[ids, m_first],
                               s["serv_model"])
        serv_mask = jnp.where(take_m[:, None], mq_mask[ids, m_first],
                              s["serv_mask"])
        mq_model = jnp.where(
            (jnp.arange(QM)[None, :] == m_first[:, None]) & take_m[:, None],
            -1, mq_model)
        serving = jnp.where(take_m, 0, serving)
        serv_left = jnp.where(take_m, T_M, serv_left)

        take_t = (serving < 0) & jnp.any(tq_model >= 0, axis=-1)
        t_first = jnp.argmax(tq_model >= 0, axis=-1)
        serv_model = jnp.where(take_t, tq_model[ids, t_first], serv_model)
        serv_slot = jnp.where(take_t, tq_slot[ids, t_first], s["serv_slot"])
        tq_model = jnp.where(
            (jnp.arange(QT)[None, :] == t_first[:, None]) & take_t[:, None],
            -1, tq_model)
        serving = jnp.where(take_t, 1, serving)
        serv_left = jnp.where(take_t, T_T, serv_left)

        # outputs
        live = (obs_birth > -jnp.inf) & (t_now - obs_birth <= tau_l)
        stored = jnp.sum(inc & live[None], axis=(1, 2))
        n_rz = jnp.maximum(jnp.sum(in_rz), 1)
        out = dict(
            availability=jnp.sum(has_model & in_rz[:, None], axis=0) / n_rz,
            busy_frac=jnp.sum((partner >= 0) & in_rz) / n_rz,
            stored=jnp.sum(jnp.where(in_rz, stored, 0)) / n_rz,
            model_holders=jnp.sum(has_model & in_rz[:, None], axis=0),
            n_in_rz=jnp.sum(in_rz),
            obs_birth=obs_birth,
            obs_holders=jnp.sum(inc & in_rz[:, None, None], axis=0),
        )
        if learn is not None:
            pred = jnp.argmax(_logits(learn, theta.astype(jnp.float32),
                                      x_test), axis=-1)
            acc = jnp.mean((pred == y_test).astype(jnp.float32), axis=-1)
            hold = (has_model[:, 0] & in_rz).astype(jnp.float32)
            n_hold = jnp.sum(hold)
            denom = jnp.maximum(n_hold, 1.0)
            th = theta.astype(jnp.float32)
            mu = jnp.sum(hold[:, None] * th, axis=0) / denom
            var = jnp.sum(hold[:, None] * (th - mu) ** 2, axis=0) / denom
            out.update(
                test_acc=jnp.mean(acc),
                test_acc_holders=jnp.where(
                    n_hold > 0, jnp.sum(hold * acc) / denom, jnp.mean(acc)),
                learn_obs=jnp.where(
                    n_hold > 0,
                    jnp.sum(hold * theta_cnt.astype(jnp.float32)) / denom,
                    0.0),
                theta_var=jnp.where(n_hold > 0, jnp.mean(var), 0.0),
                n_merges=n_merges,
            )
        new = dict(
            pos=pos, ang=ang, in_rz=in_rz, partner=partner,
            exch_elapsed=elapsed, exch_total=exch_total, snap=snap,
            snap_has=snap_has, order_seed=order_seed, prev_close=close,
            inc=inc, has_model=has_model, obs_birth=obs_birth,
            obs_head=obs_head, tq_model=tq_model, tq_slot=tq_slot,
            mq_model=mq_model, mq_mask=mq_mask, serving=serving,
            serv_left=serv_left, serv_model=serv_model, serv_mask=serv_mask,
            serv_slot=serv_slot,
        )
        if learn is not None:
            new.update(theta=theta, theta_cnt=theta_cnt, theta_age=theta_age,
                       theta_snap=theta_snap, snap_cnt=snap_cnt,
                       snap_age=snap_age, n_merges=n_merges)
        return (new, key), out

    _, outs = jax.lax.scan(step, (state, key), jnp.arange(shape.n_slots))
    return outs


@partial(jax.jit, static_argnames=("shape", "fdt", "learn"))
def run_many(keys, p: dict, shape: Shape, fdt=jnp.float32,
             learn: Learn | None = None):
    """``run`` over a batch of runs (leading axis of ``keys`` and of every
    entry of ``p``)."""
    return jax.vmap(lambda k, q: run(k, q, shape, fdt, learn))(keys, p)


def sample_points(n_slots: int, sample_every: int) -> np.ndarray:
    """The slots at which the program emits a sample: s-1, 2s-1, ..."""
    return np.arange(sample_every - 1, n_slots, sample_every)
