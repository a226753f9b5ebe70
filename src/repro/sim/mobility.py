"""Pluggable mobility models for the simulation engine (registry).

Each model is a ``MobilityModel`` record pairing

* ``init(key, cfg) -> (state, key)`` — sample an initial state (a small
  registered-dataclass pytree whose ``pos`` field is the ``(N, 2)`` node
  positions), and
* ``step(k1, k2, state, cfg) -> state`` — advance one slot of ``cfg.dt``
  seconds using at most two PRNG keys,

with the *name* of its analytic counterpart in
``repro.core.mobility.CONTACT_MODELS`` — the mean-field pipeline and the
simulator select matching physics via the same string (see
``contact_model`` below). Models:

* ``rdm``       — Random Direction with boundary reflections (the paper's
                  model): headings renew as a Poisson process, constant
                  speed, specular reflection at the area boundary.
* ``rwp``       — Random Waypoint without pauses: move at constant speed
                  toward a uniformly sampled waypoint, resample on arrival.
* ``manhattan`` — axis-aligned movement on a street grid with spacing
                  ``cfg.street_spacing``; at interior intersections turn
                  with probability 1/2 (uniform new orientation), reflect
                  at the boundary.

The two-key step contract exists so the engine can split its slot key the
same way for every model; ``rdm`` consumes both keys exactly like the
plain reference ``bench/reference.py``, keeping the engine bit-compatible
with it (``tests/test_sim_engine.py``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.mobility import ContactModel, contact_model_for
from repro.sim.contacts import close_matrix
from repro.sim.state import register_pytree_dataclass

__all__ = [
    "MobilityModel",
    "MOBILITY_MODELS",
    "register_mobility",
    "get_mobility",
    "measure_contact_rate",
    "RDMState",
    "RWPState",
    "ManhattanState",
]


@dataclasses.dataclass(frozen=True)
class MobilityModel:
    """A named mobility model plus its analytic contact-statistics twin."""

    name: str
    init: Callable    # (key, cfg) -> (state, key)
    step: Callable    # (k1, k2, state, cfg) -> state

    def contact_model(self, *, speed, r_tx, density, **geometry) -> ContactModel:
        """The analytic ContactModel registered under the same name."""
        return contact_model_for(
            self.name, speed=speed, r_tx=r_tx, density=density, **geometry
        )


MOBILITY_MODELS: dict[str, MobilityModel] = {}


def register_mobility(model: MobilityModel) -> MobilityModel:
    MOBILITY_MODELS[model.name] = model
    return model


def get_mobility(name: str) -> MobilityModel:
    try:
        return MOBILITY_MODELS[name]
    except KeyError:
        raise ValueError(
            f"unknown mobility model {name!r}; known: {sorted(MOBILITY_MODELS)}"
        ) from None


# --------------------------------------------------------------------------
# Random Direction (the paper's model)
# --------------------------------------------------------------------------


@register_pytree_dataclass
@dataclasses.dataclass(frozen=True)
class RDMState:
    pos: jnp.ndarray     # (N, 2)
    ang: jnp.ndarray     # (N,) heading [rad]
    spd: jnp.ndarray     # (N,) per-node speed [m/s] — all cfg.speed unless
                         # cfg.speed_range draws U(lo, hi) speeds at init


def _rdm_init(key, cfg):
    if getattr(cfg, "speed_range", None) is not None:
        lo, hi = cfg.speed_range
        k_pos, k_dir, k_spd, key = jax.random.split(key, 4)
        spd = jax.random.uniform(
            k_spd, (cfg.n_nodes,), minval=lo, maxval=hi
        )
    else:
        # legacy key schedule (no speed key) — the constant-speed engine
        # stays bitwise-equal to the pre-speed_range one
        k_pos, k_dir, key = jax.random.split(key, 3)
        spd = jnp.full((cfg.n_nodes,), cfg.speed, jnp.float32)
    pos = jax.random.uniform(k_pos, (cfg.n_nodes, 2), maxval=cfg.area_side)
    ang = jax.random.uniform(k_dir, (cfg.n_nodes,), maxval=2 * jnp.pi)
    return RDMState(pos=pos, ang=ang, spd=spd), key


def _rdm_step(k_renew, k_head, s: RDMState, cfg) -> RDMState:
    n = s.pos.shape[0]
    renew = jax.random.uniform(k_renew, (n,)) < cfg.dir_change_rate * cfg.dt
    new_ang = jax.random.uniform(k_head, (n,), maxval=2 * jnp.pi)
    ang = jnp.where(renew, new_ang, s.ang)
    # per-node speed times unit heading — bitwise the historical
    # ``cfg.speed * stack(...)`` when every spd entry is cfg.speed
    vel = s.spd[:, None] * jnp.stack([jnp.cos(ang), jnp.sin(ang)], axis=-1)
    pos = s.pos + vel * cfg.dt
    over = pos > cfg.area_side
    under = pos < 0.0
    pos = jnp.where(over, 2 * cfg.area_side - pos, jnp.where(under, -pos, pos))
    vel = jnp.where(over | under, -vel, vel)
    return RDMState(pos=pos, ang=jnp.arctan2(vel[:, 1], vel[:, 0]), spd=s.spd)


register_mobility(MobilityModel(name="rdm", init=_rdm_init, step=_rdm_step))


# --------------------------------------------------------------------------
# Random Waypoint (constant waypoint pause, ``cfg.pause_s``; 0 = classic)
# --------------------------------------------------------------------------


@register_pytree_dataclass
@dataclasses.dataclass(frozen=True)
class RWPState:
    pos: jnp.ndarray     # (N, 2)
    dest: jnp.ndarray    # (N, 2) current waypoint
    wait: jnp.ndarray    # (N,) remaining pause time at the waypoint [s]


def _rwp_init(key, cfg):
    k_pos, k_dest, key = jax.random.split(key, 3)
    pos = jax.random.uniform(k_pos, (cfg.n_nodes, 2), maxval=cfg.area_side)
    dest = jax.random.uniform(k_dest, (cfg.n_nodes, 2), maxval=cfg.area_side)
    return RWPState(pos=pos, dest=dest, wait=jnp.zeros((cfg.n_nodes,))), key


def _rwp_step(k_dest, _k_unused, s: RWPState, cfg) -> RWPState:
    n = s.pos.shape[0]
    step_len = cfg.speed * cfg.dt
    delta = s.dest - s.pos
    dist = jnp.linalg.norm(delta, axis=-1)
    paused = s.wait > 0.0
    arrive = (dist <= step_len) & ~paused
    direction = delta / jnp.maximum(dist, 1e-9)[:, None]
    pos = jnp.where(
        paused[:, None], s.pos,
        jnp.where(arrive[:, None], s.dest, s.pos + direction * step_len),
    )
    # the next waypoint is drawn at arrival (key use identical for any
    # pause_s); with cfg.pause_s > 0 the node then sits at the waypoint for
    # ceil(pause_s / dt) slots before moving toward it
    new_dest = jax.random.uniform(k_dest, (n, 2), maxval=cfg.area_side)
    dest = jnp.where(arrive[:, None], new_dest, s.dest)
    wait = jnp.where(
        arrive, cfg.pause_s, jnp.where(paused, s.wait - cfg.dt, s.wait)
    )
    return RWPState(pos=pos, dest=dest, wait=wait)


register_mobility(MobilityModel(name="rwp", init=_rwp_init, step=_rwp_step))


# --------------------------------------------------------------------------
# Manhattan grid
# --------------------------------------------------------------------------


@register_pytree_dataclass
@dataclasses.dataclass(frozen=True)
class ManhattanState:
    pos: jnp.ndarray     # (N, 2) on the street graph
    horiz: jnp.ndarray   # (N,) bool: moving along x (True) or y (False)
    sgn: jnp.ndarray     # (N,) movement sign, +-1.0


def _manhattan_init(key, cfg):
    k1, _, key = jax.random.split(key, 3)
    ka, kb, kc, kd = jax.random.split(k1, 4)
    n, s = cfg.n_nodes, cfg.street_spacing
    n_streets = int(round(cfg.area_side / s)) + 1
    horiz = jax.random.bernoulli(ka, 0.5, (n,))
    fixed = s * jax.random.randint(kb, (n,), 0, n_streets).astype(jnp.float32)
    moving = jax.random.uniform(kc, (n,), maxval=cfg.area_side)
    sgn = jnp.where(jax.random.bernoulli(kd, 0.5, (n,)), 1.0, -1.0)
    pos = jnp.stack(
        [jnp.where(horiz, moving, fixed), jnp.where(horiz, fixed, moving)],
        axis=-1,
    )
    return ManhattanState(pos=pos, horiz=horiz, sgn=sgn), key


def _manhattan_step(k_turn, _k_unused, st: ManhattanState, cfg) -> ManhattanState:
    n = st.pos.shape[0]
    s, side = cfg.street_spacing, cfg.area_side
    x, y = st.pos[:, 0], st.pos[:, 1]
    u = jnp.where(st.horiz, x, y)            # moving coordinate
    w = jnp.where(st.horiz, y, x)            # fixed coordinate (on a street)

    u_new = u + st.sgn * cfg.speed * cfg.dt
    # Next street line strictly ahead in the movement direction (at most one
    # per slot, assuming speed * dt < street_spacing); reaching it (inclusive,
    # symmetric for both signs) offers a turn. A node that turned last slot
    # sits exactly on a line, and its next line is strictly beyond — no
    # re-trigger. Boundary lines allow turns too (onto the boundary street),
    # keeping the stationary distribution uniform over the whole street graph.
    m = jnp.where(
        st.sgn > 0, (jnp.floor(u / s) + 1.0) * s, (jnp.ceil(u / s) - 1.0) * s
    )
    crossed = jnp.where(st.sgn > 0, u_new >= m, u_new <= m)

    r = jax.random.uniform(k_turn, (n, 2))
    turn = crossed & (m >= 0.0) & (m <= side) & (r[:, 0] < 0.5)
    turn_sgn = jnp.where(r[:, 1] < 0.5, 1.0, -1.0)

    over = u_new > side
    under = u_new < 0.0
    u_ref = jnp.where(over, 2 * side - u_new, jnp.where(under, -u_new, u_new))
    sgn_ref = jnp.where(over | under, -st.sgn, st.sgn)

    u_fin = jnp.where(turn, m, u_ref)
    sgn = jnp.where(turn, turn_sgn, sgn_ref)
    horiz = st.horiz ^ turn
    pos = jnp.stack(
        [jnp.where(st.horiz, u_fin, w), jnp.where(st.horiz, w, u_fin)],
        axis=-1,
    )
    return ManhattanState(pos=pos, horiz=horiz, sgn=sgn)


register_mobility(
    MobilityModel(name="manhattan", init=_manhattan_init, step=_manhattan_step)
)


# --------------------------------------------------------------------------
# Empirical contact-rate probe (used by tests and benchmarks)
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("name", "cfg", "n_slots"))
def measure_contact_rate(key, *, name: str, cfg, n_slots: int) -> jnp.ndarray:
    """Mean per-node contact rate [1/s] of mobility model ``name``.

    Rolls the mobility model alone (no protocol) for ``n_slots`` slots and
    counts *new* pairwise proximity events (distance <= r_tx), i.e. exactly
    the simulator's contact definition without RZ or busy gating. Each
    event counts once for each endpoint, matching the per-node ``g`` of the
    analytic ContactModels.
    """
    model = get_mobility(name)
    mob, key = model.init(key, cfg)
    everyone = jnp.ones((cfg.n_nodes,), bool)  # no RZ gating for the probe

    def step(carry, _):
        mob, prev_close, key = carry
        key, k1, k2 = jax.random.split(key, 3)
        mob = model.step(k1, k2, mob, cfg)
        close, _ = close_matrix(mob.pos, everyone, cfg.r_tx)
        new = jnp.sum(close & ~prev_close)
        return (mob, close, key), new

    init_close, _ = close_matrix(mob.pos, everyone, cfg.r_tx)
    _, counts = jax.lax.scan(
        step, (mob, init_close, key), None, length=n_slots
    )
    total_time = n_slots * cfg.dt
    return jnp.sum(counts) / (cfg.n_nodes * total_time)
