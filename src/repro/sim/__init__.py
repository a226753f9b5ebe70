"""Modular Monte-Carlo simulation engine for Floating Gossip (paper §VI).

Module map
----------

``state``         Typed pytree carry (``SimState``, registered dataclasses)
                  replacing the legacy raw-dict scan state. Every boolean
                  mask is bit-packed to uint32 words (LSB-first
                  ``compute.pack_mask`` layout) and queues use narrow int
                  dtypes — the scan carry is the engine's memory-traffic
                  hot spot when batched.
``mobility``      Pluggable mobility registry — ``rdm`` (the paper's Random
                  Direction), ``rwp`` (Random Waypoint), ``manhattan``
                  (street grid) — each paired by name with its analytic
                  ``ContactModel`` in ``repro.core.mobility``, plus an
                  empirical contact-rate probe.
``contacts``      D2D pairing (mutual-best matching), exchange progression,
                  and per-instance delivery accounting. The O(N²) pairwise
                  sweep dispatches to ``repro.kernels.contacts`` (fused
                  Pallas kernel on TPU, bit-identical word-domain ``jnp``
                  oracle elsewhere).
``cells``         O(N) cell-list contact detection for large N
                  (``SimConfig.contact_backend="cells"`` / ``"auto"``):
                  uniform spatial grid, bounded ascending per-node
                  neighbor lists with an overflow counter, match-for-match
                  equivalent to the dense sweep while never materializing
                  an (N, N) object.
``compute``       Merge/train priority queues as vectorized scatter ops —
                  the traced program is independent of the model count M.
``observations``  Observation ring, observer selection, job completions,
                  per-slot trace outputs, and the post-hoc o(τ) estimator.
``engine``        The ``lax.scan`` driver: ``simulate`` (single run) and
                  ``simulate_batch`` (seeds x scenarios in one jit).
                  Replication-Zone geometry is a first-class ``ZoneSet``
                  (``SimConfig.zones``): k discs, optionally drifting,
                  with packed per-node zone-membership words,
                  zone-sharing contact gating, union-exit churn
                  (zone-to-zone migration transfers state) and per-zone
                  ``*_z`` traces with a trailing zone axis. ``None``
                  keeps the legacy single centered disc — bitwise.
``sweep``         Fleet-scale sweep execution: the flattened, padded
                  (scenario x seed) work axis sharded over a 2-D device
                  mesh, streaming chunked dispatch with donated buffers,
                  and on-device sweep reductions (mean / final /
                  quantiles) that cut host transfers >100x.
                  ``simulate_batch`` is a thin wrapper over
                  ``sweep.run(..., reduce="trace")``.
"""

from repro.sim.engine import (
    BatchSimOutputs,
    SimConfig,
    SimOutputs,
    ZoneSet,
    effective_zones,
    simulate,
    simulate_batch,
)
from repro.sim.mobility import (
    MOBILITY_MODELS,
    MobilityModel,
    get_mobility,
    measure_contact_rate,
    register_mobility,
)
from repro.sim.observations import estimate_o_of_tau
from repro.sim.sweep import SweepPlan, SweepSummary, plan_sweep
from repro.sim import cells, dispatch, sweep
from repro.sim.dispatch import RetryPolicy

__all__ = [
    "cells",
    "dispatch",
    "RetryPolicy",
    "BatchSimOutputs",
    "SimConfig",
    "SimOutputs",
    "ZoneSet",
    "effective_zones",
    "SweepPlan",
    "SweepSummary",
    "plan_sweep",
    "simulate",
    "simulate_batch",
    "sweep",
    "MOBILITY_MODELS",
    "MobilityModel",
    "get_mobility",
    "register_mobility",
    "measure_contact_rate",
    "estimate_o_of_tau",
]
