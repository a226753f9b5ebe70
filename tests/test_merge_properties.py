"""Property tests for the merge operators (paper §III-B).

Hypothesis-driven where the optional dev dependency is installed; the
Byzantine-layer properties (ISSUE 10) also run on seeded draws so the
guarantees stay exercised in hypothesis-free environments (the
``test_sim_faults`` pattern)."""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover - optional dep
    HAVE_HYP = False

    def given(*a, **kw):          # noqa: D103 - decoration-time shim
        return pytest.mark.skip("hypothesis not installed")

    def settings(*a, **kw):       # noqa: D103
        return lambda f: f

    class _St:
        """Strategy shim: decoration-time calls resolve to None."""

        def __getattr__(self, name):
            return lambda *a, **kw: None

    st = _St()

from repro.core.merge import clip_peer_counts, merge_pytrees, merge_weights
from repro.kernels.ref import gossip_merge_rows_ref

finite = st.floats(0.0, 1e6, allow_nan=False, allow_infinity=False)


@given(c1=finite, c2=finite, a1=finite, a2=finite,
       policy=st.sampled_from(["uniform", "obs_count", "staleness"]))
@settings(max_examples=60, deadline=None)
def test_weights_form_convex_combination(c1, c2, a1, a2, policy):
    w1, w2 = merge_weights(policy, jnp.asarray(c1), jnp.asarray(c2),
                           jnp.asarray(a1), jnp.asarray(a2), tau_l=300.0)
    w1, w2 = float(w1), float(w2)
    assert 0.0 <= w1 <= 1.0 and 0.0 <= w2 <= 1.0
    assert abs(w1 + w2 - 1.0) < 1e-5


@given(c1=st.floats(1.0, 1e4), c2=st.floats(1.0, 1e4))
@settings(max_examples=40, deadline=None)
def test_obs_count_weight_matches_fedavg(c1, c2):
    w1, _ = merge_weights("obs_count", jnp.asarray(c1), jnp.asarray(c2),
                          jnp.asarray(0.0), jnp.asarray(0.0), tau_l=1.0)
    assert abs(float(w1) - c1 / (c1 + c2)) < 1e-5


@given(data=st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                     min_size=1, max_size=8),
       w=st.floats(0.0, 1.0))
@settings(max_examples=40, deadline=None)
def test_merge_is_elementwise_convex(data, w):
    """merged values lie between the two inputs (no overshoot)."""
    a = jnp.asarray(data, jnp.float32)
    b = a[::-1]
    out = merge_pytrees({"x": a}, {"x": b}, jnp.asarray(w), jnp.asarray(1 - w))
    lo = np.minimum(np.asarray(a), np.asarray(b)) - 1e-4
    hi = np.maximum(np.asarray(a), np.asarray(b)) + 1e-4
    assert np.all(np.asarray(out["x"]) >= lo)
    assert np.all(np.asarray(out["x"]) <= hi)


def test_merge_idempotent_on_equal_instances():
    """Merging identical instances is a no-op (same training set)."""
    a = {"w": jnp.arange(8, dtype=jnp.float32)}
    out = merge_pytrees(a, a, jnp.asarray(0.37), jnp.asarray(0.63))
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(a["w"]), rtol=1e-6)


# --------------------------------------------------------------------------
# Byzantine-layer properties (ISSUE 10 satellites)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(40))
def test_mutual_uniform_merge_never_increases_variance(seed):
    """A slot of *mutual* uniform merges (both partners replace their
    replica with the 0.5/0.5 average) never increases the population
    parameter variance — the contraction behind the ``theta_var``
    vanishing-variance diagnostic. Seeded draws over sizes, scales and
    pairings (runs with or without hypothesis installed)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 13)) * 2          # even count to pair fully
    d = int(rng.integers(1, 5))
    scale = 10.0 ** rng.uniform(-2, 2)
    theta = (scale * rng.standard_normal((n, d))).astype(np.float32)
    perm = rng.permutation(n)
    pidx = np.empty(n, np.int64)
    pidx[perm[0::2]], pidx[perm[1::2]] = perm[1::2], perm[0::2]
    # a random symmetric subset of pairs actually merges this slot
    pair_on = rng.uniform(size=n) < 0.6
    s = pair_on & pair_on[pidx]
    out = np.asarray(gossip_merge_rows_ref(
        jnp.asarray(theta), jnp.asarray(theta[pidx]),
        jnp.full((n,), 0.5, np.float32), jnp.asarray(s)))
    var_before = float(np.var(theta, axis=0).mean())
    var_after = float(np.var(out, axis=0).mean())
    assert var_after <= var_before + 1e-4 * max(var_before, 1.0)


def test_one_sided_merge_can_increase_variance():
    """The contraction above is a property of *mutual* symmetric merges —
    a one-sided merge (receiver updates, sender keeps its replica, the
    floating-gossip delivery pattern) can push a near-mean node toward an
    outlier and raise the population variance."""
    theta = jnp.asarray([[0.0], [5.0], [-5.0]], jnp.float32)
    pidx = jnp.asarray([1, 0, 0])
    s = jnp.asarray([True, False, False])     # only node 0 merges
    out = np.asarray(gossip_merge_rows_ref(
        theta, theta[pidx], jnp.full((3,), 0.5, jnp.float32), s))
    assert float(np.var(out, axis=0).mean()) > float(
        np.var(np.asarray(theta), axis=0).mean())


@pytest.mark.parametrize("seed", range(40))
def test_count_clip_bounds_metadata_liar_weight(seed):
    """Defended ``obs_count`` weights are invariant to how big a lie the
    peer tells: any claimed count at or above the cap produces exactly
    the capped weights, and the peer's share never exceeds
    ``cap / (own + cap)`` — the metadata-liar hijack is bounded.

    Own counts are drawn from the realistic domain (0, or >= 1 —
    observation tallies)."""
    rng = np.random.default_rng(seed + 1000)
    own = float(10.0 ** rng.uniform(0, 4)) if seed % 5 else 0.0
    claimed = float(10.0 ** rng.uniform(-1, 9))
    clip = float(10.0 ** rng.uniform(-1, 1.2))
    age = float(rng.uniform(0.0, 1e3))
    cap = clip * (1.0 + own)
    c_own = jnp.asarray(own)
    c_clip = clip_peer_counts(c_own, jnp.asarray(claimed), clip)
    assert float(c_clip) <= cap + 1e-3 * max(cap, 1.0)
    _, w_peer = merge_weights("obs_count", c_own, c_clip,
                              jnp.asarray(age), jnp.asarray(0.0),
                              tau_l=300.0)
    bound = cap / max(own + cap, 1e-12)
    assert float(w_peer) <= bound + 1e-5
    if claimed >= cap:
        _, w_at_cap = merge_weights("obs_count", c_own, jnp.asarray(cap),
                                    jnp.asarray(age), jnp.asarray(0.0),
                                    tau_l=300.0)
        assert float(w_peer) == pytest.approx(float(w_at_cap), abs=1e-6)


@given(c=finite, a=finite)
@settings(max_examples=60, deadline=None)
def test_weights_symmetric_at_equal_inputs(c, a):
    """Equal instances split exactly 0.5/0.5 under every policy — including
    the both-counts-zero corner the obs_count fallback regression fixed
    (w_own used to come out 0/1 = 0 there)."""
    for policy in ("uniform", "obs_count", "staleness"):
        w1, w2 = merge_weights(policy, jnp.asarray(c), jnp.asarray(c),
                               jnp.asarray(a), jnp.asarray(a), tau_l=300.0)
        assert abs(float(w1) - 0.5) < 1e-5, policy
        assert abs(float(w1 + w2) - 1.0) < 1e-5, policy
