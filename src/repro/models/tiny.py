"""Tiny flat-parameter models for the sim-substrate Gossip-Learning layer.

The simulator carries one parameter vector per node (``repro.sim.learn``),
so these models live on a **flat** ``(D,)`` float32 vector rather than a
pytree: merging is a row-wise convex combination (the ``gossip_merge_rows``
kernel) and the scan carry stays a single ``(N, D)`` array. ``TinySpec``
describes the architecture — ``logreg`` (multinomial logistic regression,
convex, the gossipy Hegedűs-2021 baseline's model) or ``mlp`` (one hidden
ReLU layer) — and the apply/loss/accuracy functions below accept arbitrary
leading batch axes on ``theta``, so per-node evaluation is plain
broadcasting, not a vmap tower.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["TinySpec", "param_dim", "init_theta", "tiny_logits", "tiny_loss",
           "tiny_accuracy"]


@dataclasses.dataclass(frozen=True)
class TinySpec:
    """Hashable architecture spec (rides frozen configs as a static field)."""

    model: str = "logreg"     # "logreg" | "mlp"
    n_features: int = 16
    n_classes: int = 2
    hidden: int = 16          # mlp only

    def __post_init__(self):
        if self.model not in ("logreg", "mlp"):
            raise ValueError(
                f"unknown tiny model {self.model!r}; known: 'logreg', 'mlp'"
            )
        if min(self.n_features, self.n_classes) < 1 or (
            self.model == "mlp" and self.hidden < 1
        ):
            raise ValueError("tiny model dims must be >= 1")

    @property
    def dim(self) -> int:
        return param_dim(self)


def param_dim(spec: TinySpec) -> int:
    """Length of the flat parameter vector."""
    f, c, h = spec.n_features, spec.n_classes, spec.hidden
    if spec.model == "logreg":
        return f * c + c
    return f * h + h + h * c + c


def init_theta(key, spec: TinySpec) -> jnp.ndarray:
    """Shared initialization (every replica starts from the same vector,
    as in gossip-learning baselines). Logreg starts at zero (convex);
    the MLP draws 1/sqrt(fan_in)-scaled normals to break symmetry."""
    if spec.model == "logreg":
        return jnp.zeros((param_dim(spec),), jnp.float32)
    f, c, h = spec.n_features, spec.n_classes, spec.hidden
    k1, k2 = jax.random.split(key)
    w1 = jax.random.normal(k1, (f, h), jnp.float32) / jnp.sqrt(float(f))
    w2 = jax.random.normal(k2, (h, c), jnp.float32) / jnp.sqrt(float(h))
    return jnp.concatenate([
        w1.reshape(-1), jnp.zeros((h,), jnp.float32),
        w2.reshape(-1), jnp.zeros((c,), jnp.float32),
    ])


def _unflatten(spec: TinySpec, theta):
    """Slice the flat vector into weight matrices; ``theta`` may carry
    arbitrary leading batch axes (the trailing axis is the parameter dim)."""
    f, c, h = spec.n_features, spec.n_classes, spec.hidden
    lead = theta.shape[:-1]
    if spec.model == "logreg":
        w = theta[..., : f * c].reshape(*lead, f, c)
        b = theta[..., f * c:]
        return (w, b)
    o1, o2, o3 = f * h, f * h + h, f * h + h + h * c
    w1 = theta[..., :o1].reshape(*lead, f, h)
    b1 = theta[..., o1:o2]
    w2 = theta[..., o2:o3].reshape(*lead, h, c)
    b2 = theta[..., o3:]
    return (w1, b1, w2, b2)


def tiny_logits(spec: TinySpec, theta, x):
    """Logits ``(..., B, C)`` from ``theta (..., D)`` and ``x (B, F)`` (or
    ``(..., B, F)`` matching theta's leading axes).

    The models are defined in float32: contractions pin ``HIGHEST``
    precision, which the TPU would otherwise run as one bfloat16 pass."""
    theta = theta.astype(jnp.float32)
    x = x.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    if spec.model == "logreg":
        w, b = _unflatten(spec, theta)
        return (jnp.einsum("...bf,...fc->...bc", x, w, precision=hi)
                + b[..., None, :])
    w1, b1, w2, b2 = _unflatten(spec, theta)
    hdn = jax.nn.relu(
        jnp.einsum("...bf,...fh->...bh", x, w1, precision=hi)
        + b1[..., None, :]
    )
    return (jnp.einsum("...bh,...hc->...bc", hdn, w2, precision=hi)
            + b2[..., None, :])


def tiny_loss(spec: TinySpec, theta, x, y):
    """Mean softmax cross-entropy of ``theta (D,)`` on batch ``x (B, F)``,
    ``y (B,)`` int labels."""
    logp = jax.nn.log_softmax(tiny_logits(spec, theta, x), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def tiny_accuracy(spec: TinySpec, theta, x, y):
    """Per-replica test accuracy ``(...,)``: fraction of ``x (B, F)``
    classified as ``y (B,)`` by each leading-axis parameter vector."""
    pred = jnp.argmax(tiny_logits(spec, theta, x), axis=-1)
    return jnp.mean((pred == y).astype(jnp.float32), axis=-1)
