"""Stage models for the RMI: closed-form linear fits and small MLPs.

The paper (§3.3) uses two model families: 0-hidden-layer nets (= linear
regression, trained optimally in closed form) and 1-2 hidden-layer ReLU
nets of width 4-32.  Inputs may be scalars (numeric keys) or fixed-length
vectors (tokenized strings, §3.5).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# Closed-form linear regression (float64, numpy): exact, fast, the
# workhorse for last-stage models.
# --------------------------------------------------------------------------

def linear_fit(x: np.ndarray, y: np.ndarray) -> tuple[float, float]:
    """Least-squares fit y ≈ slope * x + intercept.  x, y are 1-D."""
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    n = x.size
    if n == 0:
        return 0.0, 0.0
    if n == 1:
        return 0.0, float(y[0])
    sx, sy = x.sum(), y.sum()
    sxx, sxy = (x * x).sum(), (x * y).sum()
    denom = n * sxx - sx * sx
    if abs(denom) < 1e-30:
        return 0.0, float(sy / n)
    slope = (n * sxy - sx * sy) / denom
    intercept = (sy - slope * sx) / n
    return float(slope), float(intercept)


def segmented_linear_fit(
    x: np.ndarray, y: np.ndarray, seg: np.ndarray, num_segments: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized per-segment least squares.

    Fits y ≈ a[s]*x + b[s] for every segment s in [0, num_segments).
    Empty segments are interpolated from their neighbours so that the
    piecewise model stays roughly monotone across the key space.

    Returns (slope, intercept, count) each of shape (num_segments,).
    """
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    seg = np.asarray(seg, dtype=np.int64)
    m = num_segments
    cnt = np.bincount(seg, minlength=m).astype(np.float64)
    sx = np.bincount(seg, weights=x, minlength=m)
    sy = np.bincount(seg, weights=y, minlength=m)
    sxx = np.bincount(seg, weights=x * x, minlength=m)
    sxy = np.bincount(seg, weights=x * y, minlength=m)
    denom = cnt * sxx - sx * sx
    safe = np.abs(denom) > 1e-30
    slope = np.zeros(m)
    intercept = np.zeros(m)
    np.divide(cnt * sxy - sx * sy, denom, out=slope, where=safe)
    with np.errstate(invalid="ignore"):
        mean_y = np.divide(sy, cnt, out=np.zeros(m), where=cnt > 0)
        mean_x = np.divide(sx, cnt, out=np.zeros(m), where=cnt > 0)
    intercept = np.where(safe, mean_y - slope * mean_x, mean_y)
    # Empty segments: linearly interpolate intercept from populated
    # neighbours, slope 0 — a query landing there gets a sane position
    # estimate (bounded by construction since no stored key maps there).
    empty = cnt == 0
    if empty.any() and (~empty).any():
        idx = np.arange(m)
        filled = idx[~empty]
        intercept[empty] = np.interp(idx[empty], filled, mean_y[~empty])
        slope[empty] = 0.0
    return slope, intercept, cnt


# --------------------------------------------------------------------------
# Small MLP (0-2 hidden layers, ReLU), trained with Adam in JAX.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLPSpec:
    in_dim: int = 1
    hidden: tuple = ()          # e.g. () linear, (32,), (16, 16)
    dtype: jnp.dtype = jnp.float32

    @property
    def num_params(self) -> int:
        dims = (self.in_dim, *self.hidden, 1)
        return sum((a + 1) * b for a, b in zip(dims[:-1], dims[1:]))

    @property
    def size_bytes(self) -> int:
        return self.num_params * np.dtype(np.float32).itemsize

    @property
    def flops_per_query(self) -> int:
        dims = (self.in_dim, *self.hidden, 1)
        return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def mlp_init(spec: MLPSpec, key: jax.Array) -> Dict[str, jax.Array]:
    dims = (spec.in_dim, *spec.hidden, 1)
    params = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        key, k1 = jax.random.split(key)
        params[f"w{i}"] = jax.random.normal(k1, (a, b), spec.dtype) * jnp.sqrt(
            2.0 / a
        )
        params[f"b{i}"] = jnp.zeros((b,), spec.dtype)
    return params


def dense_f32(h: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """``h @ w + b`` as broadcast f32 multiply-adds accumulated in input
    order.  A TPU runs a float32 matmul at reduced precision by default
    and a Pallas kernel cannot use the MXU at these widths, so the
    stage-0 model is evaluated this way everywhere — build, XLA lookups
    and the kernels (`kernels.rmi_lookup._stage0`) then agree bit for
    bit on every backend."""
    acc = h[:, 0:1] * w[0:1, :]
    for k in range(1, w.shape[0]):
        acc = acc + h[:, k:k + 1] * w[k:k + 1, :]
    return acc + b[None, :]


def mlp_apply(params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """x: (B,) scalar keys or (B, D) vector keys -> (B,) predictions."""
    h = x[:, None] if x.ndim == 1 else x
    n_layers = len(params) // 2
    for i in range(n_layers):
        h = dense_f32(h, params[f"w{i}"], params[f"b{i}"])
        if i < n_layers - 1:
            h = jnp.maximum(h, 0.0)
    return h[:, 0]


def mlp_train(
    spec: MLPSpec,
    x: np.ndarray,
    y: np.ndarray,
    *,
    steps: int = 400,
    lr: float = 1e-2,
    batch_size: int | None = 65536,
    seed: int = 0,
    verbose: bool = False,
) -> Dict[str, np.ndarray]:
    """Full- or mini-batch Adam on squared error.  Targets are scaled to
    [0, 1] internally; the output layer is rescaled at the end so the
    returned params predict raw positions directly."""
    x = np.asarray(x, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    y_scale = max(float(y.max()), 1.0)
    yn = y / y_scale

    if not spec.hidden:
        # closed form: no need to iterate.
        if x.ndim == 1:
            slope, intercept = linear_fit(x, y)
            return {
                "w0": np.array([[slope]], np.float32),
                "b0": np.array([intercept], np.float32),
            }
        # multivariate least squares with ridge for stability
        xd = np.asarray(x, np.float64)
        a = np.concatenate([xd, np.ones((xd.shape[0], 1))], axis=1)
        ata = a.T @ a + 1e-6 * np.eye(a.shape[1])
        w = np.linalg.solve(ata, a.T @ np.asarray(y, np.float64))
        return {
            "w0": w[:-1, None].astype(np.float32),
            "b0": w[-1:].astype(np.float32),
        }

    params = mlp_init(spec, jax.random.PRNGKey(seed))

    def loss_fn(p, xb, yb):
        pred = mlp_apply(p, xb)
        return jnp.mean((pred - yb) ** 2)

    # hand-rolled Adam (no optax dependency)
    beta1, beta2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def update(p, m, v, t, xb, yb):
        loss, g = jax.value_and_grad(loss_fn)(p, xb, yb)
        m = jax.tree.map(lambda m_, g_: beta1 * m_ + (1 - beta1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: beta2 * v_ + (1 - beta2) * g_ * g_, v, g)
        mhat = jax.tree.map(lambda m_: m_ / (1 - beta1**t), m)
        vhat = jax.tree.map(lambda v_: v_ / (1 - beta2**t), v)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * m_ / (jnp.sqrt(v_) + eps), p, mhat, vhat
        )
        return p, m, v, loss

    rng = np.random.default_rng(seed)
    n = x.shape[0]
    for t in range(1, steps + 1):
        if batch_size is not None and batch_size < n:
            idx = rng.integers(0, n, batch_size)
            xb, yb = x[idx], yn[idx]
        else:
            xb, yb = x, yn
        params, m, v, loss = update(params, m, v, float(t), xb, yb)
        if verbose and t % 100 == 0:
            print(f"  mlp step {t}: loss={float(loss):.3e}")

    params = jax.tree.map(np.asarray, params)
    # fold the target scale back into the last layer
    last = len(params) // 2 - 1
    params[f"w{last}"] = params[f"w{last}"] * y_scale
    params[f"b{last}"] = params[f"b{last}"] * y_scale
    return params
