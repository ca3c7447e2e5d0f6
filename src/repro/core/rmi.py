"""The Recursive Model Index (paper §3.2) — TPU-native, batched.

Two stages (the paper's best configuration throughout §3.6):

  stage 0: one model (linear or small ReLU MLP) over the whole key space;
           its prediction picks one of M leaf models:
           ``leaf = clip(floor(f0(x) * M / N), 0, M-1)``.
  stage 1: M linear models stored structure-of-arrays — slope[M],
           intercept[M] (vector keys: W[M, D], b[M]) — plus per-leaf
           min/max residual bounds and residual σ for the biased
           searches.

Inference is fully vectorized: stage 0 is a single batched matmul, leaf
selection one gather, leaf evaluation one fused multiply-add, and the
final search a fixed-trip-count branchless binary search
(`core.search`).  This is the "entire index as a (sparse)
matrix-multiplication for a TPU" representation the paper sketches at
the end of §3.2.

Error-bound contract (paper §2): bounds are computed *post hoc* over the
stored keys with exactly the float32 arithmetic used at lookup time, so
any stored key is guaranteed to fall inside its leaf's window.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as search_lib
from repro.core.keys import KeySet, VectorKeySet
from repro.core.models import (
    MLPSpec,
    mlp_apply,
    mlp_train,
    segmented_linear_fit,
)


@dataclasses.dataclass
class RMIConfig:
    """Index specification — what LIF grid-searches over."""

    num_leaves: int = 10_000
    stage0_hidden: tuple = (16, 16)   # () = linear stage-0
    stage0_train_steps: int = 300
    stage0_sample: Optional[int] = 200_000  # train stage-0 on a sample
    stage0_lr: float = 1e-2
    hybrid_threshold: Optional[int] = None  # Algorithm 1 line 13; None = pure RMI
    seed: int = 0


@dataclasses.dataclass
class RMIndex:
    """Built index: numpy SoA + static metadata.

    All arrays are host numpy; `as_pytree()` yields the jnp view used by
    jitted lookups and the Pallas kernel.
    """

    config: RMIConfig
    n: int
    num_leaves: int
    in_dim: int
    stage0_params: Dict[str, np.ndarray]
    leaf_w: np.ndarray          # (M,) scalar keys or (M, D) vector keys
    leaf_b: np.ndarray          # (M,)
    err_lo: np.ndarray          # (M,) float32 <= 0
    err_hi: np.ndarray          # (M,) float32 >= 0
    sigma: np.ndarray           # (M,) float32
    is_btree: np.ndarray        # (M,) bool — hybrid leaves (Algorithm 1)
    seg_lo: np.ndarray          # (M,) int32 first position covered by leaf
    seg_hi: np.ndarray          # (M,) int32 last position covered by leaf
    max_window: int             # static worst-case search window

    # ---- reporting ------------------------------------------------------
    @property
    def model_size_bytes(self) -> int:
        """Paper-style size: model parameters only (Fig 4-6 'Size (MB)')."""
        s0 = sum(int(p.size) for p in self.stage0_params.values()) * 4
        leaves = int(self.leaf_w.size + self.leaf_b.size) * 4
        return s0 + leaves

    @property
    def total_size_bytes(self) -> int:
        """Size including the error-bound metadata arrays."""
        meta = int(
            self.err_lo.size + self.err_hi.size + self.sigma.size
        ) * 4 + int(self.is_btree.size) + int(self.seg_lo.size + self.seg_hi.size) * 4
        return self.model_size_bytes + meta

    @property
    def mean_abs_err(self) -> float:
        return float(np.mean((self.err_hi - self.err_lo) / 2.0))

    @property
    def err_variance(self) -> float:
        return float(np.var((self.err_hi - self.err_lo) / 2.0))

    def as_pytree(self) -> Dict[str, jnp.ndarray]:
        t = {
            "leaf_w": jnp.asarray(self.leaf_w),
            "leaf_b": jnp.asarray(self.leaf_b),
            "err_lo": jnp.asarray(self.err_lo),
            "err_hi": jnp.asarray(self.err_hi),
            "sigma": jnp.asarray(self.sigma),
            "seg_lo": jnp.asarray(self.seg_lo),
            "seg_hi": jnp.asarray(self.seg_hi),
            "is_btree": jnp.asarray(self.is_btree),
        }
        for k, v in self.stage0_params.items():
            t[f"s0_{k}"] = jnp.asarray(v)
        return t


def _stage0_apply(tree: Dict[str, jnp.ndarray], q: jnp.ndarray) -> jnp.ndarray:
    params = {k[3:]: v for k, v in tree.items() if k.startswith("s0_")}
    return mlp_apply(params, q)


def rmi_predict(
    tree: Dict[str, jnp.ndarray],
    q: jnp.ndarray,
    *,
    n: int,
    num_leaves: int,
) -> Tuple[jnp.ndarray, ...]:
    """Pure function: queries -> (pos, lo, hi, sigma).  jit-friendly.

    q: (B,) normalized scalar keys or (B, D) normalized vector keys.
    Returns float32 position estimates and per-query int32 window
    [lo, hi] (inclusive) plus σ for biased searches.
    """
    p0 = _stage0_apply(tree, q)
    leaf = jnp.clip(
        jnp.floor(p0 * (num_leaves / n)).astype(jnp.int32), 0, num_leaves - 1
    )
    w = tree["leaf_w"][leaf]
    b = tree["leaf_b"][leaf]
    if q.ndim == 1:
        pos = w * q + b
    else:
        pos = jnp.sum(w * q, axis=-1) + b
    pos = jnp.clip(pos, 0.0, float(n - 1))
    # hybrid leaves (Algorithm 1): window = the leaf's full key range
    lo_m = pos + tree["err_lo"][leaf]
    hi_m = pos + tree["err_hi"][leaf]
    lo = jnp.where(tree["is_btree"][leaf], tree["seg_lo"][leaf].astype(jnp.float32), lo_m)
    hi = jnp.where(tree["is_btree"][leaf], tree["seg_hi"][leaf].astype(jnp.float32), hi_m)
    return pos, lo, hi, tree["sigma"][leaf]


def rmi_lookup(
    tree: Dict[str, jnp.ndarray],
    sorted_keys: jnp.ndarray,
    q: jnp.ndarray,
    *,
    n: int,
    num_leaves: int,
    max_window: int,
    strategy: str = "binary",
) -> jnp.ndarray:
    """Full lookup: predict + error-bounded search.  Returns lower-bound
    indices into `sorted_keys` (normalized, same dtype as q)."""
    pos, lo, hi, sig = rmi_predict(tree, q, n=n, num_leaves=num_leaves)
    err_lo = lo - pos
    err_hi = hi - pos
    fn = search_lib.STRATEGIES[strategy]
    if strategy == "binary":
        return fn(sorted_keys, _q1(q), pos, err_lo, err_hi, max_window)
    return fn(sorted_keys, _q1(q), pos, err_lo, err_hi, sig, max_window)


def _q1(q: jnp.ndarray) -> jnp.ndarray:
    """Scalar comparison key for the search: vector keys compare by their
    tokenized prefix folded to a scalar via the sorted array itself —
    callers pass scalar keys for the search array; for vector keys the
    search array must be the matching scalar projection (see
    strings.sort_key)."""
    return q if q.ndim == 1 else q[:, 0]


# --------------------------------------------------------------------------
# Builder (stage-wise training, Algorithm 1)
# --------------------------------------------------------------------------

def stage0_segments(
    stage0_params: Dict[str, np.ndarray], norm: np.ndarray, *, n: int, m: int
) -> np.ndarray:
    """Leaf assignment for every key with lookup-time arithmetic."""
    pred0 = np.asarray(
        jax.jit(
            lambda q: mlp_apply(
                {k: jnp.asarray(v) for k, v in stage0_params.items()}, q
            )
        )(norm)
    )
    return np.clip(np.floor(pred0 * (m / n)).astype(np.int64), 0, m - 1)


def build_rmi(
    keys: Union[KeySet, VectorKeySet],
    config: RMIConfig,
    *,
    verbose: bool = False,
) -> RMIndex:
    norm = keys.norm
    n = keys.n
    m = config.num_leaves
    y = np.arange(n, dtype=np.float32)
    in_dim = 1 if norm.ndim == 1 else norm.shape[1]

    # ---- stage 0 ---------------------------------------------------------
    spec = MLPSpec(in_dim=in_dim, hidden=tuple(config.stage0_hidden))
    if config.stage0_sample is not None and config.stage0_sample < n:
        idx = np.linspace(0, n - 1, config.stage0_sample).astype(np.int64)
        x0, y0 = norm[idx], y[idx]
    else:
        x0, y0 = norm, y
    s0 = mlp_train(
        spec,
        x0,
        y0,
        steps=config.stage0_train_steps,
        lr=config.stage0_lr,
        seed=config.seed,
        verbose=verbose,
    )
    s0 = {k: np.asarray(v) for k, v in s0.items()}
    seg = stage0_segments(s0, norm, n=n, m=m)

    # ---- stage 1: per-leaf linear fits ------------------------------------
    if in_dim == 1:
        slope, intercept, cnt = segmented_linear_fit(norm, y, seg, m)
        leaf_w = slope.astype(np.float32)
        leaf_b = intercept.astype(np.float32)
    else:
        leaf_w, leaf_b, cnt = _segmented_multivariate_fit(norm, y, seg, m)
    return _finalize_rmi(
        config, n, in_dim, s0, leaf_w.astype(np.float32),
        leaf_b.astype(np.float32), cnt, norm, y, seg, verbose=verbose,
    )


def _finalize_rmi(
    config: RMIConfig,
    n: int,
    in_dim: int,
    s0: Dict[str, np.ndarray],
    leaf_w: np.ndarray,
    leaf_b: np.ndarray,
    cnt: np.ndarray,
    norm: np.ndarray,
    y: np.ndarray,
    seg: np.ndarray,
    *,
    verbose: bool = False,
) -> RMIndex:
    """Error bounds, per-leaf spans, hybrid replacement, final RMIndex.

    Always recomputed over *all* keys with the final leaf parameters, so
    the B-Tree-strength window guarantee holds no matter how the leaf
    parameters were obtained (cold fit or warm reuse in `refit_rmi`).
    """
    m = config.num_leaves
    prod = leaf_w[seg] * norm
    if in_dim == 1:
        pred1 = prod + leaf_b[seg]
    else:
        pred1 = np.sum(prod, axis=-1) + leaf_b[seg]
        prod = np.sum(np.abs(prod), axis=-1)
    pred1 = np.clip(pred1.astype(np.float32), 0.0, float(n - 1))

    # ---- residual bounds (the B-Tree-strength guarantee) -------------------
    # A lookup may evaluate the leaf FMA with one rounding (a fused
    # multiply-add: XLA on CPU) or two (NumPy here, the TPU), which can
    # differ by half an ulp of the product plus an ulp of the position,
    # and adds the bound to the position in f32 (another half ulp once
    # positions pass 2^24).  Widening each key's residual by that slack
    # keeps every stored key inside its window on every backend.
    slack = (np.spacing(np.abs(prod).astype(np.float32))
             + 2 * np.spacing(np.float32(n)))
    resid = np.arange(n, dtype=np.float64) - pred1
    err_lo = np.zeros(m, np.float32)
    err_hi = np.zeros(m, np.float32)
    np.minimum.at(err_lo, seg, np.floor(resid - slack).astype(np.float32))
    np.maximum.at(err_hi, seg, np.ceil(resid + slack).astype(np.float32))
    # σ per leaf
    sums = np.bincount(seg, weights=resid, minlength=m)
    sqs = np.bincount(seg, weights=resid * resid, minlength=m)
    with np.errstate(invalid="ignore"):
        mean = np.divide(sums, cnt, out=np.zeros(m), where=cnt > 0)
        var = np.divide(sqs, cnt, out=np.zeros(m), where=cnt > 0) - mean**2
    sigma = np.sqrt(np.maximum(var, 0.0)).astype(np.float32)

    # ---- segment coverage (for hybrid windows) -----------------------------
    seg_lo = np.full(m, n - 1, np.int64)
    seg_hi = np.zeros(m, np.int64)
    pos_idx = np.arange(n, dtype=np.int64)
    np.minimum.at(seg_lo, seg, pos_idx)
    np.maximum.at(seg_hi, seg, pos_idx)
    seg_lo[cnt == 0] = 0
    seg_hi[cnt == 0] = 0

    # ---- Algorithm 1 lines 11-14: hybrid replacement ------------------------
    max_abs = np.maximum(np.abs(err_lo), np.abs(err_hi))
    if config.hybrid_threshold is not None:
        is_btree = max_abs > config.hybrid_threshold
    else:
        is_btree = np.zeros(m, bool)

    window = np.where(
        is_btree, (seg_hi - seg_lo).astype(np.float32), err_hi - err_lo
    )
    max_window = int(window.max()) + 2

    idx = RMIndex(
        config=config,
        n=n,
        num_leaves=m,
        in_dim=in_dim,
        stage0_params={k: np.asarray(v) for k, v in s0.items()},
        leaf_w=leaf_w.astype(np.float32),
        leaf_b=leaf_b.astype(np.float32),
        err_lo=err_lo,
        err_hi=err_hi,
        sigma=sigma,
        is_btree=is_btree,
        seg_lo=seg_lo.astype(np.int32),
        seg_hi=seg_hi.astype(np.int32),
        max_window=max_window,
    )
    if verbose:
        print(
            f"RMI built: n={n} leaves={m} mean|err|={idx.mean_abs_err:.1f} "
            f"max_window={max_window} hybrid_leaves={int(is_btree.sum())} "
            f"size={idx.model_size_bytes/1e6:.2f}MB"
        )
    return idx


def _segmented_multivariate_fit(
    x: np.ndarray, y: np.ndarray, seg: np.ndarray, m: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-segment ridge least squares for vector keys, chunked accumulation."""
    n, d = x.shape
    da = d + 1
    ata = np.zeros((m, da, da), np.float64)
    aty = np.zeros((m, da), np.float64)
    cnt = np.bincount(seg, minlength=m).astype(np.float64)
    chunk = max(1, int(5e7 // (da * da)))
    xd = np.asarray(x, np.float64)
    yd = np.asarray(y, np.float64)
    for s in range(0, n, chunk):
        e = min(n, s + chunk)
        a = np.concatenate([xd[s:e], np.ones((e - s, 1))], axis=1)
        np.add.at(ata, seg[s:e], a[:, :, None] * a[:, None, :])
        np.add.at(aty, seg[s:e], a * yd[s:e, None])
    ata += 1e-6 * np.eye(da)[None]
    sol = np.linalg.solve(ata, aty[..., None])[..., 0]
    return sol[:, :d].astype(np.float32), sol[:, d].astype(np.float32), cnt


# --------------------------------------------------------------------------
# Warm-start refit (the index_service compaction path)
# --------------------------------------------------------------------------

def refit_rmi(
    old: RMIndex,
    old_keys: KeySet,
    new_keys: KeySet,
    *,
    config: Optional[RMIConfig] = None,
    verbose: bool = False,
) -> Tuple[RMIndex, int]:
    """Warm-start rebuild after the key set changed (e.g. a delta-buffer
    compaction merged inserts/deletes into the base array).

    Stage 0 is reused verbatim — no gradient steps — with its input
    layer affine-rescaled for the new normalization constants and its
    output layer scaled by n_new/n_old.  Stage-1 leaves whose spans hold
    exactly the same raw keys as before (merely shifted by upstream
    inserts/deletes) keep their learned slope, with the intercept
    translated by the shift; only changed leaves get fresh fits.  Error
    bounds are recomputed over *all* keys by `_finalize_rmi`, so the
    lookup guarantee never depends on the change detection — a missed
    or spurious "clean" verdict costs fit quality, not correctness.

    Returns (index, num_leaves_refit).  Scalar keys only, and the leaf
    count must match `old`; callers fall back to `build_rmi` otherwise.
    """
    cfg = config or old.config
    if old.in_dim != 1 or new_keys.norm.ndim != 1:
        raise ValueError("refit_rmi supports scalar keys only")
    if cfg.num_leaves != old.num_leaves:
        raise ValueError("refit_rmi needs an unchanged leaf count")

    norm = new_keys.norm
    n = new_keys.n
    n_old = old.n
    m = cfg.num_leaves
    y = np.arange(n, dtype=np.float32)

    # affine map between normalization frames: x_old = a * x_new + c
    span_old = old_keys.hi - old_keys.lo
    span_new = new_keys.hi - new_keys.lo
    a = span_new / span_old
    c = (new_keys.lo - old_keys.lo) / span_old

    s0 = {k: np.asarray(v, np.float64) for k, v in old.stage0_params.items()}
    n_layers = len(s0) // 2
    s0["b0"] = s0["b0"] + c * s0["w0"][0]
    s0["w0"] = s0["w0"] * a
    last = n_layers - 1
    r = n / n_old  # uniform-growth output correction
    s0[f"w{last}"] = s0[f"w{last}"] * r
    s0[f"b{last}"] = s0[f"b{last}"] * r
    s0 = {k: v.astype(np.float32) for k, v in s0.items()}

    seg = stage0_segments(s0, norm, n=n, m=m)
    cnt = np.bincount(seg, minlength=m).astype(np.float64)
    seg_lo = np.full(m, n, np.int64)
    seg_hi = np.full(m, -1, np.int64)
    pos_idx = np.arange(n, dtype=np.int64)
    np.minimum.at(seg_lo, seg, pos_idx)
    np.maximum.at(seg_hi, seg, pos_idx)

    # fresh fits everywhere (vectorized bincount passes — the cheap part),
    # then carry over clean leaves
    slope, intercept, _ = segmented_linear_fit(norm, y, seg, m)
    leaf_w = slope.astype(np.float64)
    leaf_b = intercept.astype(np.float64)

    old_raw, new_raw = old_keys.raw, new_keys.raw
    old_lo = old.seg_lo.astype(np.int64)
    old_hi = old.seg_hi.astype(np.int64)
    num_refit = 0
    for leaf in np.nonzero(cnt > 0)[0]:
        nlo, nhi = seg_lo[leaf], seg_hi[leaf]
        olo, ohi = old_lo[leaf], old_hi[leaf]
        if (
            nhi - nlo == ohi - olo
            and np.array_equal(new_raw[nlo : nhi + 1], old_raw[olo : ohi + 1])
        ):
            # identical keys, uniformly shifted positions: rescale params
            w = float(old.leaf_w[leaf])
            leaf_w[leaf] = w * a
            leaf_b[leaf] = float(old.leaf_b[leaf]) + w * c + float(nlo - olo)
        else:
            num_refit += 1

    idx = _finalize_rmi(
        cfg, n, 1, s0, leaf_w.astype(np.float32), leaf_b.astype(np.float32),
        cnt, norm, y, seg, verbose=False,
    )
    if verbose:
        print(
            f"RMI refit: n={n_old}->{n} leaves_refit={num_refit}/{m} "
            f"max_window={idx.max_window}"
        )
    return idx, num_refit


# --------------------------------------------------------------------------
# Convenience: compiled end-to-end lookup closure (what LIF §3.1 emits)
# --------------------------------------------------------------------------

def compile_lookup(index: RMIndex, keys: Union[KeySet, VectorKeySet], strategy: str = "binary"):
    """Returns a jitted fn: raw queries (already normalized) -> indices."""
    tree = index.as_pytree()
    if isinstance(keys, VectorKeySet):
        sorted_scalar = jnp.asarray(keys.norm[:, 0])
    else:
        sorted_scalar = jnp.asarray(keys.norm)
    n, m, w = index.n, index.num_leaves, index.max_window

    @jax.jit
    def lookup(q):
        return rmi_lookup(
            tree, sorted_scalar, q, n=n, num_leaves=m, max_window=w,
            strategy=strategy,
        )

    return lookup
