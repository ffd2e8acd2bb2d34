"""Seeded generators of the benchmark's data, weights and requests.

Everything large is drawn on the device from ``--seed``. The models:

* dense: ``make_glm_data``'s (``repro.data.synthetic``) Gaussian features
  with a power-law covariance spectrum ``k^-cond_decay``, unit columns,
  and logistic labels from a random ``w_true``;
* sparse: each sample holds ``k_j`` distinct feature ids drawn from a
  power law of exponent ``alpha`` over the feature ranks (the feature
  skew of ``make_sparse_glm_data``), with document lengths ``k_j`` that
  follow its sample activity ``(j + 1)^-beta``; Gaussian values scaled to
  unit columns, logistic labels as above;
* requests: one sample each, ``k`` distinct ids drawn the same way.

A solver cell's dataset is one fixed draw, from the configuration's
``data_seed``, as a public dataset is one fixed file; the run's seed
orders its features (rows of ``X``). A permutation of the features
leaves the problem, and so every iteration count of the solve, as it
was, so each seed gives the same work on different inputs. Requests and
scoring weights are drawn from the run's seed, hashed to ids by a seeded
permutation; a tick's work does not depend on which ids it holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (64 bits are kept)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d", "cond_decay"))
def _dense_mixing(key, *, d, cond_decay):
    scales = jnp.arange(1, d + 1, dtype=jnp.float32) ** (-cond_decay)
    q, _ = jnp.linalg.qr(jax.random.normal(key, (d, d), jnp.float32))
    return q * jnp.sqrt(scales)[None, :]


@functools.partial(jax.jit, static_argnames=("nb",))
def _dense_block(a, key, w_true, perm, *, nb):
    x = jnp.dot(a, jax.random.normal(key, (a.shape[0], nb), jnp.float32),
                precision=HIGHEST)
    x = x / jnp.maximum(jnp.linalg.norm(x, axis=0, keepdims=True), 1e-12)
    return x[perm], jnp.dot(w_true, x, precision=HIGHEST)


@jax.jit
def _logistic_labels(key, margins):
    p = jax.nn.sigmoid(margins / jnp.maximum(jnp.std(margins), 1e-9))
    u = jax.random.uniform(key, margins.shape)
    return jnp.where(u < p, 1.0, -1.0).astype(jnp.float32)


def feature_order(seed: int, d: int) -> jax.Array:
    """The run's order of a dataset's ``d`` features."""
    return jax.random.permutation(key_from_seed(seed), d)


def dense_glm(data_seed: int, seed: int, d: int, n: int, cond_decay: float,
              block: int = 50_000) -> tuple[np.ndarray, np.ndarray]:
    """Dense ``X (d, n)`` f32 with unit columns, its features in the
    order of ``seed``, and labels ``y (n,)`` of +-1, on the host (the
    solver takes host arrays). Column blocks of ``block`` samples are
    drawn on the device one jitted call each, so the device never holds
    more than a block beside the mixing matrix."""
    k_mix, k_w, k_y, k_x = jax.random.split(key_from_seed(data_seed), 4)
    a = _dense_mixing(k_mix, d=d, cond_decay=cond_decay)
    w_true = jax.random.normal(k_w, (d,), jnp.float32) / np.sqrt(d)
    perm = feature_order(seed, d)
    nb = min(block, n)
    X = np.empty((d, n), np.float32)
    margins = np.empty(n, np.float32)
    for i, lo in enumerate(range(0, n, nb)):
        x, m = _dense_block(a, jax.random.fold_in(k_x, i), w_true, perm,
                            nb=nb)
        hi = min(lo + nb, n)
        X[:, lo:hi] = np.asarray(x)[:, : hi - lo]
        margins[lo:hi] = np.asarray(m)[: hi - lo]
    y = np.asarray(_logistic_labels(k_y, jnp.asarray(margins)))
    return X, y


# ---------------------------------------------------------------------------
# sparse ids
# ---------------------------------------------------------------------------

def _powerlaw_ranks(key, shape, d: int, alpha: float):
    """Ranks in [0, d) with P(rank r) ~ (r + 1)^-alpha (continuous inverse
    of the power law on [1, d + 1), floored)."""
    u = jax.random.uniform(key, shape, jnp.float32)
    a = 1.0 - alpha
    x = (1.0 + u * ((d + 1.0) ** a - 1.0)) ** (1.0 / a)
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, d - 1)


def oversample(k: int) -> int:
    """Candidates drawn per row to find ``k`` distinct ids."""
    return 4 * k + 64


def distinct_ranks(key, rows: int, k: int, d: int, alpha: float):
    """``(rows, k)`` feature ranks, distinct within each row, and an
    ``ok`` mask.

    Candidates are drawn with replacement and the first ``k`` distinct
    ones are kept in draw order: successive sampling without
    replacement, proportional to the power law. ``ok`` is False only
    where a row found fewer than ``k`` distinct candidates (vanishingly
    rare at ``oversample(k)``), and such slots must be dropped.
    """
    m = oversample(k)
    if d * m >= 1 << 31:
        raise ValueError(f"d * candidates = {d * m} overflows int32")
    cand = _powerlaw_ranks(key, (rows, m), d, alpha)
    pos = jnp.arange(m, dtype=jnp.int32)
    srt = jnp.sort(cand * m + pos[None, :], axis=1)
    val, p = srt // m, srt % m
    first = jnp.concatenate(
        [jnp.ones((rows, 1), bool), val[:, 1:] != val[:, :-1]], axis=1)
    firsts = jnp.sort(jnp.where(first, p, m), axis=1)[:, :k]
    ok = firsts < m
    ranks = jnp.take_along_axis(cand, jnp.minimum(firsts, m - 1), axis=1)
    return ranks.astype(jnp.int32), ok


def doc_lengths(n: int, mean: float, beta: float, k_min: int,
                k_max: int) -> np.ndarray:
    """Nonzeros per sample: activity ``(j + 1)^-beta`` scaled so that the
    lengths, rounded and clipped to ``[k_min, k_max]``, average ``mean``
    (to within rounding). Sorted longest first; no randomness."""
    act = np.arange(1, n + 1, dtype=np.float64) ** (-beta)
    lo, hi = 0.0, float(mean) * n / act.sum() * 64
    for _ in range(100):                                   # bisection
        s = 0.5 * (lo + hi)
        k = np.clip(np.rint(s * act), k_min, k_max)
        lo, hi = (s, hi) if k.mean() < mean else (lo, s)
    return np.clip(np.rint(hi * act), k_min, k_max).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("d", "alpha", "k_max"))
def _sparse_rows(key, lengths, order, *, d, alpha, k_max):
    k_ids, k_val, k_w, k_y, k_len, k_hash = jax.random.split(key, 6)
    n = lengths.shape[0]
    lengths = jax.random.permutation(k_len, lengths)
    ranks, ok = distinct_ranks(k_ids, n, k_max, d, alpha)
    active = ok & (jnp.arange(k_max)[None, :] < lengths[:, None])
    vals = jnp.where(active, jax.random.normal(k_val, ranks.shape), 0.0)
    vals = vals / jnp.maximum(
        jnp.linalg.norm(vals, axis=1, keepdims=True), 1e-12)
    w_true = jax.random.normal(k_w, (d,), jnp.float32) / np.sqrt(d)
    margins = jnp.sum(vals * w_true[ranks], axis=1)
    ids = order[jax.random.permutation(k_hash, d)[ranks]]
    return ids, vals, active, _logistic_labels(k_y, margins)


def sparse_glm(data_seed: int, seed: int, d: int, n: int, mean_nnz: float,
               alpha: float, beta: float, k_min: int, k_max: int):
    """Sparse samples as host COO: ``(feature_ids, sample_ids, values)``
    with unit columns, its features in the order of ``seed``, and labels
    ``y (n,)`` of +-1."""
    lengths = doc_lengths(n, mean_nnz, beta, k_min, k_max)
    ids, vals, active, y = _sparse_rows(
        key_from_seed(data_seed), jnp.asarray(lengths),
        feature_order(seed, d), d=d, alpha=alpha, k_max=k_max)
    active = np.asarray(active)
    rows = np.broadcast_to(np.arange(n)[:, None], active.shape)[active]
    return (np.asarray(ids)[active], rows.astype(np.int64),
            np.asarray(vals)[active], np.asarray(y))


# ---------------------------------------------------------------------------
# scoring: weights and requests
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d", "count", "k", "alpha"))
def _requests(key, *, d, count, k, alpha):
    k_ids, k_w, k_hash = jax.random.split(key, 3)
    ranks, ok = distinct_ranks(k_ids, count, k, d, alpha)
    ids = jax.random.permutation(k_hash, d)[ranks]      # hashed ids
    vals = jnp.where(ok, 1.0, 0.0)
    vals = vals / jnp.maximum(
        jnp.linalg.norm(vals, axis=1, keepdims=True), 1e-12)
    w = jax.random.normal(k_w, (d,), jnp.float32)
    return ids, vals, ok, w


def scoring_data(seed: int, d: int, count: int, k: int, alpha: float):
    """Model weights ``w (d,)`` f32 and ``count`` requests of ``k``
    distinct ids with equal values of unit norm, on the host as lists
    of ``(ids, values)``."""
    ids, vals, ok, w = _requests(key_from_seed(seed), d=d, count=count,
                                 k=k, alpha=alpha)
    ids, vals, ok = np.asarray(ids), np.asarray(vals), np.asarray(ok)
    reqs = [(ids[i][ok[i]].astype(np.int64), vals[i][ok[i]])
            for i in range(count)]
    return np.asarray(w), reqs
