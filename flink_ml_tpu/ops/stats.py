"""Statistical test cores: chi-square, ANOVA F, F-value (regression).

TPU-native re-design of the math inside stats/chisqtest/ChiSqTest.java,
stats/anovatest/ANOVATest.java:194-235 and stats/fvaluetest/FValueTest.java.
The reference computes contingency tables / group sums with keyed shuffles;
here they are vectorized one-hot contractions. All arithmetic is float64
(the reference uses commons-math doubles; float32 would visibly shift
p-values) with the p-values from ops/special.py. Shared by the stats stages
and UnivariateFeatureSelector.java:305.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from .special import betainc_reg, gammainc_p


def chi2_sf(x, df):
    """P[Chi2(df) > x] = 1 - P(df/2, x/2) (regularized lower inc. gamma)."""
    return 1.0 - gammainc_p(np.asarray(df) / 2.0, np.asarray(x) / 2.0)


def f_sf(x, dfn, dfd):
    """P[F(dfn, dfd) > x] via the regularized incomplete beta function."""
    x = np.maximum(np.asarray(x, dtype=np.float64), 0.0)
    return betainc_reg(dfd / 2.0, dfn / 2.0, dfd / (dfd + dfn * x))


def chi_square_test(
    X: np.ndarray, y: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pearson chi-square independence test of each categorical feature
    column against a categorical label. Returns (p_values, dofs, statistics).

    Mirrors ChiSqTest.java's contingency-table computation: observed counts
    via a one-hot x one-hot contraction per feature, expected from the
    marginals.
    """
    X = np.asarray(X, dtype=np.float64)
    y = np.asarray(y)
    n, d = X.shape
    y_cats, y_idx = np.unique(y, return_inverse=True)
    k = len(y_cats)
    p_values, dofs, stats = [], [], []
    for j in range(d):
        f_cats, f_idx = np.unique(X[:, j], return_inverse=True)
        m = len(f_cats)
        # O(n) contingency table; a dense one-hot matmul would be O(n*m*k)
        observed = np.bincount(f_idx * k + y_idx, minlength=m * k).reshape(m, k).astype(np.float64)
        row = observed.sum(axis=1, keepdims=True)
        col = observed.sum(axis=0, keepdims=True)
        expected = row * col / n
        with np.errstate(divide="ignore", invalid="ignore"):
            stat = float(
                np.sum(np.where(expected > 0, (observed - expected) ** 2 / expected, 0.0))
            )
        dof = (m - 1) * (k - 1)
        p = float(chi2_sf(stat, float(dof))) if dof > 0 else 1.0
        p_values.append(p)
        dofs.append(dof)
        stats.append(stat)
    return np.asarray(p_values), np.asarray(dofs, dtype=np.int64), np.asarray(stats)


def _is_jax(x) -> bool:
    try:
        import jax

        return isinstance(x, jax.Array)
    except ImportError:  # pragma: no cover
        return False


from ..utils.lazyjit import keyed_jit, lazy_jit


def _nunique_impl(y):
    import jax.numpy as jnp

    s = jnp.sort(y)
    return 1 + jnp.sum(s[1:] != s[:-1])


_nunique_device = lazy_jit(_nunique_impl)


def _make_unique_kernel(k):
    import jax.numpy as jnp

    return lambda y: jnp.unique(y, size=k)


_unique_kernel = keyed_jit(_make_unique_kernel)


def _unique_device(y, k):
    return _unique_kernel(k)(y)


def _make_anova_kernel(k):
    """Kernel per class count k (keyed_jit caches the compiled wrapper —
    a jit created inside the call would RECOMPILE on every fit, seconds
    per call)."""
    import jax
    import jax.numpy as jnp

    def go(X, y, classes):
        # center per feature first: the ANOVA decomposition is invariant
        # under per-feature shifts, and centering keeps the float32
        # sums-of-squares differences from catastrophically cancelling
        # when |mean| >> within-class std
        Xc = X - jnp.mean(X, axis=0, keepdims=True)
        y_idx = jnp.searchsorted(classes, y)
        onehot = jax.nn.one_hot(y_idx, k, dtype=X.dtype)  # (n, k)
        sums = onehot.T @ Xc  # (k, d)
        counts = jnp.sum(onehot, axis=0)  # (k,)
        total_sq = jnp.sum(Xc * Xc, axis=0)  # (d,)
        top = jnp.concatenate([sums, counts[:, None]], axis=1)
        bottom = jnp.concatenate([total_sq[None, :], jnp.zeros((1, 1), X.dtype)], axis=1)
        pad = jnp.zeros((1, X.shape[1] + 1), X.dtype)
        return jnp.concatenate([top, bottom, pad], axis=0)

    return go


_anova_sums_kernel = keyed_jit(_make_anova_kernel)


def _anova_device_sums(X, y_dev, classes, k):
    """Per-class sums/counts/total-squares as MXU matmuls on device,
    packed into one (k + 2, d + 1) array for a single readback."""
    import jax.numpy as jnp

    go = _anova_sums_kernel(k)
    packed = np.asarray(go(X, jnp.asarray(y_dev, X.dtype), classes)).astype(np.float64)
    sums = packed[:k, :-1]
    counts = packed[:k, -1]
    total_sq = packed[k, :-1]
    return sums, counts, total_sq


def anova_f_test(
    X: np.ndarray, y: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One-way ANOVA F-test of each continuous feature against a categorical
    label. Returns (p_values, dofs, f_statistics) with the reference's
    reported dof = (k - 1) + (n - k) = n - 1 (ANOVATest.java:232).

    Device-resident X stays on device: the per-class aggregation is a
    one-hot MXU matmul with a single small readback (pulling a 10M x 100
    benchmark table to the single-core host costs minutes)."""
    if _is_jax(X):
        # keep y on device too: pulling a 10M-row label column is a bulk
        # D2H copy; class discovery reads back only the (k,) class
        # values and the kernel maps labels by searchsorted in-program
        import jax.numpy as jnp

        y_dev = y if _is_jax(y) else jnp.asarray(np.asarray(y))
        n, d = X.shape
        from ..utils.packing import packed_device_get

        k = int(packed_device_get(_nunique_device(y_dev), sync_kind="fit")[0])
        classes = _unique_device(y_dev, k)
        sums, counts, total_sq = _anova_device_sums(X, y_dev, classes, k)
    else:
        y = np.asarray(y)
        y_cats, y_idx = np.unique(y, return_inverse=True)
        k = len(y_cats)
        X = np.asarray(X, dtype=np.float64)
        n, d = X.shape
        y_onehot = np.eye(k)[y_idx]
        counts = y_onehot.sum(axis=0)  # (k,)
        sums = y_onehot.T @ X  # (k, d)
        total_sq = (X * X).sum(axis=0)
    total_sum = sums.sum(axis=0)
    ss_tot = total_sq - total_sum**2 / n
    ss_between = (sums**2 / counts[:, None]).sum(axis=0) - total_sum**2 / n
    ss_within = ss_tot - ss_between
    dfn, dfd = k - 1, n - k
    with np.errstate(divide="ignore", invalid="ignore"):
        f_stat = (ss_between / dfn) / (ss_within / dfd)
    f_stat = np.nan_to_num(f_stat, nan=0.0, posinf=np.inf)
    p = f_sf(f_stat, float(dfn), float(dfd))
    return p, np.full(d, dfn + dfd, dtype=np.int64), f_stat


def _centered_moments_impl(X, y):
    # center both sides in-program: the naive sum_x2 - n*xm^2 form
    # catastrophically cancels in float32 when |mean| >> std. Packs
    # rows [sum (x-xm)^2 ..., sum (y-ym)^2] and [sum (x-xm)(y-ym) ..., 0]
    # for one readback (y stays on device — no 40MB label pull).
    import jax.numpy as jnp

    Xc = X - jnp.mean(X, axis=0, keepdims=True)
    yc = y - jnp.mean(y)
    ss_y = jnp.sum(yc * yc)
    row0 = jnp.concatenate([jnp.sum(Xc * Xc, axis=0), ss_y[None]])
    row1 = jnp.concatenate([Xc.T @ yc, jnp.zeros((1,), X.dtype)])
    return jnp.stack([row0, row1])


_centered_moments = lazy_jit(_centered_moments_impl)


def f_value_test(
    X: np.ndarray, y: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Univariate linear-regression F-test of each continuous feature against
    a continuous label (FValueTest.java). Returns (p_values, dofs, f_stats)
    with dof = n - 2."""
    if _is_jax(X):
        import jax.numpy as jnp

        y_dev = (
            y
            if _is_jax(y) and y.dtype == X.dtype
            else jnp.asarray(np.asarray(y) if not _is_jax(y) else y, X.dtype)
        )
        n, d = X.shape
        from ..utils.packing import packed_device_get

        m = packed_device_get(_centered_moments(X, y_dev), sync_kind="fit")[
            0
        ].astype(np.float64)
        ss_x, num = m[0][:-1], m[1][:-1]
        ss_y = m[0][-1]
        den = np.sqrt(ss_x * ss_y)
    else:
        y = np.asarray(y, dtype=np.float64)
        X = np.asarray(X, dtype=np.float64)
        n, d = X.shape
        xm = X.mean(axis=0)
        ym = y.mean()
        num = ((X - xm) * (y - ym)[:, None]).sum(axis=0)
        den = np.sqrt(((X - xm) ** 2).sum(axis=0) * ((y - ym) ** 2).sum())
    with np.errstate(divide="ignore", invalid="ignore"):
        corr = np.where(den > 0, num / den, 0.0)
    dfd = n - 2
    with np.errstate(divide="ignore", invalid="ignore"):
        f_stat = corr**2 / (1 - corr**2) * dfd
    f_stat = np.nan_to_num(f_stat, nan=0.0, posinf=np.inf)
    p = f_sf(f_stat, 1.0, float(dfd))
    return p, np.full(d, dfd, dtype=np.int64), f_stat
