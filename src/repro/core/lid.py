"""Levina–Bickel MLE local intrinsic dimension (paper Tab. I, column 6).

lid_mle(x, k): for each sample, with ascending NN distances T_1..T_k,
  m_hat = [ 1/(k-1) * sum_{j<k} ln(T_k / T_j) ]^{-1}
The dataset LID is the average of per-point estimates over a subsample
(the paper reports a single scalar per dataset).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bruteforce import exact_search


def lid_mle(
    x: jax.Array,
    k: int = 20,
    sample: int = 2000,
    metric: str = "l2",
    key: jax.Array | None = None,
) -> jax.Array:
    if key is None:
        key = jax.random.PRNGKey(0)
    return _lid_mle(x, key, k, sample, metric)


@functools.partial(jax.jit, static_argnames=("k", "sample", "metric"))
def _lid_mle(x, key, k: int, sample: int, metric: str):
    n = x.shape[0]
    idx = jax.random.choice(key, n, shape=(min(sample, n),), replace=False)
    queries = x[idx]
    d, _ = exact_search(queries, x, k + 1, metric=metric)
    # Drop the self column, convert to reporting scale (sqrt for l2).
    d = d[:, 1:]
    if metric == "l2":
        d = jnp.sqrt(jnp.maximum(d, 0.0))
    d = jnp.maximum(d, 1e-12)
    tk = d[:, -1:]
    logs = jnp.log(tk / d[:, :-1])
    m_hat = 1.0 / jnp.maximum(logs.mean(axis=-1), 1e-12)
    return m_hat.mean()
