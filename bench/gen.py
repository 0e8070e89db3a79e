"""Inputs of every cell, made from seeds: corpora, queries, request sizes and
arrival times.

The corpus generator is a copy of ``manifold_dataset`` from the program's
``data/synthetic.py`` (latent uniform -> two-layer random tanh lift -> small
isotropic noise; its LID is about the latent width). It is kept here so that no
later change to the program moves the yardstick. It is split in two: the *lift*
(the two weight matrices) is fixed by a configuration's corpus seed, and the
*points* draw their latents and noise from any key. Queries are therefore fresh
points on the corpus's own manifold.

Arrivals keep loadgen's exponential inter-arrival law, but every seed draws the
same set of gaps (the exponential's quantiles) in its own order, and every
seed draws the same multiset of request sizes: the seed reorders the work and
never changes how much of it there is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_state(seed: int, *stream: int) -> int:
    """A 32-bit word from a seed of any size and a stream id."""
    return int(np.random.SeedSequence([int(seed), *stream]).generate_state(1)[0])


def jax_key(seed: int, *stream: int) -> jax.Array:
    return jax.random.PRNGKey(seed_state(seed, *stream))


def np_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def lift_keys(corpus_seed: int) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The four keys of ``manifold_dataset(PRNGKey(corpus_seed), ...)``:
    (latents, first layer, second layer, noise)."""
    return tuple(jax.random.split(jax.random.PRNGKey(corpus_seed), 4))


def _points(k_lat, k_w1, k_w2, k_noise, n: int, d: int, latent: int,
            noise: float) -> jax.Array:
    z = jax.random.uniform(k_lat, (n, latent))
    w1 = jax.random.normal(k_w1, (latent, 2 * latent)) / jnp.sqrt(latent)
    w2 = jax.random.normal(k_w2, (2 * latent, d)) / jnp.sqrt(2 * latent)
    x = jnp.tanh(z @ w1) @ w2
    return x + noise * jax.random.normal(k_noise, (n, d))


_points_jit = jax.jit(_points, static_argnums=(4, 5, 6, 7))


def corpus(c: dict) -> jax.Array:
    """The configuration's corpus, on the device in one jitted call:
    ``manifold_dataset(PRNGKey(corpus_seed), n, d, latent, noise)``."""
    k_lat, k_w1, k_w2, k_noise = lift_keys(c["corpus_seed"])
    return _points_jit(k_lat, k_w1, k_w2, k_noise, c["n"], c["d"], c["latent"],
                       c["noise"])


def fresh_points(c: dict, n: int, seed: int, *stream: int) -> jax.Array:
    """``n`` new points on the corpus's manifold: its lift, with latents and
    noise drawn from (seed, stream)."""
    _, k_w1, k_w2, _ = lift_keys(c["corpus_seed"])
    k_lat, k_noise = jax.random.split(jax_key(seed, *stream))
    return _points_jit(k_lat, k_w1, k_w2, k_noise, n, c["d"], c["latent"],
                       c["noise"])


def arrivals(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``round(rate * seconds)``
    requests: exponential gaps at ``rate``, the same set of gap lengths for
    every seed in a seeded order, scaled so the last one falls at
    ``seconds``."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
    gaps = np_rng(seed, 1).permutation(gaps)
    t = np.cumsum(gaps)
    return t * (seconds / t[-1])


def request_sizes(sizes, count: int, seed: int) -> np.ndarray:
    """``count`` request sizes cycling through ``sizes`` in equal shares, in a
    seeded order (loadgen's uniform draw over ``REQUEST_SIZES``, with the
    counts fixed)."""
    sizes = np.asarray(sizes, np.int64)
    return np_rng(seed, 2).permutation(np.resize(sizes, count))
