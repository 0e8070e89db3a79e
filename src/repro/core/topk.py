"""Fixed-shape top-k / sorted-list utilities used across builders and search.

Conventions: candidate lists are kept sorted ascending by distance; the id
``INVALID`` (= -1) marks padding and always sorts last (distance = +inf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INVALID = jnp.int32(-1)
INF = jnp.float32(jnp.inf)


def topk_smallest(dists: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """(.., m) -> (values, indices) of the k smallest, ascending."""
    neg_vals, idx = jax.lax.top_k(-dists, k)
    return -neg_vals, idx


def topk_smallest_with(
    dists: jax.Array, payloads: tuple[jax.Array, ...], k: int
) -> tuple[jax.Array, ...]:
    """(.., m) -> (values, *payloads) of the k smallest, ascending.

    Each payload has the shape of ``dists`` and travels with its distance
    through one stable sort keyed on ``dists`` alone, so ties keep the lowest
    index first, as ``lax.top_k`` does. On the TPU ``top_k`` is itself a
    full-width stable sort with an index payload; carrying the payloads
    through the sort replaces the gathers that would reorder them by index.
    Distances that compare equal tie, -0.0 and +0.0 among them, where
    ``top_k`` puts -0.0 first; of the metrics only ``ip`` yields a -0.0.
    """
    out = jax.lax.sort((dists, *payloads), dimension=dists.ndim - 1,
                       num_keys=1, is_stable=True)
    return tuple(x[..., :k] for x in out)


def sort_by_distance(dists: jax.Array, ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sort (…, m) candidate lists ascending by distance (stable)."""
    order = jnp.argsort(dists, axis=-1, stable=True)
    return (
        jnp.take_along_axis(dists, order, axis=-1),
        jnp.take_along_axis(ids, order, axis=-1),
    )


def dedup_by_id(dists: jax.Array, ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mask duplicate ids (keep smallest distance per id). Fixed shape.

    Works on the last axis (vmap or batch freely). Strategy: one stable
    lexicographic sort by (id, dist), mark entries equal to their
    predecessor id, set their distance to +inf, then sort by distance —
    ties in distance fall back to id order.
    """
    ids_s, dists_s = jax.lax.sort((ids, dists), num_keys=2)
    first = jnp.zeros(ids.shape[:-1] + (1,), bool)
    dup = jnp.concatenate([first, ids_s[..., 1:] == ids_s[..., :-1]], axis=-1)
    dup = dup | (ids_s == INVALID)
    dists_s = jnp.where(dup, INF, dists_s)
    ids_s = jnp.where(dup, INVALID, ids_s)
    return sort_by_distance(dists_s, ids_s)


def merge_candidates(
    dists_a: jax.Array,
    ids_a: jax.Array,
    dists_b: jax.Array,
    ids_b: jax.Array,
    k: int,
    *,
    dedup: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Merge two 1-D candidate lists into the k best (ascending, id-deduped)."""
    dists = jnp.concatenate([dists_a, dists_b])
    ids = jnp.concatenate([ids_a, ids_b])
    if dedup:
        dists, ids = dedup_by_id(dists, ids)
    else:
        dists, ids = sort_by_distance(dists, ids)
    return dists[:k], ids[:k]


def recall_at_k(found_ids: jax.Array, true_ids: jax.Array) -> jax.Array:
    """Mean recall@k: fraction of true_ids (…, k) present in found_ids (…, k')."""
    hits = (found_ids[..., :, None] == true_ids[..., None, :]) & (
        true_ids[..., None, :] != INVALID
    )
    per_query = hits.any(axis=-2).sum(axis=-1) / true_ids.shape[-1]
    return per_query.mean()
