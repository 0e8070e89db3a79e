"""Core ANN library: distances, topk invariants, brute force, LID."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bruteforce, distances, lid, topk


def test_pairwise_l2_matches_numpy():
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (13, 7))
    y = jax.random.normal(jax.random.fold_in(k, 1), (11, 7))
    want = ((np.asarray(x)[:, None] - np.asarray(y)[None]) ** 2).sum(-1)
    np.testing.assert_allclose(distances.pairwise(x, y, "l2"), want, rtol=1e-5,
                               atol=1e-5)


def test_cos_range():
    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (20, 5))
    d = distances.pairwise(x, x, "cos")
    assert float(d.min()) > -1e-5 and float(d.max()) < 2 + 1e-5
    np.testing.assert_allclose(np.diag(np.asarray(d)), 0.0, atol=1e-5)


def test_topk_merge_dedup():
    da = jnp.array([0.1, 0.5, jnp.inf])
    ia = jnp.array([3, 7, -1], jnp.int32)
    db = jnp.array([0.2, 0.5, 0.05])
    ib = jnp.array([4, 7, 9], jnp.int32)
    d, i = topk.merge_candidates(da, ia, db, ib, 4)
    assert list(np.asarray(i)) == [9, 3, 4, 7]
    assert float(d[0]) == pytest.approx(0.05)


@pytest.mark.parametrize("m,k", [(148, 128), (532, 512), (128 + 8, 128)])
def test_topk_smallest_with_matches_topk_and_gathers(m, k):
    """The beam merge's carried sort against the formulation it replaced
    (``topk_smallest``, then one ``take_along_axis`` per payload), at the
    merge widths of ef 128 and 512 with R = 20 and of a restart (ef + 8):
    rows of tied distances, +inf padding and INVALID ids."""
    rng = np.random.default_rng(m)
    Q = 16
    d = (rng.integers(0, 8, (Q, m)) / 4).astype(np.float32)  # many ties
    ids = rng.integers(0, 1_000_000, (Q, m)).astype(np.int32)
    pad = rng.random((Q, m)) < 0.3
    pad[0] = True                                           # an empty row
    d[pad] = np.inf
    ids[pad] = -1
    ids[1, ::3] = -1                     # INVALID beside finite distances
    flags = rng.random((Q, m)) < 0.5

    want_d, order = topk.topk_smallest(jnp.asarray(d), k)
    want_i = jnp.take_along_axis(jnp.asarray(ids), order, axis=1)
    want_e = jnp.take_along_axis(jnp.asarray(flags), order, axis=1)
    got_d, got_i, got_e = jax.jit(topk.topk_smallest_with, static_argnums=2)(
        jnp.asarray(d), (jnp.asarray(ids), jnp.asarray(flags)), k)
    assert got_d.shape == got_i.shape == got_e.shape == (Q, k)
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))


def test_topk_smallest_with_signed_zeros_tie_by_index():
    """-0.0 and +0.0 compare equal, so they keep their order like any tie."""
    d = jnp.array([[0.0, -0.0, 1.0, -0.0, -1e-7]], jnp.float32)
    ids = jnp.arange(5, dtype=jnp.int32)[None]
    got_d, got_i = topk.topk_smallest_with(d, (ids,), 4)
    assert np.asarray(got_i).tolist() == [[4, 0, 1, 3]]
    assert np.signbit(np.asarray(got_d)).tolist() == [[True, False, True, True]]


def test_exact_search_vs_numpy():
    k = jax.random.PRNGKey(2)
    base = jax.random.normal(k, (500, 12))
    q = jax.random.normal(jax.random.fold_in(k, 3), (9, 12))
    d, i = bruteforce.exact_search(q, base, 5, chunk=64)
    full = ((np.asarray(q)[:, None] - np.asarray(base)[None]) ** 2).sum(-1)
    want = np.argsort(full, axis=1)[:, :5]
    np.testing.assert_array_equal(np.asarray(i), want)
    assert bool(jnp.all(d[:, :-1] <= d[:, 1:]))  # ascending


def test_exact_knn_graph_no_self():
    base = jax.random.normal(jax.random.PRNGKey(4), (100, 8))
    g = bruteforce.exact_knn_graph(base, 6)
    assert g.neighbors.shape == (100, 6)
    assert bool((g.neighbors != jnp.arange(100)[:, None]).all())


@pytest.mark.parametrize("d_true", [4, 8])
def test_lid_recovers_gaussian_dim(d_true):
    x = jax.random.normal(jax.random.PRNGKey(5), (3000, d_true))
    est = float(lid.lid_mle(x, k=20, sample=1000))
    assert abs(est - d_true) / d_true < 0.35, est


def test_lid_manifold_lower_than_ambient():
    from repro.data.synthetic import manifold_dataset

    x = manifold_dataset(jax.random.PRNGKey(6), 4000, d=64, latent_dim=6)
    est = float(lid.lid_mle(x, k=20, sample=1000))
    assert est < 16, est  # ambient 64, latent 6
