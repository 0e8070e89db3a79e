"""Synthetic datasets.

ANN (paper Tab. I): uniform RAND* sets, plus *manifold* stand-ins for the
real-world corpora — points generated on a low-dimensional latent manifold
and lifted nonlinearly into R^d, matching each corpus's (n, d, LID) profile
(SIFT1M: d=128/LID~16, GIST1M: d=960/LID~38, GloVe1M: d=100/LID~40; the LID
estimator is validated against the synthetic rows where ground truth exists).

Model substrates: learnable token streams for the LM archs, planted-logistic
criteo-like batches for recsys, SBM graphs for the GNN — all deterministic in
(seed, step) so training resumes bit-exactly.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np


# -- ANN datasets ----------------------------------------------------------------


def rand_dataset(key: jax.Array, n: int, d: int) -> jax.Array:
    """Paper's synthetic family: each dim uniform in [0, 1)."""
    return jax.random.uniform(key, (n, d), jnp.float32)


def manifold_dataset(
    key: jax.Array, n: int, d: int, latent_dim: int, noise: float = 0.01
) -> jax.Array:
    """Low-LID data embedded in R^d: latent uniform -> 2-layer random tanh
    lift -> small isotropic noise. LID(result) ~ latent_dim."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    z = jax.random.uniform(k1, (n, latent_dim))
    w1 = jax.random.normal(k2, (latent_dim, 2 * latent_dim)) / jnp.sqrt(latent_dim)
    w2 = jax.random.normal(k3, (2 * latent_dim, d)) / jnp.sqrt(2 * latent_dim)
    x = jnp.tanh(z @ w1) @ w2
    return x + noise * jax.random.normal(k4, (n, d))


PAPER_DATASETS: dict[str, dict] = {
    # name: (n, d, latent/None, metric, paper LID)
    "RAND10M4D": dict(n=10_000_000, d=4, latent=None, metric="l2", paper_lid=3.6),
    "RAND10M8D": dict(n=10_000_000, d=8, latent=None, metric="l2", paper_lid=6.5),
    "RAND10M16D": dict(n=10_000_000, d=16, latent=None, metric="l2", paper_lid=11.6),
    "RAND10M32D": dict(n=10_000_000, d=32, latent=None, metric="l2", paper_lid=19.4),
    "RAND1M": dict(n=1_000_000, d=100, latent=None, metric="l2", paper_lid=48.9),
    "SIFT1M": dict(n=1_000_000, d=128, latent=16, metric="l2", paper_lid=16.3),
    "GIST1M": dict(n=1_000_000, d=960, latent=38, metric="l2", paper_lid=38.1),
    "GLOVE1M": dict(n=1_200_000, d=100, latent=40, metric="cos", paper_lid=39.5),
}


def make_ann_dataset(
    name: str, key: jax.Array | None = None, scale: float = 1.0, n_queries: int = 1000
):
    """Returns (base (n, d), queries (q, d), metric). ``scale`` shrinks n for
    CI (benchmarks use --full for paper sizes). The default key derives from
    the name by CRC-32, so every process draws the same corpus (``hash`` is
    salted per process)."""
    spec = PAPER_DATASETS[name]
    if key is None:
        key = jax.random.PRNGKey(zlib.crc32(name.encode()) & 0x7FFFFFFF)
    n = max(int(spec["n"] * scale), 1000)
    kb, kq = jax.random.split(key)
    if spec["latent"] is None:
        base = rand_dataset(kb, n, spec["d"])
        queries = rand_dataset(kq, n_queries, spec["d"])
    else:
        both = manifold_dataset(kb, n + n_queries, spec["d"], spec["latent"])
        base, queries = both[:n], both[n : n + n_queries]
    return base, queries, spec["metric"]


# -- LM token streams ---------------------------------------------------------------


def lm_batch(key: jax.Array, batch: int, seq: int, vocab: int) -> dict:
    """Learnable stream: affine-recurrent tokens with noise, so a real model
    drives loss well below ln(vocab)."""
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.random.randint(k1, (batch, 1), 1, 17)
    start = jax.random.randint(k2, (batch, 1), 0, vocab)
    t = jnp.arange(seq)[None, :]
    toks = (start + a * t) % vocab
    noise = jax.random.bernoulli(k3, 0.05, (batch, seq))
    rnd = jax.random.randint(k3, (batch, seq), 0, vocab)
    toks = jnp.where(noise, rnd, toks).astype(jnp.int32)
    labels = jnp.concatenate([toks[:, 1:], jnp.full((batch, 1), -100, jnp.int32)], 1)
    return {"tokens": toks, "labels": labels}


def lm_batch_for_step(seed: int, step: int, batch: int, seq: int, vocab: int) -> dict:
    return lm_batch(jax.random.fold_in(jax.random.PRNGKey(seed), step), batch, seq, vocab)


# -- recsys batches -------------------------------------------------------------------


def recsys_batch(
    key: jax.Array, batch: int, vocab_sizes: tuple[int, ...], n_dense: int = 0
) -> dict:
    """Criteo-like batch with a planted logistic teacher so training is
    meaningful: y ~ Bernoulli(sigmoid(sum of per-field hash weights))."""
    ks, kd, kl = jax.random.split(key, 3)
    F = len(vocab_sizes)
    maxv = max(vocab_sizes)
    raw = jax.random.randint(ks, (batch, F), 0, 1 << 30)
    sparse = raw % jnp.array(vocab_sizes)[None, :]
    # planted teacher: weight of id v in field f = sin(v * phi_f), cheap + fixed
    phi = jnp.linspace(0.1, 1.7, F)[None, :]
    teacher = jnp.sin(sparse.astype(jnp.float32) * phi).sum(axis=1) / jnp.sqrt(F)
    out = {"sparse": sparse.astype(jnp.int32)}
    if n_dense:
        dense = jax.random.normal(kd, (batch, n_dense))
        teacher = teacher + dense.sum(axis=1) / jnp.sqrt(n_dense)
        out["dense"] = dense
    out["label"] = jax.random.bernoulli(kl, jax.nn.sigmoid(teacher)).astype(jnp.float32)
    return out


def bert4rec_batch(key: jax.Array, batch: int, seq: int, n_items: int,
                   mask_token: int, mask_prob: float = 0.15) -> dict:
    """Markov item sequences + cloze masking."""
    k1, k2, k3 = jax.random.split(key, 3)
    step_sz = jax.random.randint(k1, (batch, 1), 1, 7)
    start = jax.random.randint(k2, (batch, 1), 0, n_items)
    seqs = (start + step_sz * jnp.arange(seq)[None, :]) % n_items
    m = jax.random.bernoulli(k3, mask_prob, (batch, seq))
    inputs = jnp.where(m, mask_token, seqs).astype(jnp.int32)
    labels = jnp.where(m, seqs, -100).astype(jnp.int32)
    return {"items": inputs, "labels": labels}


# -- GNN graphs ------------------------------------------------------------------------


def sbm_graph(
    key: jax.Array, n: int, n_classes: int, d_feat: int,
    p_in: float = 0.05, p_out: float = 0.005, avg_deg: int = 10,
) -> dict:
    """Stochastic block model with class-correlated features; edges sampled
    with fixed count E ~ n * avg_deg (fixed-shape friendly)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    labels = jax.random.randint(k1, (n,), 0, n_classes)
    E = n * avg_deg
    src = jax.random.randint(k2, (E,), 0, n)
    # biased destination: with prob p_in/(p_in+p_out) pick same-class node
    dst_rand = jax.random.randint(k3, (E,), 0, n)
    same = labels[src] == labels[dst_rand]
    accept = jax.random.uniform(k4, (E,)) < jnp.where(same, 1.0, p_out / p_in)
    dst = jnp.where(accept, dst_rand, src)  # rejected -> self loop
    edges = jnp.stack([src, dst], axis=1).astype(jnp.int32)
    centers = jax.random.normal(jax.random.fold_in(k1, 1), (n_classes, d_feat))
    feats = centers[labels] + 0.5 * jax.random.normal(
        jax.random.fold_in(k1, 2), (n, d_feat)
    )
    return {"feats": feats, "edges": edges, "labels": labels.astype(jnp.int32)}


def edges_to_csr(edges: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side CSR build for the neighbor sampler."""
    edges = np.asarray(edges)
    order = np.argsort(edges[:, 0], kind="stable")
    src, dst = edges[order, 0], edges[order, 1]
    counts = np.bincount(src, minlength=n)
    indptr = np.zeros(n + 1, np.int32)
    np.cumsum(counts, out=indptr[1:])
    return indptr, dst.astype(np.int32)
