"""Ahead-of-time compiles for a described TPU v5e, without the chip.

The TPU compiler refuses kernels that interpret mode runs happily: blocks not
aligned to the (8, 128) tiling, lowerings Mosaic does not have, scalar
prefetch that overflows SMEM. These tests compile the main path's kernels at
real widths (n = 10^6) for one chip of a described ``v5e:2x2`` topology, so
such a regression fails here instead of on the chip. Nothing runs: a compile
that passes says nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 1_000_000
R = 32


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies

        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_persistent_cache):
    def shape(shp, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)

    def run(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel
        return compiled

    return shape, run


@pytest.mark.parametrize("Q", [64, 256])
@pytest.mark.parametrize("d", [128, 960])
@pytest.mark.parametrize("kernel", ["exact", "sq8", "pq"])
def test_masked_gather_kernels_compile(compile_for, kernel, d, Q):
    from repro.kernels.gather_adc import gather_adc_masked
    from repro.kernels.gather_distance import gather_distance_masked
    from repro.kernels.gather_sq8 import gather_sq8_masked

    S, run = compile_for
    W = (N + 31) // 32
    ids, vis = S((Q, R), jnp.int32), S((Q, W), jnp.uint32)
    if kernel == "exact":
        run(gather_distance_masked, S((Q, d)), ids, S((N, d)), vis)
    elif kernel == "sq8":
        run(gather_sq8_masked, S((Q, d)), ids, S((N, d), jnp.uint8),
            S((d,)), S((d,)), vis)
    else:
        M = 8 if d == 128 else 16       # code bytes per vector
        run(gather_adc_masked, ids, S((N, M), jnp.uint8), S((Q, M, 256)),
            vis)


def test_nndescent_scoring_tile_compiles(compile_for, monkeypatch):
    """NN-Descent scores (chunk=1024, C=144+24+72=240) candidate pools per
    tile through the gather kernel; the whole pool's ids once overflowed
    SMEM when scalar-prefetched in one call."""
    from repro.core import nndescent

    monkeypatch.setenv("REPRO_PALLAS", "native")
    S, run = compile_for
    cfg = nndescent.NNDescentConfig()
    C = (cfg.sample * cfg.sample_nn + cfg.reverse
         + max(2, cfg.reverse // 4) * cfg.sample_nn)
    assert (cfg.chunk, C) == (1024, 240)
    run(lambda base, pool: nndescent._score_chunked(base, pool, "l2",
                                                    cfg.chunk),
        S((N, 128)), S((N, C), jnp.int32))


@pytest.mark.parametrize("scorer", ["exact", "sq8", "pq"])
def test_beam_step_compiles(compile_for, monkeypatch, scorer):
    """One beam-search step at SIFT1M size, steered to the native kernels
    (``jax.default_backend()`` sees the CPU here)."""
    from repro.core import beam_search as bs

    monkeypatch.setenv("REPRO_PALLAS", "native")
    S, run = compile_for
    Q, d, ef = 256, 128, 128
    state_ops = {
        "exact": None,
        "sq8": (S((N, d), jnp.uint8), S((d,)), S((d,))),
        "pq": (S((N, 8), jnp.uint8), S((Q, 8, 256))),
    }[scorer]
    args = (S((Q, d)), S((N, d)), S((N, 20), jnp.int32),
            S((Q, 8), jnp.int32))
    state = jax.eval_shape(
        lambda q, b, nb, e, st: bs._init_state(q, b, nb, e, ef, "l2",
                                               scorer=scorer,
                                               scorer_state=st),
        *args, state_ops)
    state = jax.tree.map(lambda x: S(x.shape, x.dtype), state)
    run(lambda st, q, b, nb, sc: bs._step(st, q, b, nb, "l2", scorer=scorer,
                                          scorer_state=sc, k=10),
        state, *args[:3], state_ops)


@pytest.mark.parametrize("n,d,ef,metric", [
    (1_000_000, 128, 128, "l2"),     # sift1m.batch
    (1_200_000, 100, 512, "cos"),    # glove1m.batch
])
def test_beam_merge_compiles_without_gathers(compile_for, monkeypatch, n, d,
                                             ef, metric):
    """The merge of a batch cell's beam step (Q 256, R 20) carries ids and
    expanded flags through its sort: no gather in the compiled step carries
    the ``beam_merge`` scope."""
    from repro.core import beam_search as bs

    monkeypatch.setenv("REPRO_PALLAS", "native")
    S, run = compile_for
    Q = 256
    args = (S((Q, d)), S((n, d)), S((n, 20), jnp.int32),
            S((Q, 8), jnp.int32))
    state = jax.eval_shape(
        lambda q, b, nb, e: bs._init_state(q, b, nb, e, ef, metric), *args)
    state = jax.tree.map(lambda x: S(x.shape, x.dtype), state)
    hlo = run(lambda st, q, b, nb: bs._step(st, q, b, nb, metric, k=10),
              state, *args[:3]).as_text()
    merge = [line for line in hlo.splitlines() if "/beam_merge/" in line]
    assert any(re.search(r"\ssort\(", line) for line in merge)
    assert not [line for line in merge if re.search(r"\sgather\(", line)]
