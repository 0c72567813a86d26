"""Ring collectives (ICI bandwidth-optimal merges) on the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from flink_tpu.parallel.mesh import build_mesh
from flink_tpu.parallel.ring import ring_all_gather, ring_all_reduce, ring_global_topk


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(8)


def test_ring_all_reduce_matches_psum(mesh):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 40, 3)).astype(np.float32)

    def body(xs):
        local = xs[0]  # [40, 3] per shard
        return ring_all_reduce(local, "shards")[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("shards"), out_specs=P("shards")))
    got = np.asarray(f(x))
    want = x.sum(axis=0)
    for s in range(8):
        np.testing.assert_allclose(got[s], want, rtol=1e-5)


def test_ring_all_reduce_unaligned_rows(mesh):
    x = np.arange(8 * 13, dtype=np.float32).reshape(8, 13)  # 13 % 8 != 0

    def body(xs):
        return ring_all_reduce(xs[0], "shards")[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("shards"), out_specs=P("shards")))
    got = np.asarray(f(x))
    np.testing.assert_allclose(got[0], x.sum(axis=0), rtol=1e-5)


def test_ring_all_reduce_max_combine(mesh):
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 16)).astype(np.float32)

    def body(xs):
        return ring_all_reduce(xs[0], "shards", combine=jnp.maximum)[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("shards"), out_specs=P("shards")))
    got = np.asarray(f(x))
    np.testing.assert_allclose(got[3], x.max(axis=0), rtol=1e-6)


def test_ring_all_gather(mesh):
    x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)

    def body(xs):
        return ring_all_gather(xs[0], "shards")[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("shards"), out_specs=P("shards")))
    got = np.asarray(f(x))
    for s in range(8):
        np.testing.assert_array_equal(got[s], x)


def test_ring_global_topk(mesh):
    rng = np.random.default_rng(2)
    x = rng.permutation(8 * 50).astype(np.float32).reshape(8, 50)

    def body(xs):
        v, s = ring_global_topk(xs[0], 5, "shards")
        return v[None], s[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("shards"),
                          out_specs=(P("shards"), P("shards"))))
    vals, shards = map(np.asarray, f(x))
    want = np.sort(x.ravel())[::-1][:5]
    for s in range(8):
        np.testing.assert_array_equal(np.sort(vals[s])[::-1], want)
        # provenance: the reported shard really holds that value
        for v, src in zip(vals[s], shards[s]):
            assert v in x[src]
