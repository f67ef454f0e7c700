"""Collective facade tests (analogue of reference tests/unit/comm/test_dist.py).

In-jit collectives run inside shard_map against the global mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu.parallel import groups


@pytest.fixture
def mesh():
    dist.init_distributed()
    return groups.initialize_mesh({"data_parallel_size": 8})


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


def test_all_reduce(mesh):
    x = jnp.arange(8.0)

    def f(x):
        return dist.all_reduce(x, group=("data",))

    out = _shard_map(f, mesh, P(("data",)), P(("data",)))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_all_reduce_max(mesh):
    x = jnp.arange(8.0)

    def f(x):
        return dist.all_reduce(x, group=("data",), op=dist.ReduceOp.MAX)

    out = _shard_map(f, mesh, P(("data",)), P(("data",)))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 7.0))


def test_all_gather_into_tensor(mesh):
    x = jnp.arange(8.0)

    def f(x):
        return dist.all_gather_into_tensor(x, group=("data",))

    out = _shard_map(f, mesh, P(("data",)), P())(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_reduce_scatter_tensor(mesh):
    x = jnp.ones((8, 4))

    def f(x):
        # each shard holds [1, 4]; gather to [8,4] then reduce-scatter back
        full = dist.all_gather_into_tensor(x, group=("data",))
        return dist.reduce_scatter_tensor(full, group=("data",))

    out = _shard_map(f, mesh, P(("data",)), P(("data",)))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 4), 8.0))


def test_all_to_all_single(mesh):
    # rank r holds values [8r, 8r+8); after all-to-all rank r holds value
    # 8p + r from every peer p — i.e. the block transpose.
    x = jnp.arange(64.0)

    def f(x):
        return dist.all_to_all_single(x, group=("data",))

    out = _shard_map(f, mesh, P(("data",)), P(("data",)))(x)
    expected = np.arange(64.0).reshape(8, 8).T.reshape(-1)
    np.testing.assert_allclose(np.asarray(out), expected, rtol=0, atol=0)


def test_broadcast(mesh):
    x = jnp.arange(8.0)

    def f(x):
        return dist.broadcast(x, src=3, group="data")

    out = _shard_map(f, mesh, P(("data",)), P(("data",)))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))


def test_host_collectives():
    dist.init_distributed()
    arr = np.array([1.0, 2.0])
    out = dist.host_all_reduce(arr)
    np.testing.assert_allclose(out, arr)  # single process
    g = dist.host_all_gather(arr)
    assert g.shape == (1, 2)
    b = dist.host_broadcast(arr, src=0)
    np.testing.assert_allclose(b, arr)


def test_world_size_and_rank():
    dist.init_distributed()
    assert dist.get_world_size() == 8  # 8 virtual devices
    assert dist.get_rank() == 0


def test_comms_logger(mesh):
    dist.configure(enabled=True, prof_all=True)
    x = jnp.arange(8.0)

    def f(x):
        return dist.all_reduce(x, group=("data",))

    _shard_map(f, mesh, P(("data",)), P(("data",)))(x)
    summary = dist.log_summary()
    assert "all_reduce" in summary
    dist.configure(enabled=False)
