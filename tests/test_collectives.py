"""The paper's shard_map collectives on 8 fake devices (subprocess)."""
import pytest

# jax model/integration tier: excluded from the fast CI
# lane (scripts/check.sh), run by the `slow` CI job
pytestmark = pytest.mark.slow



def test_allgather_modes(multidev):
    multidev(
        """
import pytest
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import collectives as C
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
n = 64
full = jnp.arange(8 * n, dtype=jnp.float32)
sharded = jax.device_put(full, NamedSharding(mesh, P('x')))
for mode in ['ring', 'bidi']:
    out = C.make_allgather(mesh, 'x', mode)(sharded)
    assert np.allclose(np.asarray(out), np.asarray(full)), mode
for m in [1, 2, 4, 8]:
    out = C.make_allgather(mesh, 'x', 'bcast', n_chains=m)(sharded)
    assert np.allclose(np.asarray(out), np.asarray(full)), m
# 2-D shards gather along their leading dim (the FSDP weight layout)
full2 = full.reshape(8 * 4, n // 4)
sharded2 = jax.device_put(full2, NamedSharding(mesh, P('x', None)))
for mode in ['ring', 'bidi', 'bcast']:
    out = C.make_allgather(mesh, 'x', mode)(sharded2)
    assert out.shape == full2.shape, (mode, out.shape)
    assert np.array_equal(np.asarray(out), np.asarray(full2)), mode
print('ok')
"""
    )


def test_reduce_scatter_and_concurrent(multidev):
    multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import collectives as C
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
n = 64
full = jnp.arange(8 * n, dtype=jnp.float32)
per_dev = jnp.stack([full * (i + 1) for i in range(8)])
for mode, local in [('ring', C.ring_reduce_scatter_local),
                    ('bidi', C.bidi_ring_reduce_scatter_local)]:
    sm = jax.shard_map(lambda x: local(x[0], 'x'), mesh=mesh,
                    in_specs=P('x'), out_specs=P('x'), check_vma=False)
    out = sm(per_dev)
    expect = np.asarray(full).reshape(8, n) * 36
    assert np.allclose(np.asarray(out), expect.reshape(-1)), mode
# concurrent AG+RS (direction split)
sharded = jax.device_put(full, NamedSharding(mesh, P('x')))
agf, rss = jax.jit(lambda a, r: jax.shard_map(
    lambda aa, rr: C.concurrent_ag_rs_local(aa, rr[0], 'x'),
    mesh=mesh, in_specs=(P('x'), P('x')), out_specs=(P(), P('x')),
    check_vma=False)(a, r))(sharded, per_dev.reshape(8, 8 * n))
assert np.allclose(np.asarray(agf), np.asarray(full))
assert np.allclose(np.asarray(rss), (np.asarray(full).reshape(8, n) * 36).reshape(-1))
print('ok')
"""
    )


def test_pipelined_broadcast_roots_and_chunks(multidev):
    multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import collectives as C
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
n = 64
full = jnp.arange(8 * n, dtype=jnp.float32)
sharded = jax.device_put(full, NamedSharding(mesh, P('x')))
for root in [0, 3, 7]:
    for nc in [1, 4, 8, 16]:
        out = C.make_broadcast(mesh, 'x', root=root, n_chunks=nc)(sharded)
        assert np.allclose(np.asarray(out), np.asarray(full[root*n:(root+1)*n])), (root, nc)
print('ok')
"""
    )


def test_collectives_gradients(multidev):
    """AD through the ppermute collectives: grad of sum(allgather(x)) == ones
    broadcast back (the transpose is the matching reduce-scatter)."""
    multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import collectives as C

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
n = 32
full = jnp.arange(8 * n, dtype=jnp.float32)
sharded = jax.device_put(full, NamedSharding(mesh, P('x')))
for mode in ['ring', 'bidi']:
    ag = C.make_allgather(mesh, 'x', mode)
    g = jax.grad(lambda x: jnp.sum(ag(x) * 2.0))(sharded)
    assert np.allclose(np.asarray(g), 2.0), mode
print('ok')
"""
    )
