"""Ring-allgather TPU kernel: schedule oracle + CPU-validatable datapath.

The remote-DMA kernel itself executes only on TPU hardware; on CPU we verify
(1) the forwarding schedule equals the numerically-verified shard_map
implementation, and (2) the local double-buffered chunk datapath in
interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ring_allgather import (local_double_buffer_drain,
                                          ring_allgather_tpu, ring_schedule)


@pytest.mark.parametrize("p", [2, 4, 8, 16])
def test_schedule_delivers_every_shard_once(p):
    deliveries = {}  # (receiver, shard) -> step
    for s, trip in enumerate(ring_schedule(p)):
        assert len(trip) == p  # every link busy every step (bandwidth-optimal)
        for snd, rcv, shard in trip:
            assert rcv == (snd + 1) % p
            key = (rcv, shard)
            assert key not in deliveries, "duplicate delivery"
            deliveries[key] = s
    # after P-1 steps every device has every shard except... exactly the P-1
    # foreign shards were delivered to each device
    for d in range(p):
        got = {sh for (rcv, sh) in deliveries if rcv == d}
        assert got == set(range(p)) - {d}


def test_schedule_matches_shardmap_collective(multidev):
    """The kernel's (sender, shard) schedule is exactly what the verified
    ring_allgather_local executes: shard (d-s)%P leaves device d at step s."""
    multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import collectives as C
from repro.kernels.ring_allgather import ring_schedule
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
full = jnp.arange(8 * 16, dtype=jnp.float32)
sharded = jax.device_put(full, NamedSharding(mesh, P('x')))
out = C.make_allgather(mesh, 'x', 'ring')(sharded)
assert np.allclose(np.asarray(out), np.asarray(full))
sched = ring_schedule(8)
assert sched[0][3] == (3, 4, 3)   # step 0: device d sends its own shard
assert sched[2][0] == (0, 1, 6)   # step 2: device 0 forwards shard (0-2)%8
print('ok')
"""
    )


@pytest.mark.parametrize("shape", [(6, 8, 128), (3, 16, 64)])
def test_local_datapath_interpret(shape):
    rng = np.random.default_rng(0)
    staged = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    out = local_double_buffer_drain(staged)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(staged))


def test_tpu_kernel_traces_and_shapes():
    """The remote-DMA kernel cannot EXECUTE off-TPU, but it must always
    TRACE: abstract evaluation runs the full pallas_call lowering contract
    (BlockSpecs, scratch semaphores, compiler params) without touching
    hardware. Replaces a perpetual TPU-only skip. Mosaic's own checks
    (memory spaces, barriers) run only in the v5e compile of
    tests/test_v5e_compile.py. On a real TPU backend the same function
    additionally executes and must match the identity allgather."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((jax.device_count(),), ("ring",))
    n = jax.device_count()
    x = jnp.arange(n * 8 * 128, dtype=jnp.float32).reshape(n * 8, 128)
    f = jax.shard_map(
        lambda xs: ring_allgather_tpu(xs, n_devices=n),
        mesh=mesh, in_specs=P("ring", None), out_specs=P(None, None),
        check_vma=False,
    )
    out = jax.eval_shape(f, x)
    assert out.shape == x.shape and out.dtype == x.dtype
    if jax.default_backend() == "tpu":   # numerical check where it can run
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x))
