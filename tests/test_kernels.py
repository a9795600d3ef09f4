"""Pallas kernel sweeps vs pure-jnp oracles (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 384, 128),
                                 (512, 256, 256), (128, 512, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel(mkn, dtype):
    m, k, n = mkn
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((k, n)), dtype)
    y = ops.matmul(x, w)
    yr = ref.matmul_ref(x, w)
    tol = 0.5 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=tol
    )


@pytest.mark.parametrize("tiles", [(128, 128, 128), (64, 128, 128), (128, 64, 64)])
def test_matmul_tile_sweep(tiles):
    bm, bk, bn = tiles
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    y = ops.matmul(x, w, bm=bm, bk=bk, bn=bn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.matmul_ref(x, w)),
                               atol=2e-3)


@pytest.mark.parametrize("cfg", [
    # (n_chunks, chunk, n_staged, n_valid, dtype)
    (32, 256, 20, 15, jnp.float32),
    (64, 128, 64, 64, jnp.float32),
    (16, 512, 10, 0, jnp.float32),     # nothing valid
    (32, 256, 20, 20, jnp.bfloat16),
    (8, 1024, 8, 5, jnp.int32),
])
def test_chunk_reassembly(cfg):
    n_chunks, chunk, n_staged, n_valid, dtype = cfg
    rng = np.random.default_rng(n_chunks + n_staged)
    if dtype == jnp.int32:
        staging = jnp.asarray(rng.integers(0, 1000, (n_staged, chunk)), dtype)
        user = jnp.zeros((n_chunks, chunk), dtype) - 1
    else:
        staging = jnp.asarray(rng.standard_normal((n_staged, chunk)), dtype)
        user = jnp.zeros((n_chunks, chunk), dtype) - 1.0
    psn = jnp.asarray(rng.permutation(n_chunks)[:n_staged], jnp.int32)
    u1, b1 = ops.reassemble(staging, psn, user, n_valid)
    u2, b2 = ref.chunk_reassembly_ref(staging, psn, user, n_valid)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))


def test_chunk_reassembly_out_of_order_with_duplicates():
    """Adaptive-routing OOO + retransmitted duplicates: last write wins and
    the untouched chunks keep previous content (input/output aliasing)."""
    n_chunks, chunk = 16, 128
    rng = np.random.default_rng(5)
    user = jnp.asarray(rng.standard_normal((n_chunks, chunk)), jnp.float32)
    staging = jnp.asarray(rng.standard_normal((6, chunk)), jnp.float32)
    psn = jnp.asarray([3, 9, 3, 0, 9, 12], jnp.int32)  # dups of 3 and 9
    u1, b1 = ops.reassemble(staging, psn, user)
    u2, b2 = ref.chunk_reassembly_ref(staging, psn, user)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u2))
    # untouched chunk preserved
    np.testing.assert_array_equal(np.asarray(u1[1]), np.asarray(user[1]))
    assert int(b1.sum()) == 4  # chunks {0,3,9,12}


@pytest.mark.parametrize("n", [32 * 8, 32 * 256, 32 * 1024])
def test_bitmap_roundtrip(n):
    rng = np.random.default_rng(n)
    flags = jnp.asarray(rng.integers(0, 2, n), jnp.uint32)
    words = ops.pack_bitmap(flags)
    np.testing.assert_array_equal(
        np.asarray(words), np.asarray(ref.bitmap_pack_ref(flags))
    )
    blk = min(1024, n // 32)
    assert int(ops.popcount(words, block=blk)) == int(flags.sum())


def test_collective_matmul_multidev(multidev):
    multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))
rng = np.random.default_rng(1)
x = jnp.asarray(rng.standard_normal((8*128, 256)), jnp.float32)
w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
xs = jax.device_put(x, NamedSharding(mesh, P('x', None)))
y = ops.make_allgather_matmul(mesh, 'x')(xs, w)
yr = ref.allgather_matmul_ref(x, w)
assert float(jnp.max(jnp.abs(y - yr))) < 1e-3
print('ok')
"""
    )


# ------------------------------------------------- pool-completion scan


@pytest.mark.parametrize("cfg", [
    # (rows, n, n_workers, service, staging) — ragged (w does not divide n),
    # aligned, w > n, single element, and a staging window wider than W
    (5, 17, 4, 0.3, 3),
    (8, 32, 8, 1.5, 2),
    (3, 7, 16, 0.01, 1),
    (1, 1, 2, 1.0, 4),
    (13, 40, 5, 0.7, 6),
])
def test_pool_scan_kernel_bit_exact_vs_numpy_twin(cfg):
    """The Pallas residue-class-parallel scan must be BIT-exact with its
    jax-free numpy twin (the engine's production inner path) in f64 — both
    run the identical per-lane op sequence, so equality is exact, not
    approximate."""
    from repro.kernels import pool
    from repro.kernels.pool_np import pool_completion_rows_np

    rows, n, w, s, staging = cfg
    rng = np.random.default_rng(rows * 1000 + n)
    a = np.sort(rng.uniform(0.0, 10.0, (rows, n)), axis=1)
    d_np, m_np = pool_completion_rows_np(a, w, s, staging)
    with jax.enable_x64(True):
        d_j, m_j = pool.pool_completion_rows(jnp.asarray(a), w, s, staging)
        assert np.asarray(d_j).dtype == np.float64
        np.testing.assert_array_equal(np.asarray(d_j), d_np)
        np.testing.assert_array_equal(np.asarray(m_j), m_np)


def test_pool_scan_kernel_f32_lane_semantics():
    """In f32 (jax default) the kernel replays the same lane ops at f32
    precision — pin it bitwise against the scan replayed in f32 numpy."""
    from repro.kernels import pool

    rows, n, w, s = 6, 23, 4, 0.3
    rng = np.random.default_rng(7)
    a32 = np.sort(rng.uniform(0.0, 10.0, (rows, n)), axis=1) \
        .astype(np.float32)
    d_j = np.asarray(pool.pool_scan_rows(jnp.asarray(a32), w, s))
    assert d_j.dtype == np.float32
    s32 = np.float32(s)
    pad = (-n) % w
    n_per = (n + pad) // w
    buf = np.full((rows, n_per * w), np.inf, np.float32)
    buf[:, :n] = a32
    b3 = buf.reshape(rows, n_per, w)
    i3 = np.arange(n_per, dtype=np.float32)[None, :, None]
    b3 = np.maximum.accumulate(b3 - i3 * s32, axis=1) \
        + (i3 + np.float32(1.0)) * s32
    np.testing.assert_array_equal(d_j, b3.reshape(rows, -1)[:, :n])
