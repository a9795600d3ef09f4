"""Blockwise online-softmax attention and the fused kernel vs a naive oracle
(+ the dispatch between them, and decode paths)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as A


def naive_attention(q, k, v, causal=True, window=None, softcap=None):
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    qq = q.reshape(b, sq, kvh, h // kvh, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qq, k.astype(jnp.float32)) * hd**-0.5
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)


CASES = [
    # (S, H, KV, hd, causal, window, qb, kb)
    (64, 4, 4, 16, True, None, 16, 16),
    (96, 4, 2, 16, True, None, 32, 16),   # GQA, ragged blocks
    (64, 4, 1, 16, True, None, 16, 32),   # MQA
    (100, 2, 2, 8, True, None, 32, 32),   # non-divisible padding
    (64, 4, 4, 16, False, None, 16, 16),  # non-causal (encoder/cross)
    (128, 4, 2, 16, True, 32, 32, 32),    # windowed (RG local attention)
]


@pytest.mark.parametrize("case", CASES)
def test_blockwise_matches_naive(case):
    s, h, kv, hd, causal, window, qb, kb = case
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, kv, hd)), jnp.float32)
    out = A.blockwise_attention(
        q, k, v, causal=causal, window=window, q_block=qb, kv_block=kb
    )
    ref = naive_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_softcap():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.float32)
    out = A.blockwise_attention(q, k, v, causal=True, q_block=8, kv_block=8,
                                softcap=5.0)
    ref = naive_attention(q, k, v, causal=True, softcap=5.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_plain_decode_matches_naive_last_row():
    rng = np.random.default_rng(2)
    b, s, h, kv, hd = 2, 40, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    full = naive_attention(q, k, v, causal=True)
    # decode the last position against the cache
    kc = k.transpose(0, 2, 1, 3)
    vc = v.transpose(0, 2, 1, 3)
    pos = jnp.full((b,), s - 1, jnp.int32)
    out = A.plain_decode_attention(q[:, -1], kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, -1]), atol=2e-5)


def test_ring_decode_matches_window():
    rng = np.random.default_rng(3)
    b, h, kv, hd, w = 2, 4, 1, 16, 16
    s = 40  # decode at position 39 with a 16-deep ring
    q_all = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k_all = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((b, s, kv, hd)), jnp.float32)
    full = naive_attention(q_all, k_all, v_all, causal=True, window=w)
    # build the ring cache for the last w positions
    kc = jnp.zeros((b, kv, w, hd))
    vc = jnp.zeros((b, kv, w, hd))
    for p in range(s):
        kc = kc.at[:, :, p % w].set(k_all[:, p])
        vc = vc.at[:, :, p % w].set(v_all[:, p])
    pos = jnp.full((b,), s - 1, jnp.int32)
    idx = jnp.arange(w)
    abs_pos = pos[:, None] - ((pos[:, None] - idx[None, :]) % w)
    out = A.ring_decode_attention(q_all[:, -1], kc, vc, abs_pos, pos, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, -1]), atol=2e-5)


def test_cache_scatter_update():
    b, kv, s, hd = 3, 2, 16, 8
    cache = jnp.zeros((b, kv, s, hd))
    new = jnp.ones((b, kv, hd))
    pos = jnp.array([0, 5, 15], jnp.int32)
    out = A.cache_scatter_update(cache, new, pos)
    for i, p in enumerate([0, 5, 15]):
        assert float(out[i, :, p].sum()) == kv * hd
    assert float(out.sum()) == b * kv * hd


# ------------------------------------------------ fused kernel (interpret mode)


def _qkv(b, s, h, kv, hd, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(shape), dtype)
                 for shape in ((b, s, h, hd), (b, s, kv, hd), (b, s, kv, hd)))


def _grads(f, q, k, v):
    # a fixed cotangent that differs across the head dim
    w = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))
    return jax.grad(lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w),
                    argnums=(0, 1, 2))(q, k, v)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("h,kv", [(9, 3), (4, 4)], ids=["gqa9-3", "mha4"])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("s", [256, 512])
def test_fused_matches_blockwise_and_naive(h, kv, hd, s):
    """The Splash kernel (Pallas interpret mode) against the scans and a
    float32 oracle, bf16 inputs: the output and the q, k and v gradients."""
    q, k, v = _qkv(1, s, h, kv, hd)
    fused = jax.jit(lambda q, k, v: A.fused_causal_attention(
        q, k, v, q_block=128, kv_block=256, interpret=True))
    blockwise = jax.jit(lambda q, k, v: A.blockwise_attention(
        q, k, v, causal=True, q_block=128, kv_block=256))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    naive = naive_attention(*f32)

    out = fused(q, k, v)
    assert out.dtype == q.dtype and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(naive), atol=1.6e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(blockwise(q, k, v), np.float32), atol=1.6e-2)

    g_fused = _grads(fused, q, k, v)
    g_block = _grads(blockwise, q, k, v)
    g_naive = _grads(naive_attention, *f32)
    for name, gf, gb, gn in zip("qkv", g_fused, g_block, g_naive):
        assert gf.dtype == jnp.bfloat16, name
        # bf16 gradients: the kernel's gap to the oracle is the scans' own
        assert _rel(gf, gn) < 1e-2, (name, _rel(gf, gn), _rel(gb, gn))
        assert _rel(gf, gb) < 1e-2, (name, _rel(gf, gb))


CELL = dict(q_shape=(16, 2048, 9, 64), k_shape=(16, 2048, 3, 64), window=None,
            softcap=None, q_block=512, kv_block=1024, batch_shards=1, head_shards=1)


@pytest.mark.parametrize("change,blocks", [
    ({}, (512, 1024)),                                    # the benchmark cell's shapes
    ({"window": 1024}, None),
    ({"softcap": 50.0}, None),
    ({"window": 1024, "softcap": 50.0}, None),
    ({"q_shape": (16, 2000, 9, 64), "k_shape": (16, 2000, 3, 64)}, None),  # S % blocks
    ({"q_shape": (16, 1536, 9, 64), "k_shape": (16, 1536, 3, 64)}, None),  # S % kv_block
    ({"q_shape": (16, 512, 9, 64), "k_shape": (16, 512, 3, 64)}, (512, 512)),  # clipped
    ({"q_shape": (2, 64, 9, 64), "k_shape": (2, 64, 3, 64)}, None),  # tiles under 128
    ({"q_block": 32, "kv_block": 32}, None),              # the CPU-sized configs' blocks
    ({"q_shape": (16, 2048, 9, 96), "k_shape": (16, 2048, 3, 96)}, (512, 1024)),
    ({"q_shape": (16, 2048, 9, 192), "k_shape": (16, 2048, 3, 192)}, None),  # hd
    ({"q_shape": (16, 2048, 8, 256), "k_shape": (16, 2048, 8, 256)}, (512, 1024)),
    # yi-9b's heads under FSDP over data=4
    ({"q_shape": (8, 2048, 32, 128), "k_shape": (8, 2048, 4, 128), "batch_shards": 4},
     (512, 1024)),
    ({"batch_shards": 4}, (512, 1024)),
    ({"batch_shards": 3}, None),
    ({"head_shards": 3}, (512, 1024)),                    # 9 and 3 heads over tp=3
    ({"head_shards": 2}, None),                           # 9 heads over tp=2
    ({"q_shape": (16, 2048, 8, 64), "k_shape": (16, 2048, 1, 64), "head_shards": 2}, None),
])
def test_fused_attention_dispatch_rule(change, blocks):
    args = {**CELL, **change}
    q_shape, k_shape = args.pop("q_shape"), args.pop("k_shape")
    assert A.fused_attention_blocks(q_shape, k_shape, **args) == blocks


def test_causal_attention_takes_the_scans_when_lowered_for_the_cpu():
    """Shapes the kernel takes on a TPU: lowered for the CPU, the dispatcher
    holds no kernel and its output and gradients are the scans' exactly."""
    q, k, v = _qkv(2, 256, 9, 3, 64)
    f = functools.partial(A.causal_attention, q_block=128, kv_block=128)
    assert A.fused_attention_blocks(q.shape, k.shape, window=None, softcap=None,
                                    q_block=128, kv_block=128, batch_shards=1, head_shards=1)
    text = jax.jit(f).lower(q, k, v).as_text()
    assert "tpu_custom_call" not in text and "splash" not in text
    ref = functools.partial(A.blockwise_attention, causal=True, q_block=128, kv_block=128)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(q, k, v), np.float32),
                                  np.asarray(ref(q, k, v), np.float32))
    for a, b in zip(_grads(f, q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_fused_under_a_mesh_equals_no_mesh(multidev):
    """Four CPU devices: the kernel inside ``shard_map`` over data=4, and
    over data=2 x tp=2 with the heads split, gives the unsharded result."""
    out = multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models import attention as A
from repro.sharding import ShardCtx, use_ctx

rng = np.random.default_rng(0)
q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
           for s in ((4, 256, 4, 64), (4, 256, 2, 64), (4, 256, 2, 64)))

def run(ctx):
    with use_ctx(ctx):
        f = lambda q, k, v: A.fused_causal_attention(q, k, v, q_block=128, kv_block=128,
                                                     interpret=True)
        loss = lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(lambda q, k, v: (f(q, k, v), jax.grad(loss, (0, 1, 2))(q, k, v)))(q, k, v)

base = run(ShardCtx(mesh=None))
for shape in ((4, 1), (2, 2)):
    mesh = make_mesh(shape, ("data", "model"))
    got = run(ShardCtx(mesh=mesh, dp_axes=("data",), tp_axis="model"))
    same = all(np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(base)))
    print("mesh", shape, "equal" if same else "differ")
""", n_devices=4)
    assert "mesh (4, 1) equal" in out and "mesh (2, 2) equal" in out, out


def test_fused_in_a_dp_manual_region_equals_no_mesh(multidev):
    """Four CPU devices: the kernel called from inside a region already
    manual over data, as the FSDP layer runs it (``fsdp.py:
    ParamGather.dp_manual``), on data=4 and on data=2 x tp=2 with the heads
    split, gives the unsharded result and gradients. Its own ``shard_map``
    must then be manual over tp alone: were it manual over data again, its
    transpose would sum each device's q, k and v gradients over data."""
    out = multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.models import attention as A
from repro.sharding import ShardCtx, use_ctx

rng = np.random.default_rng(0)
q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
           for s in ((4, 256, 4, 64), (4, 256, 2, 64), (4, 256, 2, 64)))
f = lambda q, k, v: A.fused_causal_attention(q, k, v, q_block=128, kv_block=128,
                                             interpret=True)

def run(f):
    loss = lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)
    return jax.jit(lambda q, k, v: (f(q, k, v), jax.grad(loss, (0, 1, 2))(q, k, v)))(q, k, v)

base = run(f)
for shape in ((4, 1), (2, 2)):
    mesh = make_mesh(shape, ("data", "model"))

    def local(q, k, v):
        ctx = ShardCtx(mesh=jax.sharding.get_abstract_mesh(), dp_axes=(), tp_axis="model",
                       shard_batch=False)
        with use_ctx(ctx):
            return f(q, k, v)

    region = jax.shard_map(local, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                           axis_names={"data"}, check_vma=False)
    got = run(region)
    same = all(np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(base)))
    print("mesh", shape, "equal" if same else "differ")
""", n_devices=4)
    assert "mesh (4, 1) equal" in out and "mesh (2, 2) equal" in out, out
