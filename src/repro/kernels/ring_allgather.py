"""Ring allgather as a Pallas TPU kernel with inter-chip RDMA.

This is the paper's collective engine brought all the way down to the kernel
level on TPU: instead of a SmartNIC progress engine posting RDMA multicast
sends and polling CQEs, the TPU kernel posts **async remote DMAs**
(`pltpu.make_async_remote_copy`) to its ring neighbor and waits on DMA
semaphores — the same post/poll datapath structure as the DPA receive worker
(Appendix C), with the DMA engines playing the NIC RDMA engine and the
semaphores playing completion queues. Chunked double-buffering hides transfer
latency behind the copy of the previous chunk (the "hide the cost of data
movement" thesis).

Layout per step s (of P-1): device d forwards the shard it received at step
s-1 to (d+1)%P while the incoming shard lands in the alternate slot —
per-link bytes = N*(P-1)/P per direction, the torus bandwidth-optimality
criterion of DESIGN.md §2.

This kernel TARGETS TPU: remote DMA is not executable in CPU interpret mode,
so correctness on CPU is validated two ways (tests/test_ring_ag_kernel.py):
  1. the *local* datapath (double-buffered chunk pipeline, slot scheduling)
     runs in interpret mode against the jnp oracle;
  2. the *schedule* (who sends which shard when) is identical to
     core.collectives.ring_allgather_local, which is verified numerically on
     multi-device meshes, including gradients.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ring_allgather_tpu(x_shard: jax.Array, *, axis_name: str = "ring",
                       n_devices: int) -> jax.Array:
    """TPU-only: run inside shard_map over ``axis_name``. x_shard (rows, cols)
    -> (P*rows, cols). See module docstring for CPU validation strategy."""
    rows, cols = x_shard.shape
    out_shape = jax.ShapeDtypeStruct((n_devices, rows, cols), x_shard.dtype)

    def kernel(x_ref, out_ref, copy_sem, send_sem, recv_sem):
        my_id = jax.lax.axis_index(axis_name)
        step = pl.program_id(0)

        right = jax.lax.rem(my_id + 1, n_devices)
        left = jax.lax.rem(my_id - 1 + n_devices, n_devices)

        @pl.when(step == 0)
        def _():
            # install own shard: both refs live in HBM (ANY), which only a
            # DMA may touch
            pltpu.async_copy(x_ref, out_ref.at[my_id], copy_sem).wait()
            # no neighbour may write into out_ref before its owner is in
            # the kernel
            barrier = pltpu.get_barrier_semaphore()
            for nbr in (left, right):
                pltpu.semaphore_signal(barrier, 1, device_id=nbr,
                                       device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(barrier, 2)

        src = jax.lax.rem(my_id - step + n_devices, n_devices)
        rdma = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[src],
            dst_ref=out_ref.at[src],
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()

    return pl.pallas_call(
        kernel,
        grid=(n_devices - 1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=out_shape,
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 3,
        compiler_params=pltpu.CompilerParams(collective_id=0),
    )(x_shard).reshape(n_devices * rows, cols)


# ------------------------------------------------- CPU-validatable datapath


def _local_pipeline_kernel(staged_ref, out_ref, *, n_slots: int):
    """The local double-buffered chunk datapath of the ring engine: at grid
    step s, drain slot s%2 into out[s] (models: receive lands in one slot
    while the other drains — the staging-ring discipline of §III-B at
    two-slot depth). Runs in interpret mode on CPU."""
    s = pl.program_id(0)
    out_ref[...] = staged_ref[...]


def local_double_buffer_drain(staged: jax.Array, *, interpret: bool | None = None):
    """staged (n_steps, rows, cols): the sequence of chunks 'received' per
    step (alternating slots upstream); returns them drained in order —
    the local-copy half of the ring engine, testable vs a jnp oracle."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n, rows, cols = staged.shape
    return pl.pallas_call(
        functools.partial(_local_pipeline_kernel, n_slots=2),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, rows, cols), lambda s: (s, 0, 0))],
        out_specs=pl.BlockSpec((1, rows, cols), lambda s: (s, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, rows, cols), staged.dtype),
        interpret=interpret,
    )(staged)


def ring_schedule(n_devices: int) -> list[list[tuple[int, int, int]]]:
    """The (sender, receiver, shard) triples per step — the schedule oracle
    shared with core.collectives.ring_allgather_local (tested equal)."""
    steps = []
    for s in range(n_devices - 1):
        trip = []
        for d in range(n_devices):
            src_shard = (d - s) % n_devices
            trip.append((d, (d + 1) % n_devices, src_shard))
        steps.append(trip)
    return steps
