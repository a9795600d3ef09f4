"""Compiles of the main path for a described TPU v5e; nothing runs.

The TPU compiler refuses what the CPU backend and interpret mode accept:
memory spaces, tiling, programs that do not fit the device. These tests run
that compiler on programs of real widths. The topology is described only
inside the module fixture (only one process at a time may load the TPU
library, and every test worker imports this file), and the persistent
compile cache is off around them: entries compiled for a described chip
cannot be read back without one.
"""
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import (CollectiveConfig, MeshConfig, RunConfig, ShapeConfig,
                           TrainConfig, get_model_config)
from repro.kernels.ring_allgather import ring_allgather_tpu
from repro.launch.mesh import make_mesh, mesh_for
from repro.models import batch_dims, build_model
from repro.runtime.train_loop import abstract_state, make_train_step
from repro.sharding.fsdp import make_param_gather
from repro.sharding.specs import param_pspecs

V5E_HBM_BYTES = 16 * 2**30
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA4 = MeshConfig((4, 1), ("data", "model"))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _with_sharding(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def test_smollm_train_step_fits_one_chip(topo):
    """smollm-135m at published widths, cut to 2 layers, B=8 S=2048."""
    one = SingleDeviceSharding(topo.devices[0])
    model = dataclasses.replace(get_model_config("smollm-135m"), num_layers=2)
    run = RunConfig(model=model, shape=ShapeConfig("t", "train", 2048, 8),
                    train=TrainConfig(checkpoint_every=0))
    _, _, step = make_train_step(run, None)
    batch = {k: jax.ShapeDtypeStruct(v, jnp.int32, sharding=one)
             for k, v in batch_dims(model, run.shape).items()}
    compiled = jax.jit(step).lower(
        _with_sharding(abstract_state(run), one), batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem

    # attention runs as the fused kernel: its forward, remat recompute and
    # backward (dq, dkv) custom calls, all inside the ``attention`` scope
    text = compiled.as_text()
    kernels = {}
    for name, body in _instructions(text):
        if 'custom_call_target="tpu_custom_call"' in body:
            kernels[name] = re.search(r'op_name="([^"]*)"', body).group(1)
    assert kernels, "no kernel in the compiled step"
    kinds = {re.match(r"splash_mha_(fwd|dq|dkv)_", n).group(1) for n in kernels}
    assert kinds == {"fwd", "dq", "dkv"}, sorted(kernels)
    for name, op_name in kernels.items():
        assert re.search(r"(^|/)(\w+\()*attention\)*/", op_name), (name, op_name)
    passes = {("backward" if "transpose(" in op else "forward") +
              ("+recompute" if "rematted_computation" in op else "")
              for op in kernels.values()}
    assert passes == {"forward", "backward+recompute", "backward"}, passes
    # and the scans' float32 (q_block, kv_block) score blocks are gone
    assert not re.search(r"f32\[[\d,]*512,1024\]", text)


def _instructions(text):
    """(name, text) of each instruction of compiled HLO text; an
    instruction's attributes may run over several lines."""
    return re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*?)"
                      r"(?=^\s+(?:ROOT )?%[\w.\-]+ = |^\}$|\Z)", text, re.M | re.S)


@pytest.mark.parametrize("mode", ["mcast", "mcast_bcast"])
def test_yi9b_layer_gather_on_data4(topo, mode):
    """One layer's FSDP weight gather at yi-9b widths on the 2x2 host: the
    paper's schedules lower to collective-permutes and leave every weight
    whole on each device."""
    mesh = mesh_for(DATA4, devices=topo.devices)
    model = dataclasses.replace(get_model_config("yi-9b"), num_layers=1)
    params = jax.eval_shape(build_model(model).init_params, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                         params["blocks"])
    specs = param_pspecs(layer, mesh, DATA4)
    layer = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        layer, specs)
    gather = make_param_gather(mesh, DATA4, CollectiveConfig(fsdp_mode=mode))
    compiled = jax.jit(gather).lower(layer).compile()
    assert "collective-permute" in compiled.as_text()
    outs = jax.tree.leaves(layer)
    for x, sh in zip(outs, jax.tree.leaves(compiled.output_shardings)):
        assert sh.shard_shape(x.shape) == x.shape, (x.shape, sh)


def test_yi9b_l8_mcast_step_fits_2x2(topo):
    """The four-chip benchmark cell's whole train step (yi-9b at published
    widths, 8 layers, B=8 x S=2048, ``mcast`` gathers over data=4, full
    remat), as the benchmark assembles it, fits each chip; its ring sends
    ZeRO-3's bytes less the replicated norms' share, and attention is the
    fused kernel inside ``shard_map``."""
    sys.path.insert(0, ROOT)
    from chipbench import cell as C
    from chipbench import zero3
    from chipbench.harness import Program
    from repro.launch.hlo_stats import _loop_multiplier, _shape_bytes

    cell = C.load("yi9b-l8-fsdp4-mcast")
    prog = Program(cell, topo.devices)
    run = prog.run_cfg
    batch = {k: jax.ShapeDtypeStruct(v, jnp.int32)
             for k, v in batch_dims(run.model, run.shape).items()}
    compiled = prog.step_fn.lower(abstract_state(run), batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem

    # each collective-permute-done holds what its start received; it runs
    # once per layer and ring step (the layer scan, and the ring's P - 1)
    text = compiled.as_text()
    sent = 0
    for name, body in _instructions(text):
        m = re.match(r"(\S+) collective-permute-done\(", body)
        if m:
            sent += _shape_bytes(m.group(1)) * _loop_multiplier(body, (8, 3))
    norms = 3 * 3 * (2 * 8 * 4096 * 4) // 4
    assert sent == zero3.bytes_per_step(cell.config) - norms == 6_228_541_440
    assert "tpu_custom_call" in text


def test_ring_rdma_kernel_compiles_for_2x2(topo):
    mesh = make_mesh((4,), ("ring",), devices=topo.devices)
    x = jax.ShapeDtypeStruct((4 * 8, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P("ring", None)))
    f = jax.shard_map(lambda xs: ring_allgather_tpu(xs, n_devices=4), mesh=mesh,
                      in_specs=P("ring", None), out_specs=P(None, None),
                      check_vma=False)
    compiled = jax.jit(f).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
