"""Checkpoint roundtrip, fault-tolerant supervision, elastic restore."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore, save
from repro.configs import RunConfig, ShapeConfig, TrainConfig, get_model_config, reduced
from repro.data import SyntheticPipeline
from repro.runtime import init_state, make_train_step
from repro.runtime.fault import FailureInjector, StragglerMonitor, TrainSupervisor
# jax model/integration tier: excluded from the fast CI
# lane (scripts/check.sh), run by the `slow` CI job
pytestmark = pytest.mark.slow


def _tiny_run():
    cfg = reduced(get_model_config("smollm-135m"))
    return RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 2),
                     train=TrainConfig(steps=50))


def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "b": {"c": jnp.ones((4,), jnp.bfloat16), "d": jnp.array(3)}}
    save(state, str(tmp_path), 7)
    assert latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, manifest = restore(str(tmp_path), 7, like)
    assert manifest["step"] == 7
    for l1, l2 in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                      np.asarray(l2, np.float32))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    save({"a": jnp.ones((2,))}, str(tmp_path), 1)
    with pytest.raises(ValueError):
        restore(str(tmp_path), 1, {"a": jax.ShapeDtypeStruct((3,), jnp.float32)})


def test_async_checkpoint(tmp_path):
    fut = save({"a": jnp.ones((8,))}, str(tmp_path), 2, blocking=False)
    fut.result()
    assert latest_step(str(tmp_path)) == 2


def test_supervisor_recovers_from_failures(tmp_path):
    run = _tiny_run()
    api, ctx, step = make_train_step(run, None)
    state = init_state(run, None, jax.random.PRNGKey(0))
    pipe = SyntheticPipeline(run.model, run.shape)
    jstep = jax.jit(step)

    # run to completion WITH two injected failures; checkpoint every 4 steps
    sup = TrainSupervisor(
        step_fn=jstep, pipeline=pipe, ckpt_dir=str(tmp_path), ckpt_every=4,
        injector=FailureInjector(fail_at_steps=(6, 11)), async_ckpt=False,
    )
    final, hist = sup.run(state, 16)
    executed = [h["step"] for h in hist]
    assert executed[-1] == 15
    # failure at 6 -> restart from ckpt@4 (replays 4,5); at 11 -> from 8
    assert executed.count(4) >= 2 or executed.count(5) >= 2
    assert int(final.opt.step) > 0

    # determinism: a failure-free run from the same seed reaches the same loss
    state2 = init_state(run, None, jax.random.PRNGKey(0))
    sup2 = TrainSupervisor(step_fn=jstep, pipeline=pipe, ckpt_dir=str(tmp_path) + "2",
                           ckpt_every=0, async_ckpt=False)
    final2, hist2 = sup2.run(state2, 16)
    assert hist[-1]["loss"] == pytest.approx(hist2[-1]["loss"], abs=1e-5)


def test_straggler_monitor():
    mon = StragglerMonitor(threshold=2.0)
    for i in range(10):
        assert not mon.observe(i, 0.1)
    assert mon.observe(10, 1.0)       # 10x slower -> flagged
    assert len(mon.events) == 1
    assert not mon.observe(11, 0.1)   # recovers


def test_elastic_restore_into_other_mesh(multidev):
    multidev(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import RunConfig, ShapeConfig, TrainConfig, MeshConfig, get_model_config, reduced
from repro.runtime import init_state
from repro.runtime.elastic import reshard_state, scale_plan
from repro.checkpoint import save, restore
from repro.runtime.train_loop import state_pspecs
from repro.launch.mesh import mesh_for
from jax.sharding import NamedSharding, PartitionSpec as P

cfg = reduced(get_model_config('smollm-135m'))
run1 = RunConfig(model=cfg, shape=ShapeConfig('t','train',32,8),
                 mesh=MeshConfig((2, 4), ('data', 'model')))
mesh1 = mesh_for(run1.mesh)
state = init_state(run1, mesh1, jax.random.PRNGKey(0))
import tempfile, os

d = tempfile.mkdtemp()
save(state, d, 5)

# restore into a (4,2) mesh — elastic rescale
run2 = run1.replace(mesh=MeshConfig((4, 2), ('data', 'model')))
mesh2 = mesh_for(run2.mesh)
specs = state_pspecs(run2, mesh2)
sh = jax.tree.map(lambda s: NamedSharding(mesh2, s), specs, is_leaf=lambda x: isinstance(x, P))
like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
restored, _ = restore(d, 5, like, shardings=sh)
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
# in-memory reshard path
rs = reshard_state(state, run2, mesh2)
for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(rs)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
plan = scale_plan(2, 4, 32)
assert plan['new_per_replica'] == 8
print('ok')
"""
    )
