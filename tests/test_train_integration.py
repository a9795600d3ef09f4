"""End-to-end training integration: descent, grad-accum equivalence, and the
FSDP-mode equivalence on a multi-device mesh."""
import re

import jax
import pytest

from repro.configs import (RunConfig, ShapeConfig, TrainConfig,
                           get_model_config, reduced)
from repro.data import SyntheticPipeline
from repro.runtime import init_state, make_train_step
# jax model/integration tier: excluded from the fast CI
# lane (scripts/check.sh), run by the `slow` CI job
pytestmark = pytest.mark.slow


def _run(grad_accum=1, steps=30):
    cfg = reduced(get_model_config("smollm-135m"))
    return RunConfig(
        model=cfg, shape=ShapeConfig("t", "train", 64, 8),
        train=TrainConfig(steps=steps, grad_accum=grad_accum,
                          learning_rate=1e-2, warmup_steps=2),
    )


def test_loss_descends():
    run = _run()
    api, ctx, step = make_train_step(run, None)
    state = init_state(run, None, jax.random.PRNGKey(0))
    pipe = SyntheticPipeline(run.model, run.shape)
    jstep = jax.jit(step)
    losses = []
    for i in range(30):
        state, m = jstep(state, pipe.next_batch(i))
        losses.append(float(m["loss"]))
    assert min(losses[-5:]) < losses[0] - 0.3, losses[:3] + losses[-3:]


def test_grad_accum_equivalence():
    """accum=2 on the same global batch gives (nearly) the same first step."""
    pipe = SyntheticPipeline(_run().model, _run().shape)
    batch = pipe.next_batch(0)
    results = {}
    for a in (1, 2):
        run = _run(grad_accum=a)
        api, ctx, step = make_train_step(run, None)
        state = init_state(run, None, jax.random.PRNGKey(0))
        _, m = jax.jit(step)(state, batch)
        results[a] = (float(m["loss"]), float(m["grad_norm"]))
    assert results[1][0] == pytest.approx(results[2][0], rel=1e-5)
    assert results[1][1] == pytest.approx(results[2][1], rel=1e-3)


@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 1)], ids=["data2-model4", "data4"])
def test_fsdp_modes_bitwise_equal(multidev, mesh_shape):
    """xla vs the paper's gathers (mcast, mcast_ring, mcast_bcast): identical
    loss and grad norm on a data x model mesh, with tp splitting the heads
    (2, 4) and without (4, 1). The gathered layers run in a region manual
    over data on both."""
    multidev(
        """
import jax, dataclasses
from repro.configs import (CollectiveConfig, MeshConfig, RunConfig, ShapeConfig,
                           TrainConfig, get_model_config, reduced)
from repro.launch.mesh import mesh_for
from repro.runtime import init_state
from repro.runtime.train_loop import jit_train_step
from repro.data import SyntheticPipeline

cfg = reduced(get_model_config('smollm-135m'))
out = {}
for mode in ['xla', 'mcast', 'mcast_ring', 'mcast_bcast']:
    run = RunConfig(model=cfg, shape=ShapeConfig('t','train',64,4),
                    mesh=MeshConfig(%r, ('data', 'model')),
                    train=TrainConfig(steps=5),
                    collective=CollectiveConfig(fsdp_mode=mode, n_chains=2))
    mesh = mesh_for(run.mesh)
    api, jstep = jit_train_step(run, mesh)
    state = init_state(run, mesh, jax.random.PRNGKey(0))
    pipe = SyntheticPipeline(cfg, run.shape)
    state, m = jstep(state, pipe.next_batch(0))
    out[mode] = (float(m['loss']), float(m['grad_norm']))
base = out['xla']
for mode, val in out.items():
    assert abs(val[0] - base[0]) < 1e-6, (mode, val, base)
    assert abs(val[1] - base[1]) < 1e-5, (mode, val, base)
print('ok', out)
""" % (mesh_shape,), n_devices=mesh_shape[0] * mesh_shape[1]
    )


def test_mcast_reduces_each_weight_gradient_once(multidev):
    """The compiled ``mcast`` step of a reduced dense model on a (4, 1) mesh:
    inside the layer scan the only all-reduce is the replicated norms'
    (the ring reduce-scatter, the gather's transpose, is each weight
    gradient's one reduction), and the ring's collective-permutes send
    what they sent when XLA also all-reduced every gathered gradient:
    each sharded byte (P-1)/P times in each of three passes (forward,
    remat recompute, backward reduce-scatter)."""
    out = multidev(
        r"""
import re
import jax
from repro.configs import (CollectiveConfig, MeshConfig, RunConfig, ShapeConfig,
                           TrainConfig, get_model_config, reduced)
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import mesh_for
from repro.runtime import abstract_state
from repro.runtime.train_loop import jit_train_step
from repro.models import batch_dims

cfg = reduced(get_model_config('smollm-135m'))
run = RunConfig(model=cfg, shape=ShapeConfig('t', 'train', 64, 4),
                mesh=MeshConfig((4, 1), ('data', 'model')), train=TrainConfig(steps=1),
                collective=CollectiveConfig(fsdp_mode='mcast'))
_, jstep = jit_train_step(run, mesh_for(run.mesh))
batch = {k: jax.ShapeDtypeStruct(v, jax.numpy.int32)
         for k, v in batch_dims(cfg, run.shape).items()}
text = jstep.lower(abstract_state(run), batch).compile().as_text()
# the layer scan's instructions (the loss head's chunk loop is jvp(xent))
scan = [l for l in text.splitlines() if re.search(r"jvp\(\)\)?/while/body/", l)]
for l in scan:
    m = re.search(r"= (.+?) all-reduce(-start)?\(", l)
    if m:
        print("AR", m.group(1))
sent = collective_stats("\n".join(scan), 4, loop_chain=(cfg.num_layers, 3))
print("SENT", sent.per_device_bytes["collective-permute"])
print("SHAPES", cfg.d_model, cfg.num_layers)
""", n_devices=4)
    lines = out.splitlines()
    d_model, layers = map(int, next(x for x in lines if x.startswith("SHAPES")).split()[1:])
    ars = [x[3:] for x in lines if x.startswith("AR ")]
    assert ars, out
    for result in ars:
        # at most the two norms, float32 (d_model,) each
        assert set(re.findall(r"\w+\[[\d,]*\]", result)) == {f"f32[{d_model}]"}, ars
    # the weights of one layer, float32: wq, wo (d x d), wk, wv (d x d/2),
    # w_gate, w_up, w_down (d x 2d) at the reduced widths
    per_layer = (2 * d_model * d_model + 2 * d_model * d_model // 2
                 + 3 * d_model * 2 * d_model) * 4
    sent = int(next(x for x in lines if x.startswith("SENT")).split()[1])
    assert sent == 3 * per_layer * layers * 3 // 4 == 663_552


def test_moe_train_multidev(multidev):
    """MoE arch trains on the mesh (EP dispatch lowers + finite loss)."""
    multidev(
        """
import jax
from repro.configs import (MeshConfig, RunConfig, ShapeConfig, TrainConfig,
                           get_model_config, reduced)
from repro.launch.mesh import mesh_for
from repro.runtime import init_state
from repro.runtime.train_loop import jit_train_step
from repro.data import SyntheticPipeline

cfg = reduced(get_model_config('deepseek-moe-16b'))
run = RunConfig(model=cfg, shape=ShapeConfig('t','train',64,4),
                mesh=MeshConfig((2, 4), ('data', 'model')), train=TrainConfig(steps=2))
mesh = mesh_for(run.mesh)
api, jstep = jit_train_step(run, mesh)
state = init_state(run, mesh, jax.random.PRNGKey(0))
pipe = SyntheticPipeline(cfg, run.shape)
state, m = jstep(state, pipe.next_batch(0))
import math

assert math.isfinite(float(m['loss']))
print('ok', float(m['loss']))
"""
    )
