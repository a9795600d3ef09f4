"""The FSDP gather's two forms, and the layer scans' remat policies.

Under the paper's gathers a layer's weights are gathered in one of two forms
(``sharding/fsdp.py:ParamGather``): inside a region manual over the dp axes
(the train path of dense and VLM blocks with the batch split over dp), or by
a replicated-out ``shard_map`` per leaf (MoE, RWKV6, the RG-LRU hybrid,
encoder-decoder, and any batch left whole). Both must give what XLA's own
gathers give. Every family's layer scans take their remat policy from one
helper (``models/layers.py:remat_body``).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import (CollectiveConfig, MeshConfig, ShapeConfig, get_model_config,
                           reduced)
from repro.models import batch_dims, build_model
from repro.models.layers import remat_body
from repro.sharding.fsdp import make_param_gather

# (arch, global batch): a batch of 4 is split over data=4, a batch of 2 is
# left whole (so smollm-135m there takes the replicated-out form too)
FORMS = [
    ("deepseek-moe-16b", 4),
    ("rwkv6-7b", 4),
    ("recurrentgemma-9b", 4),
    ("whisper-base", 4),
    ("phi-3-vision-4.2b", 4),
    ("smollm-135m", 2),
]


@pytest.mark.parametrize("arch,batch", FORMS, ids=[f"{a}-b{b}" for a, b in FORMS])
def test_mcast_equals_xla(multidev, arch, batch):
    """One train step of the reduced model on a (4, 1) data x model mesh:
    the paper's bidirectional ring gives XLA's loss and grad norm."""
    multidev(
        """
import jax
from repro.configs import (CollectiveConfig, MeshConfig, RunConfig, ShapeConfig,
                           TrainConfig, get_model_config, reduced)
from repro.data import SyntheticPipeline
from repro.launch.mesh import mesh_for
from repro.runtime import init_state
from repro.runtime.train_loop import jit_train_step

cfg = reduced(get_model_config(%r))
out = {}
for mode in ['xla', 'mcast']:
    run = RunConfig(model=cfg, shape=ShapeConfig('t', 'train', 64, %d),
                    mesh=MeshConfig((4, 1), ('data', 'model')),
                    train=TrainConfig(steps=5), collective=CollectiveConfig(fsdp_mode=mode))
    mesh = mesh_for(run.mesh)
    _, jstep = jit_train_step(run, mesh)
    state = init_state(run, mesh, jax.random.PRNGKey(0))
    _, m = jstep(state, SyntheticPipeline(cfg, run.shape).next_batch(0))
    out[mode] = (float(m['loss']), float(m['grad_norm']))
base = out['xla']
for got, want in zip(out['mcast'], base):
    assert abs(got - want) <= 1e-5 * abs(want), out
print('ok', out)
""" % (arch, batch), n_devices=4)


def _remat_eqns(jaxpr):
    """The checkpoint equations of ``jaxpr`` and of every jaxpr nested in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("remat2", "checkpoint"):
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _remat_eqns(sub)


def _loss_remats(arch: str, remat: str):
    cfg = reduced(get_model_config(arch))
    api = build_model(cfg, remat=remat)
    params = jax.eval_shape(api.init_params, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct(v, jnp.int32 if k in ("tokens", "targets") else jnp.float32)
             for k, v in batch_dims(cfg, ShapeConfig("t", "train", 64, 2)).items()}
    closed = jax.make_jaxpr(lambda p, b: api.loss_fn(p, b)[0])(params, batch)
    return list(_remat_eqns(closed.jaxpr))


@pytest.mark.parametrize("remat", ["none", "full", "dots"])
@pytest.mark.parametrize("arch", ["smollm-135m", "rwkv6-7b", "recurrentgemma-9b", "whisper-base"])
def test_layer_scans_honour_remat(arch, remat):
    """The loss head checkpoints its own chunks under every policy; "full"
    and "dots" add a checkpoint around each layer scan's body, and under
    "dots" each added one saves the matmul outputs (it carries a policy)."""
    head = len(_loss_remats(arch, "none"))
    assert head >= 1
    eqns = _loss_remats(arch, remat)
    if remat == "none":
        assert len(eqns) == head
        return
    assert len(eqns) > head, (arch, remat, len(eqns))
    with_policy = [e for e in eqns if e.params["policy"] is not None]
    assert len(with_policy) == (len(eqns) - head if remat == "dots" else 0), eqns


def test_unknown_remat_policy_raises():
    with pytest.raises(ValueError, match="remat"):
        remat_body(lambda c, x: (c, x), "dot")


def test_unknown_fsdp_mode_raises():
    with pytest.raises(ValueError, match="fsdp_mode"):
        make_param_gather(None, MeshConfig((4, 1), ("data", "model")),
                          CollectiveConfig(fsdp_mode="mcats"))
