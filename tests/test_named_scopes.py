"""The train step names its layers and the train loop its host phases: the
named scopes reach the compiled HLO's ``op_name`` metadata (forward,
backward and the FSDP gather), and ``TrainSupervisor.run`` writes its
``train.*`` spans into a profiler trace without changing what it computes."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import RunConfig, ShapeConfig, TrainConfig, get_model_config, reduced
from repro.data import SyntheticPipeline
from repro.models.model_builder import batch_dims
from repro.runtime import abstract_state, init_state, make_train_step
from repro.runtime.fault import TrainSupervisor

pytestmark = pytest.mark.slow

LOOP_SPANS = ("train.next_batch", "train.sync", "train.record", "train.checkpoint")


def _run():
    return RunConfig(model=reduced(get_model_config("smollm-135m")),
                     shape=ShapeConfig("t", "train", 64, 2),
                     train=TrainConfig(steps=4, remat="full", learning_rate=1e-2))


def _scope_paths(op_names, scope):
    # the scope as a path component, bare or inside transforms: "mlp", "jvp(xent)"
    comp = re.compile(rf"(^|/)(\w+\()*{scope}\)*(/|$)")
    return [n for n in op_names if comp.search(n)]


@pytest.fixture(scope="module")
def op_names():
    run = _run()
    _, _, step = make_train_step(run, None)
    batch = {k: jax.ShapeDtypeStruct(v, jnp.int32)
             for k, v in batch_dims(run.model, run.shape).items()}
    text = jax.jit(step).lower(abstract_state(run), batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope,backward", [
    ("attention", True), ("mlp", True), ("embed", True), ("xent", True),
    ("optimizer", False),
])
def test_scope_in_compiled_op_names(op_names, scope, backward):
    paths = _scope_paths(op_names, scope)
    assert any("transpose(" not in p for p in paths), f"no forward op in {scope!r}"
    if backward:
        assert any("transpose(" in p for p in paths), f"no backward op in {scope!r}"


def test_layer_scopes_cover_the_remat_recompute(op_names):
    recompute = [n for n in op_names if "rematted_computation" in n]
    for scope in ("attention", "mlp"):
        assert _scope_paths(recompute, scope), scope


def test_fsdp_gather_scope_on_the_mcast_permutes(multidev):
    out = multidev(
        """
import re
import jax, jax.numpy as jnp
from repro.configs import (CollectiveConfig, MeshConfig, RunConfig, ShapeConfig,
                           TrainConfig, get_model_config, reduced)
from repro.launch.mesh import mesh_for
from repro.runtime import abstract_state
from repro.runtime.train_loop import jit_train_step

cfg = reduced(get_model_config('smollm-135m'))
run = RunConfig(model=cfg, shape=ShapeConfig('t', 'train', 64, 4),
                mesh=MeshConfig((4, 1), ('data', 'model')), train=TrainConfig(steps=2),
                collective=CollectiveConfig(fsdp_mode='mcast'))
mesh = mesh_for(run.mesh)
_, jstep = jit_train_step(run, mesh)
batch = {k: jax.ShapeDtypeStruct((4, 64), jnp.int32) for k in ('tokens', 'targets')}
text = jstep.lower(abstract_state(run), batch).compile().as_text()
permutes = re.findall(r'= \\S+ collective-permute(?:-start)?\\(.*op_name="([^"]*)"', text)
print('permutes', len(permutes))
print('scoped', sum('fsdp_gather' in n for n in permutes))
"""
    , n_devices=4)
    counts = dict(line.split() for line in out.splitlines() if line.split()[0] in
                  ("permutes", "scoped"))
    assert int(counts["permutes"]) > 0
    assert counts["scoped"] == counts["permutes"], out


def _losses(tmp_path, name, trace_dir=None):
    run = _run()
    _, _, step = make_train_step(run, None)
    sup = TrainSupervisor(step_fn=jax.jit(step), pipeline=SyntheticPipeline(run.model, run.shape),
                          ckpt_dir=str(tmp_path / name), ckpt_every=1, async_ckpt=False)
    state = init_state(run, None, jax.random.PRNGKey(0))
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        _, hist = sup.run(state, 2)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return [(h["step"], h["loss"], h["grad_norm"]) for h in hist]


def test_supervisor_spans_once_per_step_and_same_losses(tmp_path):
    from jax.profiler import ProfileData

    plain = _losses(tmp_path, "plain")
    traced = _losses(tmp_path, "traced", trace_dir=tmp_path / "trace")
    assert traced == plain

    files = glob.glob(os.path.join(str(tmp_path / "trace"), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(files[0])
    names = [ev.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    for span in LOOP_SPANS:
        assert names.count(span) == 2, (span, names.count(span))
    steps = [dict(ev.stats).get("step_num") for plane in data.planes
             for line in plane.lines for ev in line.events if ev.name == "train"]
    assert sorted(int(s) for s in steps) == [0, 1]


_CACHE_PROBE = """
import os, sys
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
events = []
jax.monitoring.register_event_listener(lambda e, **kw: events.append(e))
sys.path.insert(0, sys.argv[1])
from probe_fn import f
text = jax.jit(f).lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
print("hit" if "/jax/compilation_cache/cache_hits" in events else "miss",
      "xent" in text)
"""

_PLAIN = "import jax.numpy as jnp\n\n\ndef f(x):\n    return jnp.sum(jnp.exp(x))\n"
_SCOPED = ("import jax\nimport jax.numpy as jnp\n\n\ndef f(x):\n"
           "    with jax.named_scope('xent'):\n        return jnp.sum(jnp.exp(x))\n")


def test_compile_cache_keys_on_scopes_and_not_on_checkout_paths(tmp_path):
    import subprocess
    import sys

    from conftest import SRC

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))

    def compile_from(name, source):
        d = tmp_path / name
        d.mkdir()
        (d / "probe_fn.py").write_text(source)
        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, str(d)], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-3000:]
        return out.stdout.split()

    assert compile_from("plain", _PLAIN) == ["miss", "False"]
    # the same computation with a named scope: its own entry, its own op_names
    assert compile_from("scoped", _SCOPED) == ["miss", "True"]
    # the same source at another path shares the entry
    assert compile_from("scoped_elsewhere", _SCOPED) == ["hit", "True"]
