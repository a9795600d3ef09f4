"""System behaviour: dry-run machinery on a small mesh + HLO collective stats.

The production 512-device dry-run runs via ``python -m repro.launch.dryrun``;
here we validate the same machinery end-to-end at test scale (8 devices).
"""
import pytest

# jax model/integration tier: excluded from the fast CI
# lane (scripts/check.sh), run by the `slow` CI job
pytestmark = pytest.mark.slow



def test_hlo_collective_stats(multidev):
    multidev(
        """
import pytest
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))

def f(x, w):
    y = x @ w                          # contraction over sharded dim -> AR/RS
    return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P()))

x = jax.ShapeDtypeStruct((128, 256), jnp.float32,
                         sharding=NamedSharding(mesh, P('data', 'model')))
w = jax.ShapeDtypeStruct((256, 64), jnp.float32,
                         sharding=NamedSharding(mesh, P('model', None)))
comp = jax.jit(f).lower(x, w).compile()
st = collective_stats(comp.as_text(), 8)
assert st.total_bytes > 0, st.as_dict()
assert sum(st.counts.values()) >= 1
print('ok', st.as_dict())
"""
    )


def test_loop_scaled_collectives(multidev):
    """Collectives inside a scan are multiplied by the loop-chain length."""
    multidev(
        """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('x',))

def f(x, ws):
    def body(c, w):
        wg = jax.lax.with_sharding_constraint(w, NamedSharding(mesh, P()))
        return jnp.tanh(c @ wg), None
    y, _ = jax.lax.scan(body, x, ws)
    return y

x = jax.ShapeDtypeStruct((64, 64), jnp.float32,
                         sharding=NamedSharding(mesh, P()))
ws = jax.ShapeDtypeStruct((12, 64, 64), jnp.float32,
                          sharding=NamedSharding(mesh, P(None, 'x', None)))
comp = jax.jit(f).lower(x, ws).compile()
txt = comp.as_text()
st1 = collective_stats(txt, 8, loop_chain=())
st12 = collective_stats(txt, 8, loop_chain=(12,))
in_loop = any('while/body' in l and 'all-gather' in l for l in txt.splitlines())
if in_loop:
    assert st12.total_bytes > st1.total_bytes
print('ok', st1.total_bytes, st12.total_bytes, 'in_loop', in_loop)
"""
    )


def test_dryrun_cell_machinery(multidev):
    """run_cell on a full config compiles on the production mesh and emits
    roofline inputs (512 fake devices; one fast cell)."""
    multidev(
        """
import os
assert os.environ['XLA_FLAGS'].endswith('512')
from repro.launch.dryrun import run_cell

rec = run_cell('smollm-135m', 'decode_32k', False)
assert rec['ok'], rec.get('error')
assert rec['analytic']['model_flops'] > 0
assert rec['analytic']['hbm_bytes_per_device'] > 0
assert rec['collectives_hlo']['per_device_total'] >= 0
print('ok', rec['compile_s'])
""",
        n_devices=512,
        timeout=420,
    )


def test_cell_enumeration():
    from repro.configs import iter_cells

    cells = list(iter_cells(include_skipped=True))
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    # long_500k runs only for the two sub-quadratic archs
    assert len(runnable) == 32
    assert all(c[1] == "long_500k" for c in skipped)
    assert {c[0] for c in cells if c[1] == "long_500k" and c[2]} == {
        "rwkv6-7b", "recurrentgemma-9b"
    }
