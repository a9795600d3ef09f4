"""Seeds, architectures and norms shared by the harness and the reference.

An architecture is a module ``chipbench/arch/<model_type>.py``, found by the
``model_type`` of a configuration file. It gives the sizes (``Dims``), the
weights made from a seed in the reference's layout (``leaf_specs``,
``make_params``), the mapping to and from the program's parameter tree
(``to_program``, ``named``), the plain forward and loss (``loss_sum``) and
the useful FLOPs (``useful_flops_per_token``).
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType

import jax
import jax.numpy as jnp
import numpy as np

_ARCH_DIR = Path(__file__).resolve().parent / "arch"


def arch(model_type: str) -> ModuleType:
    path = _ARCH_DIR / f"{model_type}.py"
    if not path.is_file():
        raise ValueError(f"no architecture module for model_type {model_type!r} ({path})")
    return load_module(f"chipbench.arch.{model_type}", path)


def load_module(name: str, path: Path) -> ModuleType:
    """The module at ``path``, imported once under ``name``."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def make_params(specs: dict, key: jax.Array) -> dict[str, jax.Array]:
    """Every leaf of ``specs`` (name -> (shape, dtype, std)) drawn from
    ``key``; call under jit. A leaf's values depend only on its name and the
    key, so any sharding of the output gives the same bits."""
    out = {}
    for i, name in enumerate(sorted(specs)):
        shape, dtype, std = specs[name]
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
        out[name] = x.astype(dtype)
    return out


def leaf_norms(p: dict[str, jax.Array], stacked: frozenset) -> dict[str, jax.Array]:
    """Float32 L2 norm of each leaf; a leaf named in ``stacked`` carries a
    leading layer axis and gives one norm per layer. Call under jit."""
    out = {}
    for name, x in p.items():
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if name in stacked else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


def flat_norms(norms: dict) -> dict[str, float]:
    """{"wq": [n0, n1, ...], "embed": n} -> {"wq.0": n0, ..., "embed": n}."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            for i, x in enumerate(v):
                out[f"{name}.{i}"] = float(x)
    return out
