"""ZeRO-3's weight traffic per chip and training step, from the configuration
alone (Rajbhandari et al., arXiv:1910.02054: partitioning the parameters
moves 1.5x the bytes of data parallelism's gradient all-reduce).

Each layer's weights are gathered before its forward, gathered again before
its backward (the remat recompute), and its gradients are reduce-scattered:
three passes over the stacked layer weights, each carrying (P-1)/P of them
through every chip's links, in the dtype the weights are stored in. The
embedding and the head are left out. The count depends on the shapes only,
never on how the program gathers.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import model as M

PASSES = 3  # forward gather, backward re-gather, gradient reduce-scatter


def layer_weight_bytes(config: dict) -> int:
    """Bytes of the stacked per-layer weights (``LAYER_LEAVES``) as stored."""
    arch = M.arch(config["model_type"])
    specs = arch.leaf_specs(arch.Dims.from_config(config))
    return sum(math.prod(shape) * np.dtype(dtype).itemsize
               for name, (shape, dtype, _) in specs.items() if name in arch.LAYER_LEAVES)


def bytes_per_step(config: dict) -> int:
    """Bytes each chip sends per step over the data axis of P chips that
    shards every layer: 3 x (P-1)/P x the layer weights' bytes; 0 where
    the configuration has no data axis."""
    p = (config.get("mesh") or {}).get("data", 1)
    return PASSES * (p - 1) * layer_weight_bytes(config) // p
