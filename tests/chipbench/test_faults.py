"""A whole run of a cell, cut to a size the CPU holds, with the chip check
skipped: a sound run comes out correct, and a run with a fault planted in
the program's timed path (or the float8 control in its place) does not."""
import dataclasses
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from chipbench import cell as C  # noqa: E402
from chipbench import compare  # noqa: E402
from chipbench.faults import planted  # noqa: E402
from chipbench.harness import run_cell  # noqa: E402
from chipbench.reference import Reference  # noqa: E402

TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512)


def tiny_cell(name: str, batch: int, rows: int) -> C.Cell:
    cell = C.load(name)
    return dataclasses.replace(
        cell, config=dict(cell.config, **TINY),
        traffic=dict(cell.traffic, global_batch=batch, seq_len=64, ref_rows=rows))


def run(cell, devices, seed=2**33 + 7):
    return run_cell(cell, seed=seed, seconds=0.2, trace=False, devices=devices,
                    peak={}, t0=time.perf_counter())


@pytest.fixture(scope="module")
def cell():
    return tiny_cell("smollm135m-s2048-b16", batch=4, rows=2)


def test_sound_run_is_correct(cell):
    r = run(cell, jax.devices()[:1])
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "shift_targets"])
def test_fault_is_caught(cell, fault):
    with planted(fault):
        r = run(cell, jax.devices()[:1])
    assert not r["correct"], (fault, r["checks"])


def test_control_is_caught(cell):
    """The float8 control in the program's place lands far from the
    reference. At this size it reads about 10-30x the program's loss gap and
    8-25x its gradient gap; at the cell's own size on the chip, where the
    limits were set, it fails the gradient limit by far (PERF.md)."""
    from chipbench import model as M
    from chipbench.harness import Program, Spans

    arch = M.arch(cell.config["model_type"])
    dims = arch.Dims.from_config(cell.config)
    devices = jax.devices()[:1]
    ref = Reference(arch, dims, cell.traffic, devices).run(11, 3)
    ctrl = Reference(arch, dims, cell.traffic, devices, precision="fp8").run(11, 3)
    _, _, prog, _ = Program(cell, devices).check_steps(11, Spans())
    g_prog, g_ctrl = compare.gaps(prog, ref), compare.gaps(ctrl, ref)
    assert g_ctrl["loss"] > 5 * g_prog["loss"], (g_prog, g_ctrl)
    assert g_ctrl["grad"] > 5 * g_prog["grad"], (g_prog, g_ctrl)
