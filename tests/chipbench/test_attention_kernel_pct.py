"""``attention_kernel_pct`` on traces written by hand: the fused kernel's
instructions in and out of the ``attention`` scope, the other instructions
of that scope, nesting inside a loop, and traces with no kernel."""
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import scopes as S  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.cell import metric_reader  # noqa: E402
from test_scopes import _run_record  # noqa: E402

STEP = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint"
SPLASH = "cond/branch_0_fun/vmap(jit(_splash_attention))"
OP_NAMES = {
    "while.1": "jit(train_step)/jvp()/while",
    # the kernel inside the attention scope: forward, recompute, backward
    "splash_mha_fwd_residuals.2": "jit(train_step)/jvp()/while/body/closed_call/attention/"
    f"{SPLASH}/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call",
    "splash_mha_fwd_residuals.3": f"{STEP}/rematted_computation/attention/{SPLASH}/"
    "splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call",
    "splash_mha_dkv_no_residuals.1": f"{STEP}/attention/{SPLASH}/"
    "splash_mha_dkv_no_residuals/splash_mha_dkv_no_residuals/pallas_call",
    # the rest of the attention scope: QKV, and the reduction of the kernel's dq
    "fusion.qkv": f"{STEP}/rematted_computation/attention/dot_general",
    "fusion.dq": f"{STEP}/attention/{SPLASH}/reduce_sum",
    # a kernel-named instruction outside the attention scope, and the MLP
    "splash_mha_fwd_residuals.9": "jit(other)/splash_mha_fwd_residuals/pallas_call",
    "fusion.mlp": f"{STEP}/mlp/dot_general",
}


def traced(op_names=OP_NAMES) -> S.Scoped:
    """One device, window [0, 1000]: a ``while`` holding the forward kernel
    (100) and a QKV fusion (50); then the recompute kernel (60), the
    backward kernel (90), the dq reduction (20), the outside kernel (40),
    the MLP (30) and an unnamed op (10)."""
    return S.Scoped(
        device_ops={0: [("while.1", 0, 200), ("splash_mha_fwd_residuals.2", 10, 110),
                        ("fusion.qkv", 120, 170),
                        ("splash_mha_fwd_residuals.3", 200, 260),
                        ("splash_mha_dkv_no_residuals.1", 260, 350),
                        ("fusion.dq", 350, 370), ("splash_mha_fwd_residuals.9", 400, 440),
                        ("fusion.mlp", 500, 530), ("fusion.x", 600, 610)]},
        op_names=dict(op_names),
        host_spans=[(T.WINDOW_SPAN, 0, 1000), ("train", 0, 999)],
    )


def test_kernel_names():
    for name in ("splash_mha_fwd_residuals", "splash_mha_dq_no_residuals",
                 "splash_mqa_dkv_segmented_no_residuals", "transpose(splash_mha_fwd_residuals)"):
        assert S.scope_of(f"jit(s)/attention/{name}/pallas_call")[0] == "attention"
        assert metric_reader("attention_kernel_pct").is_kernel(f"jit(s)/attention/{name}/x")
    for op_name in ("jit(s)/attention/dot_general", "jit(s)/attention/splash_helper/add",
                    "jit(s)/attention/jit(_splash_attention)/reduce_sum", None, ""):
        assert not metric_reader("attention_kernel_pct").is_kernel(op_name)


def test_share_of_the_attention_scope():
    pct = metric_reader("attention_kernel_pct").kernel_pct(traced())
    # kernel 100 + 60 + 90 of the scope's 100 + 50 + 60 + 90 + 20; the while's
    # own 50 and the kernel outside the scope count in neither
    assert math.isclose(pct, 100 * 250 / 320)
    red = S.reduce(traced())
    assert math.isclose(1e9 * S.scope_s(red, "attention"), 320)


def test_silent_without_a_kernel_in_the_attention_scope():
    reader = metric_reader("attention_kernel_pct")
    # a program whose attention runs as scans: no kernel-named instruction
    blockwise = {n: op for n, op in OP_NAMES.items() if not n.startswith("splash_")}
    assert reader.kernel_pct(traced(blockwise)) is None
    # a kernel-named instruction only outside the scope
    outside = {**blockwise, "splash_mha_fwd_residuals.9": OP_NAMES["splash_mha_fwd_residuals.9"]}
    assert reader.kernel_pct(traced(outside)) is None
    # no scopes at all
    assert reader.kernel_pct(traced({})) is None


def test_read_end_to_end(tmp_path, monkeypatch, capsys):
    import chipbench.harness as H

    monkeypatch.setattr(H, "OUT", tmp_path)
    reader = metric_reader("attention_kernel_pct")
    assert math.isclose(reader.read(_run_record(tmp_path, traced(), steps=2)), 100 * 250 / 320)
    # the kernel's own time is logged beside the share: 250 of 320 ns over 2 steps
    assert "attention kernel 0.000 ms per step of the scope's 0.000" in capsys.readouterr().err
    kernel, scope = reader.split(traced(), S.reduce(traced()))
    assert math.isclose(1e9 * kernel, 250) and math.isclose(1e9 * scope, 320)


@pytest.mark.parametrize("case", ["no_kernel", "not_this_run", "no_trace"])
def test_read_silent(tmp_path, monkeypatch, case):
    import chipbench.harness as H

    monkeypatch.setattr(H, "OUT", tmp_path)
    reader = metric_reader("attention_kernel_pct")
    if case == "no_kernel":
        blockwise = {n: op for n, op in OP_NAMES.items() if not n.startswith("splash_")}
        run = _run_record(tmp_path, traced(blockwise))
    else:
        run = _run_record(tmp_path, traced())
    if case == "not_this_run":
        run = type(run)(**{**run.__dict__, "device": dict(run.device,
                                                          busy_s=2 * run.device["busy_s"])})
    if case == "no_trace":
        monkeypatch.setattr(H, "OUT", tmp_path / "nothing")
    assert reader.read(run) is None


def test_declared():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "attention_kernel_pct")
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"],
            entry["workloads"]) == ("%", "higher", "device_trace", "attention",
                                    "train_tokens_per_s", ["smollm135m-s2048-b16"])
