"""Per-layer device self time and the train loop's host time, on traces
written by hand: self time of nested ops, the scope and pass of an
``op_name``, idle gaps named by the train loop's spans, the ``op_name`` of
each instruction read from the HLO a trace's metadata plane carries, and
the six metric readers end to end on a serialized trace."""
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import scopes as S  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.cell import metric_reader  # noqa: E402
from chipbench.harness import RunRecord  # noqa: E402
from test_trace_reduce import fixture_trace  # noqa: E402

NEW_METRICS = ("attention_ms_per_step", "mlp_ms_per_step", "xent_ms_per_step",
               "optimizer_ms_per_step", "unscoped_ms_per_step", "host_loop_ms")

FWD = "jit(train_step)/jvp()/while/body/closed_call/attention/dot_general"
BWD = ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
       "dot_general")
REMAT = ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/attention/tanh")
OP_NAMES = {
    "while.1": "jit(train_step)/jvp()/while",
    "fusion.a": FWD,
    "fusion.b": BWD,
    "fusion.r": REMAT,
    "fusion.o": "jit(train_step)/optimizer/sub",
}


def nested() -> S.Scoped:
    """One device: a ``while`` holding an attention fusion, an mlp fusion and
    an unnamed one; a recompute fusion beside it; an optimizer op after an
    idle gap; the window [50, 500]."""
    return S.Scoped(
        device_ops={0: [("while.1", 100, 400), ("fusion.a", 120, 200),
                        ("fusion.b", 220, 300), ("fusion.c", 300, 350),
                        ("fusion.r", 400, 420), ("fusion.o", 450, 480)]},
        op_names=dict(OP_NAMES),
        host_spans=[(T.WINDOW_SPAN, 50, 500), ("train", 60, 499),
                    ("train.next_batch", 60, 90), ("train.sync", 90, 430),
                    ("next_batch", 62, 88), ("wait", 410, 440),
                    ("train.record", 440, 499), ("train.next_batch", 0, 40)],
    )


def test_scope_and_pass_of_an_op_name():
    assert S.scope_of(FWD) == ("attention", "forward")
    assert S.scope_of(BWD) == ("mlp", "backward")
    assert S.scope_of(REMAT) == ("attention", "recompute")
    assert S.scope_of("jit(train_step)/transpose(jvp(xent))/while/body/add") == \
        ("xent", "backward")
    assert S.scope_of("jit(train_step)/jvp(embed)/jit(_take)/lt") == ("embed", "forward")
    # the innermost scope wins; a name that only contains a scope's is none
    assert S.scope_of("jit(s)/fsdp_gather/x/mlp/add")[0] == "mlp"
    assert S.scope_of("jit(s)/jvp()/blockwise_attention/mlp_apply/add")[0] is None
    assert S.scope_of(None) == (None, "forward")


def test_self_times_of_nested_and_overlapping_events():
    # a while [0,100) holding [10,30) and [40,90), which holds [50,60)
    assert S.self_times([(0, 100), (10, 30), (40, 90), (50, 60)]) == [30, 20, 40, 10]
    # partial overlap: the later start is innermost from its start on
    assert S.self_times([(0, 10), (5, 15)]) == [5, 10]
    # the same start: the shorter is innermost
    assert S.self_times([(0, 10), (0, 4)]) == [6, 4]
    # disjoint events and an empty list
    assert S.self_times([(20, 30), (0, 5)]) == [10, 5]
    assert S.self_times([]) == []


def test_scope_self_times_add_up_to_busy():
    r = S.reduce(nested())
    # busy: [100,420] and [450,480]
    assert math.isclose(r["busy_s"], 350e-9)
    ns = {s: {k: round(v * 1e9) for k, v in p.items()} for s, p in r["scopes"].items()}
    assert ns["attention"] == {"forward": 80, "recompute": 20, "backward": 0}
    assert ns["mlp"] == {"forward": 0, "recompute": 0, "backward": 80}
    assert ns["optimizer"] == {"forward": 30, "recompute": 0, "backward": 0}
    assert ns["xent"] == ns["embed"] == ns["fsdp_gather"] == dict.fromkeys(S.PASSES, 0)
    # the while's own 90 and the unnamed fusion's 50
    assert round(r["unscoped_s"] * 1e9) == 140
    assert [(n, round(t * 1e9)) for n, t in r["unscoped_top"]] == \
        [("while.1", 90), ("fusion.c", 50)]
    scoped = sum(S.scope_s(r, s) for s in S.SCOPES)
    assert math.isclose(scoped + r["unscoped_s"], r["busy_s"])


def test_loop_spans_and_steps_in_the_window():
    r = S.reduce(nested())
    # the train.next_batch at [0,40] is before the window
    assert {n: round(t * 1e9) for n, t in r["loop_s"].items()} == {
        "train.next_batch": 30, "train.sync": 340, "train.record": 59,
        "train.checkpoint": 0}
    assert r["steps"] == 1


def test_idle_gaps_named_by_the_innermost_loop_span():
    r = S.reduce(nested())
    gaps = [(n, round(t * 1e9)) for n, t in r["idle_gaps"]]
    # [50,100]: the harness's next_batch overlaps it most and keeps its
    # label; [420,450]: "wait" overlaps it; [480,500]: no harness span, and
    # train.record is the shorter of the two loop spans around it
    assert gaps == [("next_batch", 50), ("wait", 30), ("train.record", 20)]
    assert S.label((495, 500), [("train", 0, 499)]) == "train"
    assert S.label((600, 700), [("train", 0, 499)]) == "host"


def test_existing_reduction_unchanged_and_agreed_with():
    tr = fixture_trace()
    assert T.reduce(tr) == {
        "window_s": 450e-9, "busy_s": 275e-9, "collective_s": 80e-9,
        "exposed_collective_s": 70e-9, "devices": 2,
        "device_ops": [["fusion.1", 150e-9], ["fusion.0", 35e-9], ["fusion.2", 30e-9],
                       ["collective-permute-done.1", 25e-9], ["all-gather.3", 25e-9],
                       ["reduce-scatter.2", 25e-9], ["collective-permute-start.1", 5e-9]],
        "idle_gaps": [["wait", 150e-9], ["next_batch", 100e-9], ["wait", 50e-9],
                      ["host", 50e-9]],
    }
    mine = S.reduce(S.Scoped(device_ops=tr.device_ops, host_spans=tr.host_spans))
    assert math.isclose(mine["busy_s"], T.reduce(tr)["busy_s"])
    assert math.isclose(mine["unscoped_s"], mine["busy_s"])
    # the harness's labels are kept where a harness span overlaps the gap
    assert mine["idle_gaps"] == T.reduce(tr)["idle_gaps"]


# ------------------------------------------------------------ HLO and traces


def _field(num: int, value) -> bytes:
    def varint(x):
        out = bytearray()
        while True:
            out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
            x >>= 7
            if not x:
                return bytes(out)
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(value)) + value


def hlo_proto(module: str, op_names: dict[str, str]) -> bytes:
    """An ``HloProto`` holding one module of one computation."""
    comp = _field(1, "main") + b"".join(
        _field(2, _field(1, name) + _field(2, "fusion") + _field(7, _field(2, op)))
        for name, op in op_names.items())
    return _field(1, _field(1, module) + _field(3, comp))


def test_module_op_names_match_the_compiled_text():
    def f(x):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("mlp"):
            return jnp.sum(jax.nn.relu(y @ x))

    compiled = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8))).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    ops = S.module_op_names(module.as_serialized_hlo_module_proto())
    text = dict(re.findall(r'%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', compiled.as_text()))
    assert text and all(ops[k] == v for k, v in text.items())
    assert {S.scope_of(v)[0] for v in ops.values()} >= {"attention", "mlp"}


def test_modules_from_a_cpu_profiler_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("xent"):
            return jnp.sum(jnp.exp(x))

    x = jnp.ones((4,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    with open(S.newest(tmp_path), "rb") as fh:
        modules = S.metadata_hlo_modules(fh.read())
    # named as the XLA Modules line names them: the module and its program id
    name = next(n for n in modules if n.startswith("jit_f("))
    assert any(S.scope_of(op)[0] == "xent" for op in S.module_op_names(modules[name]).values())


def _esc(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


def xspace(ops, spans, module_ops, module="jit_train_step") -> bytes:
    """A serialized ``XSpace`` as a TPU run writes it: one device's ``XLA Ops``
    line (event names as HLO text) and ``XLA Modules`` line (the module that
    ran, and a short one that did not), host spans, and both modules' HLO in
    the metadata plane."""
    from jax.profiler import ProfileData

    other = hlo_proto("jit_other", {"fusion.a": "jit(other)/mlp/add"})
    names = sorted({op for op, _, _ in ops})
    meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "%{n} = f32[] fusion()" }} }}\n' for i, n in enumerate(names))
    events = "".join(
        f"events {{ metadata_id: {names.index(op) + 1} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}\n" for op, s, e in ops)
    span_names = sorted({n for n, _, _ in spans})
    host_meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}\n'
                        for i, n in enumerate(span_names))
    host_events = "".join(
        f"events {{ metadata_id: {span_names.index(n) + 1} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in spans)
    first, last = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{events}  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 100 offset_ps: {first * 1000} duration_ps: {(last - first) * 1000} }}
    events {{ metadata_id: 101 offset_ps: 0 duration_ps: 1000 }}
  }}
{meta}  event_metadata {{ key: 100 value {{ id: 100 name: "{module}(1)" }} }}
  event_metadata {{ key: 101 value {{ id: 101 name: "jit_other(2)" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host_events}  }}
{host_meta}}}
planes {{ id: 3 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "{module}(1)"
    stats {{ metadata_id: 1 bytes_value: "{_esc(hlo_proto(module, module_ops))}" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_other(2)"
    stats {{ metadata_id: 1 bytes_value: "{_esc(other)}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
"""
    return ProfileData.text_proto_to_serialized_xspace(text)


def write_trace(directory, tr: S.Scoped) -> None:
    ops = [op for evs in tr.device_ops.values() for op in evs]
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, "host.xplane.pb"), "wb") as f:
        f.write(xspace(ops, tr.host_spans, tr.op_names))


def test_load_a_serialized_tpu_trace(tmp_path):
    write_trace(tmp_path, nested())
    tr = S.load(tmp_path)
    want = nested()
    assert tr.op_names == want.op_names
    assert sorted(tr.device_ops[0]) == sorted(want.device_ops[0])
    assert sorted(tr.host_spans) == sorted(want.host_spans)
    assert S.reduce(tr) == S.reduce(want)


def _run_record(tmp_path, tr: S.Scoped, steps: int = 1) -> RunRecord:
    trace_dir = tmp_path / "trace" / "cell" / "plugins" / "profile" / "1"
    write_trace(trace_dir, tr)
    return RunRecord(steps=steps, tokens_per_step=1, window_s=1.0, chips=1,
                     useful_flops_per_token=1.0, peak={"bf16_flops": 1.0}, spans={},
                     device=T.reduce(T.load(str(tmp_path / "trace" / "cell"))))


def test_new_metrics_end_to_end(tmp_path, monkeypatch):
    import chipbench.harness as H

    monkeypatch.setattr(H, "OUT", tmp_path)
    run = _run_record(tmp_path, nested(), steps=2)
    read = {n: metric_reader(n).read(run) for n in NEW_METRICS}
    ms = 1e-6 / 2  # ns over 2 steps, in ms
    assert math.isclose(read["attention_ms_per_step"], 100 * ms)
    assert math.isclose(read["mlp_ms_per_step"], 80 * ms)
    assert read["xent_ms_per_step"] is None  # no op in that scope
    assert math.isclose(read["optimizer_ms_per_step"], 30 * ms)
    assert math.isclose(read["unscoped_ms_per_step"], 140 * ms)
    assert math.isclose(read["host_loop_ms"], (30 + 59) * ms)
    # the scopes and the unscoped rest add up to the harness's busy time
    total = sum(v for n, v in read.items() if n.endswith("_ms_per_step") and v)
    assert math.isclose(total, 1e3 * run.device["busy_s"] / run.steps)


def test_new_metrics_silent_on_a_program_without_scopes_or_spans(tmp_path, monkeypatch):
    import chipbench.harness as H

    monkeypatch.setattr(H, "OUT", tmp_path)
    tr = nested()
    tr.op_names = {}
    tr.host_spans = [sp for sp in tr.host_spans if not sp[0].startswith("train")]
    run = _run_record(tmp_path, tr)
    assert {n: metric_reader(n).read(run) for n in NEW_METRICS} == dict.fromkeys(NEW_METRICS)


def test_new_metrics_silent_without_this_runs_trace(tmp_path, monkeypatch):
    import chipbench.harness as H

    monkeypatch.setattr(H, "OUT", tmp_path)
    run = _run_record(tmp_path, nested())
    # a run whose busy time is not the newest trace's reads nothing from it
    doubled = dict(run.device, busy_s=2 * run.device["busy_s"])
    other = RunRecord(**{**run.__dict__, "device": doubled})
    assert {n: metric_reader(n).read(other) for n in NEW_METRICS} == dict.fromkeys(NEW_METRICS)
    monkeypatch.setattr(H, "OUT", tmp_path / "nothing")
    assert {n: metric_reader(n).read(run) for n in NEW_METRICS} == dict.fromkeys(NEW_METRICS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_declared(name):
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]) == \
        ("ms", "lower", "device_trace", "train_tokens_per_s")
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics", f"{name}.py"))
