"""The copied FLOP arithmetic against counts made by hand, the files each
cell of BENCHMARK.json is found by, and the refusal to run without a TPU."""
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import cell as C  # noqa: E402
from chipbench import compare  # noqa: E402
from chipbench import model as M  # noqa: E402


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def dims(config_file):
    with open(os.path.join(ROOT, config_file)) as f:
        c = json.load(f)
    return M.arch(c["model_type"]), c


@pytest.mark.parametrize("config,seq,useful,executed_fwd", [
    # 30 layers x (7.08 M linear + causal attention) + 56.6 M head, times 3
    ("chipbench/configs/smollm-135m.json", 2048, 1_019_215_872, 410_517_504),
    ("chipbench/configs/smollm-135m.json", 512, 859_963_392, 304_349_184),
    ("chipbench/configs/yi-9b-l8.json", 2048, 10_280_239_104, 3_560_964_096),
])
def test_flops_per_token(config, seq, useful, executed_fwd):
    arch, c = dims(config)
    m = arch.Dims.from_config(c)
    assert math.isclose(arch.useful_flops_per_token(m, seq), useful, rel_tol=1e-12)
    assert math.isclose(arch.forward_flops_per_token(m, seq, causal_frac=1.0),
                        executed_fwd, rel_tol=1e-12)


def test_every_cell_finds_its_files():
    b = bench()
    metric_names = {m["name"] for m in b["per_layer"]}
    for w in b["workloads"]:
        cell = C.load(w["name"])
        assert cell.chips in (1, 4)
        assert {"train_tokens_per_s", "setup_s"} <= {m["name"] for m in cell.end_to_end}
        assert cell.per_layer, w["name"]
        assert set(cell.limits) >= set(compare.NUMBERS)
        for key in ("global_batch", "seq_len", "fsdp_mode", "check_steps", "ref_rows"):
            assert key in cell.traffic, (w["name"], key)
        M.arch(cell.config["model_type"])
    for name in metric_names:
        assert callable(C.metric_reader(name).read), name
    for m in b["per_layer"]:
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in b["workloads"]}, (m["name"], w)


def test_configs_state_their_cuts():
    for conf in bench()["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            c = json.load(f)
        assert sorted(c["reduced"]) == sorted(conf["reduced"]), conf["name"]
        assert c["source"].startswith(conf["source"]), conf["name"]
        for key, cut in c["reduced"].items():
            assert c[key] == cut["here"], (conf["name"], key)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload",
         "smollm135m-s2048-b16", "--seed", "3", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr
