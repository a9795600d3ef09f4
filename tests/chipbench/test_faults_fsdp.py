"""A run of the FSDP configuration (yi-9b-l8, ``mcast`` gathers over
data=4) cut to a size the CPU holds, on four fake CPU devices in a child
process: sound, it comes out correct; with the gathers exchanging nothing,
it does not. The limits are the one-chip cell's."""
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FSDP = """
import sys
sys.path[:0] = [{root!r}, {here!r}]
import jax
import dataclasses, json
from test_faults import TINY, run
from chipbench import cell as C
from chipbench.faults import planted
base = C.load("smollm135m-s2048-b16")
config = json.loads((C.HERE / "configs" / "yi-9b-l8.json").read_text())
traffic = json.loads((C.HERE / "traffic" / "s2048-b8-mcast.json").read_text())
cell = dataclasses.replace(
    base, name="fsdp-tiny", chips=4, config=dict(config, **TINY),
    traffic=dict(traffic, global_batch=8, seq_len=64, ref_rows=4))
devices = jax.devices()[:4]
sound = run(cell, devices)
with planted("no_exchange"):
    broken = run(cell, devices)
print("RESULT", sound["correct"], broken["correct"], sound["checks"], broken["checks"])
"""


def test_fsdp_sound_and_no_exchange(multidev):
    out = multidev(FSDP.format(root=ROOT, here=os.path.dirname(os.path.abspath(__file__))),
                   n_devices=4)
    line = next(x for x in out.splitlines() if x.startswith("RESULT"))
    _, sound, broken, *_ = line.split(" ", 3)
    assert sound == "True", line
    assert broken == "False", line
