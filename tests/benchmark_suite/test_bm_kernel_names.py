"""The per-kernel readers (PR 24) on a small synthetic trace whose op line
carries the three flash kernels' event names as a v5e printed them, plus
one Mosaic call with no kernel name: the three readers are exclusive, and
they sum to ``kernel.custom_call_share.train`` less the unnamed call."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import layer_metrics, result, trace_reduce
from benchmark.manifest import HERE, ROOT, Manifest
import xplane_writer

TAIL = (', custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{bf16[2,16,4096,128]{3,2,1,0}, bf16[2,8,4096,128]{3,2,1,0}, '
        'bf16[2,8,4096,128]{3,2,1,0}}, frontend_attributes={kernel_metadata={}}')
# event names of the v5e trace of internlm2-1.8b.train-4k (PR 24), operands cut
FWD = ("%flash_fwd.18 = (bf16[2,16,4096,128]{3,2,1,0:T(8,128)(2,1)S(1)}, "
       "f32[2,16,4096,128]{3,2,1,0:T(8,128)}) custom-call(bf16[2,16,4096,128]"
       "{3,2,1,0:T(8,128)(2,1)S(1)} %custom-call.20)" + TAIL)
FWD_REMAT = FWD.replace("%flash_fwd.18", "%flash_fwd.17")
DQ = ("%flash_bwd_dq.11 = bf16[2,16,4096,128]{3,2,1,0:T(8,128)(2,1)} custom-call("
      "bf16[2,16,4096,128]{3,2,1,0:T(8,128)(2,1)} %dynamic-slice_bitcast_fusion.18)" + TAIL)
DKDV = ("%flash_bwd_dkdv.11 = (bf16[2,16,4096,128]{3,2,1,0:T(8,128)(2,1)}, "
        "bf16[2,16,4096,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(bf16[2,16,4096,128]"
        "{3,2,1,0:T(8,128)(2,1)} %dynamic-slice_bitcast_fusion.18)" + TAIL)
UNNAMED = "%checkpoint.23 = bf16[8]{0} custom-call(bf16[8]{0} %p.1)" + TAIL
# an op that only MENTIONS a kernel (its operand) is not that kernel
FUSION = "%fusion.331 = bf16[8]{0} fusion(bf16[8]{0} %flash_fwd.18), kind=kOutput, calls=%fc.1"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv")
METRICS = [f"kernel.{k}_share.train" for k in KERNELS]


def reader(metric):
    with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def obs(tmp_path_factory):
    """Device 0 busy 1-9 ms of a 0-10 ms window: forward 1 ms, recomputed
    forward 1 ms, dQ 1.5 ms, dK/dV 2 ms, the unnamed call 0.5 ms, a fusion 2 ms."""
    path = xplane_writer.write(
        str(tmp_path_factory.mktemp("trace") / "k.xplane.pb"),
        {"/device:TPU:0": {"XLA Ops": [(FWD, 1.0, 1.0), (FUSION, 2.0, 2.0),
                                       (FWD_REMAT, 4.0, 1.0), (DQ, 5.0, 1.5),
                                       (DKDV, 6.5, 2.0), (UNNAMED, 8.5, 0.5)],
                           "XLA Modules": [("jit_train_step(1)", 1.0, 8.0)]},
         "/host:CPU": {"main/1": [("benchmark_capture", 0.0, 10.0)]}})
    return {"trace": trace_reduce.reduce_trace(path, trace_reduce.load_profile("tpu"))}


@pytest.mark.parametrize("kernel,ms", zip(KERNELS, (2.0, 1.5, 2.0)))
def test_a_kernels_reader_sees_its_own_events_and_no_others(obs, kernel, ms):
    r = reader(f"kernel.{kernel}_share.train")
    assert obs["trace"]["busy_s"] == pytest.approx(0.008)
    assert layer_metrics.trace_share(obs, r["params"], "") == pytest.approx(100 * ms / 8.0)
    # its `what` quotes an event name of the real trace, which its pattern matches
    quoted = r["what"].split("(PR 24): ")[1]
    assert trace_reduce.matching({quoted: [1.0, 1]}, r["params"]["pattern"]) == (1.0, 1)
    others = [n for n in (FWD, DQ, DKDV, UNNAMED, FUSION) if not n.startswith(f"%{kernel}.")]
    assert trace_reduce.matching({n: [1.0, 1] for n in others}, r["params"]["pattern"])[1] == 0


def test_the_three_sum_to_the_custom_call_share_less_the_unnamed_call(obs):
    values = layer_metrics.read_all(
        {m: reader(m) for m in METRICS + ["kernel.custom_call_share.train"]}, obs)
    assert values["kernel.custom_call_share.train"] == pytest.approx(100 * 6.0 / 8.0)
    assert sum(values[m] for m in METRICS) == pytest.approx(
        values["kernel.custom_call_share.train"] - 100 * 0.5 / 8.0)


def test_the_new_metrics_are_declared_for_both_cells_and_the_manifest_is_sound():
    manifest = Manifest()
    assert manifest.problems() == []
    cells = [w["name"] for w in manifest.doc["workloads"]]
    for m in manifest.doc["per_layer"]:
        if m["name"] in METRICS:
            assert m["workloads"] == cells and m["layer"] == "ops/ kernels"
            assert (m["source"], m["moves"], m["unit"], m["better"]) == (
                "device_trace", "train_tok_s_chip", "%", "lower")
    # declared in this order and next to each other; a later PR appends its
    # own metrics after them (PR 26 did), so not "the last three"
    names = [m["name"] for m in manifest.doc["per_layer"]]
    first = names.index(METRICS[0])
    assert names[first:first + len(METRICS)] == METRICS


@pytest.mark.parametrize("cell", [w["name"] for w in Manifest().doc["workloads"]])
def test_the_traced_rehearsal_reports_the_new_metrics(cell):
    """The untraced mode and the rest of the line are test_bm_rehearsal's;
    here: a traced line that ``result.check`` accepts carries the three."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed",
         str(2**31 + 24), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, text=True, timeout=420,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert out.returncode == 0, out.stdout[-3000:]
    obj = json.loads(out.stdout.rstrip("\n").rsplit("\n", 1)[-1])
    declared = Manifest().cell(cell).declared(True)
    assert result.check(obj, declared, trace=True, chips=None, platform="cpu") == []
    assert set(METRICS) <= set(obj["metrics"]) and set(METRICS) <= set(declared)
