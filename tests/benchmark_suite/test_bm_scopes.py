"""The step-by-scope readers (PR 36) on a small synthetic trace whose op line
carries ``rt_scope="<path>"`` in each event's name, as a v5e prints the
frontend attribute ``tracing.device_scope`` puts on an op: a scope's reader
sees the scope and everything inside it and no other scope's name, the six
top-level readers partition the busy time, every cell declares its own, and
a traced rehearsal reports them (0 on a CPU trace, whose names carry no HLO
text)."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import layer_metrics, result, trace_reduce
from benchmark.manifest import HERE, ROOT, Manifest
import xplane_writer

DENSE, FSDP, ROUTED, HYBRID, SPARSE = (
    "internlm2-1.8b.train-4k", "mistral-7b-v0.3.train-4k-fsdp4", "olmoe-1b-7b-0125.train-4k-moe",
    "qwen3-next-80b-a3b.train-8k-hybrid", "dots3-note-prev.train-8k-sparse")
# scope -> (its layer, the cells that report it), in the order declared
TOP = ("attn", "mlp", "embed", "lm_head_loss", "stack", "unscoped")
SCOPES = {
    **{s: ("models/llama step", [DENSE, FSDP, ROUTED, HYBRID, SPARSE]) for s in TOP},
    **{s: ("models/moe", [ROUTED, HYBRID, SPARSE])
       for s in ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")},
    "moe_shared": ("models/moe", [HYBRID, SPARSE]),
    **{s: ("models/gdn", [HYBRID]) for s in ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_out")},
    **{s: ("models/mla", [SPARSE])
       for s in ("mla_q", "mla_kv", "dsa_index", "dsa_select", "dsa_loss")},
}
METRICS = {s: f"scope.{s}_share.train" for s in SCOPES}


def reader(scope):
    with open(os.path.join(HERE, "layer_metrics", METRICS[scope] + ".json")) as f:
        return json.load(f)


def op(i, path, kernel=False):
    """An event name as a v5e prints one: a fusion, or a Mosaic call whose
    attribute stands beside ``kernel_metadata``; bare where ``path`` is ""."""
    if kernel:
        return (f"%flash_fwd.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p.{i}), "
                'custom_call_target="tpu_custom_call", '
                f'frontend_attributes={{kernel_metadata={{}},rt_scope="{path}"}}')
    return (f"%fusion.{i} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p.{i}), kind=kLoop, calls=%fc.{i}"
            + (f', frontend_attributes={{rt_scope="{path}"}}' if path else ""))


# path -> ms of self time: 20 ms busy in a 25 ms window
TABLE = {"stack/attn": 3.0, "stack/attn/mla_q": 1.0, "stack/attn/attn_gate": 0.5,
         "stack/attn/gdn_scan": 1.5, "stack/mlp": 2.0, "stack/mlp/moe_experts": 4.0,
         "stack/mlp/moe_shared": 1.0, "stack": 2.0, "embed": 0.5, "lm_head_loss": 2.5, "": 2.0}


@pytest.fixture(scope="module")
def obs(tmp_path_factory):
    events, at = [], 1.0
    for i, (path, ms) in enumerate(TABLE.items()):
        events.append((op(i, path, kernel=path == "stack/attn"), at, ms))
        at += ms
    path = xplane_writer.write(
        str(tmp_path_factory.mktemp("trace") / "s.xplane.pb"),
        {"/device:TPU:0": {"XLA Ops": events, "XLA Modules": [("jit_train_step(1)", 1.0, 20.0)]},
         "/host:CPU": {"main/1": [("benchmark_capture", 0.0, 25.0)]}})
    return {"trace": trace_reduce.reduce_trace(path, trace_reduce.load_profile("tpu"))}


def test_the_top_level_readers_partition_the_busy_time(obs):
    assert obs["trace"]["busy_s"] == pytest.approx(0.020)
    values = layer_metrics.read_all({METRICS[s]: reader(s) for s in SCOPES}, obs)
    share = lambda *paths: 100 * sum(TABLE[p] for p in paths) / 20.0  # noqa: E731
    assert values[METRICS["attn"]] == pytest.approx(share(
        "stack/attn", "stack/attn/mla_q", "stack/attn/attn_gate", "stack/attn/gdn_scan"))
    assert values[METRICS["mlp"]] == pytest.approx(share(
        "stack/mlp", "stack/mlp/moe_experts", "stack/mlp/moe_shared"))
    assert values[METRICS["stack"]] == pytest.approx(share("stack"))  # directly under it
    assert values[METRICS["unscoped"]] == pytest.approx(share(""))
    assert values[METRICS["mla_q"]] == pytest.approx(share("stack/attn/mla_q"))
    assert values[METRICS["moe_experts"]] == pytest.approx(share("stack/mlp/moe_experts"))
    assert values[METRICS["gdn_scan"]] == pytest.approx(share("stack/attn/gdn_scan"))
    assert values[METRICS["gdn_conv"]] == 0.0
    assert sum(values[METRICS[s]] for s in TOP) == pytest.approx(100.0)


@pytest.mark.parametrize("scope", list(SCOPES))
def test_a_scopes_reader_matches_its_own_names_and_no_other_scopes(scope):
    r = reader(scope)
    assert (r["kind"], r["params"]["of"]) == ("trace_share", "busy_s")
    pattern = r["params"]["pattern"]
    hits = lambda names: trace_reduce.matching(  # noqa: E731
        {n: [1.0, 1] for n in names}, pattern)[1]
    # its `what` quotes an event name of the real trace, which its pattern matches
    assert hits([r["what"].split("(PR 36): ")[1]]) == 1
    if scope == "unscoped":
        assert hits([op(0, ""), op(1, "stack"), op(2, "stack/attn", kernel=True)]) == 1
        return
    if scope == "stack":
        own, others = ["stack"], ["stack/attn", "stack/mlp/moe_route", "stack_x", "a/stack"]
    else:
        own = [scope, f"stack/{scope}", f"{scope}/inner", f"stack/attn/{scope}/inner"]
        others = [f"stack/{scope}_gate", f"stack/x_{scope}", f"{scope}x/inner", f"stack/x{scope}",
                  *(f"stack/{s}" for s in SCOPES if s not in (scope, "unscoped"))]
    assert hits([op(i, p, kernel=i % 2) for i, p in enumerate(own)]) == len(own)
    assert hits([op(i, p, kernel=i % 2) for i, p in enumerate(others)]) == 0
    assert hits([op(0, "")]) == 0
    # an op that only MENTIONS the scope (an operand's name) is not in it
    assert hits([f"%fusion.9 = bf16[8]{{0}} fusion(bf16[8]{{0}} %{scope}.1), kind=kLoop"]) == 0


def test_the_new_metrics_are_appended_for_their_cells_and_the_manifest_is_sound():
    manifest = Manifest()
    assert manifest.problems() == []
    rows = manifest.doc["per_layer"]
    assert [m["name"] for m in rows[-len(SCOPES):]] == list(METRICS.values())
    for m, (scope, (layer, cells)) in zip(rows[-len(SCOPES):], SCOPES.items()):
        assert (m["layer"], m["workloads"]) == (layer, cells), scope
        assert (m["source"], m["moves"], m["unit"], m["better"]) == (
            "device_trace", "train_tok_s_chip", "%", "lower")
        assert re.compile(reader(scope)["params"]["pattern"])


def mine(cell):
    return {METRICS[s] for s, (_, cells) in SCOPES.items() if cell in cells}


@pytest.mark.parametrize("cell", [DENSE, FSDP, ROUTED, HYBRID, SPARSE])
def test_a_cell_declares_its_own_scope_metrics_in_the_traced_run_only(cell):
    declared = Manifest().cell(cell)
    assert mine(cell) == {m for m in declared.declared(True) if m.startswith("scope.")}
    assert not [m for m in declared.declared(False) if m.startswith("scope.")]


# the hybrid and the sparse cell's traced rehearsals (two minutes each on the
# CPU) are test_bm_rehearsal.py's, which holds every cell's reported metrics
# to the declared ones; the readers run here are the same code on the same names
@pytest.mark.parametrize("cell", [DENSE, FSDP, ROUTED])
def test_the_traced_rehearsal_reports_the_cells_scope_metrics(cell):
    """A CPU trace names an op by its instruction's name alone: every scope
    reads 0 and ``unscoped`` holds all there is."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed",
         str(2**31 + 36), "--seconds", "5", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, text=True, timeout=420,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert out.returncode == 0, out.stdout[-3000:]
    obj = json.loads(out.stdout.rstrip("\n").rsplit("\n", 1)[-1])
    declared = Manifest().cell(cell).declared(True)
    assert result.check(obj, declared, trace=True, chips=None, platform="cpu") == []
    assert mine(cell) <= set(obj["metrics"])
    values = {m: obj["metrics"][m]["value"] for m in mine(cell)}
    assert values.pop(METRICS["unscoped"]) > 50.0  # busy is a union, self times a sum
    assert set(values.values()) == {0.0}
