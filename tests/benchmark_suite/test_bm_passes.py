"""The step-by-pass readers (PR 50) on a small synthetic trace whose op line
carries ``rt_pass="fwd" | "remat" | "bwd"`` beside ``rt_scope`` in each event's
name, as a v5e prints the frontend attributes ``tracing.with_passes`` and
``tracing.device_scope`` put on an op, in both attribute orders: the four
top-level readers partition the busy time, the two crossed ones see their
scope under ``remat`` alone, the eight older cells declare the six and the
ninth none, and ``profile.summarize`` tables the same capture by pass."""

import json
import os
import re

import pytest

from benchmark import layer_metrics, trace_reduce
from benchmark.manifest import HERE, Manifest
from ray_tpu.observability import profile
import xplane_writer

CELLS = ["internlm2-1.8b.train-4k", "mistral-7b-v0.3.train-4k-fsdp4",
         "olmoe-1b-7b-0125.train-4k-moe", "qwen3-next-80b-a3b.train-8k-hybrid",
         "dots3-note-prev.train-8k-sparse", "laguna-s-2.1.train-16k-swa",
         "kimi-k2-instruct.train-mla-full", "smallthinker-21ba3b-instruct.train-16k-win4k"]
NINTH = "minicpm-sala.train-16k-sala"
TOP = ("fwd", "remat", "bwd", "none")
METRICS = {**{p: f"pass.{p}_share.train" for p in TOP},
           "remat_attn": "pass.remat_attn_share.train",
           "remat_mlp": "pass.remat_mlp_share.train"}


def reader(key):
    with open(os.path.join(HERE, "layer_metrics", METRICS[key] + ".json")) as f:
        return json.load(f)


def op(i, which, path="", kernel=False, scope_first=False):
    """An event name as a v5e prints one: a fusion, or a Mosaic call whose
    attributes stand beside ``kernel_metadata``; bare where both are ""."""
    attrs = [f'rt_pass="{which}"'] if which else []
    if path:
        attrs.insert(0 if scope_first else len(attrs), f'rt_scope="{path}"')
    if kernel:
        return (f"%flash_fwd.{i} = bf16[8]{{0}} custom-call(bf16[8]{{0}} %p.{i}), "
                'custom_call_target="tpu_custom_call", '
                f'frontend_attributes={{{",".join(["kernel_metadata={}", *attrs])}}}')
    return (f"%fusion.{i} = bf16[8]{{0}} fusion(bf16[8]{{0}} %p.{i}), kind=kLoop, calls=%fc.{i}"
            + (f', frontend_attributes={{{",".join(attrs)}}}' if attrs else ""))


# (pass, path, scope printed first) -> ms of self time: 20 ms busy in a 25 ms window
TABLE = {("fwd", "stack/attn", False): 3.0, ("fwd", "lm_head_loss", True): 2.0,
         ("fwd", "", False): 0.5, ("remat", "stack/attn/mla_q", False): 1.0,
         ("remat", "stack/mlp/moe_experts", True): 1.5, ("remat", "stack/mlp", False): 0.5,
         ("remat", "stack", False): 0.5, ("bwd", "stack/attn", True): 4.0,
         ("bwd", "stack/mlp", False): 3.0, ("bwd", "", False): 1.0, ("bwd", "embed", False): 0.5,
         ("", "", False): 2.0, ("", "stack/attn_gate", False): 0.5}


def share(*keys):
    return 100 * sum(ms for key, ms in TABLE.items() if key[:2] in keys or key[0] in keys) / 20.0


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One synthetic capture that both reducers read: the benchmark's window
    event and the operator's (``profile.WINDOW``) cover the same 25 ms."""
    events, at = [], 1.0
    for i, ((which, path, scope_first), ms) in enumerate(TABLE.items()):
        events.append((op(i, which, path, kernel=(which, path) == ("fwd", "stack/attn"),
                          scope_first=scope_first), at, ms))
        at += ms
    return xplane_writer.write(
        str(tmp_path_factory.mktemp("trace") / "s.xplane.pb"),
        {"/device:TPU:0": {"XLA Ops": events, "XLA Modules": [("jit_train_step(1)", 1.0, 20.0)]},
         "/host:CPU": {"main/1": [("benchmark_capture", 0.0, 25.0),
                                  (profile.WINDOW, 0.0, 25.0, {"wall_s": 1.0, "mono_s": 1.0})]}})


def test_the_four_pass_readers_partition_the_busy_time_and_the_crossed_ones_see_remat_alone(
        capture):
    obs = {"trace": trace_reduce.reduce_trace(capture, trace_reduce.load_profile("tpu"))}
    assert obs["trace"]["busy_s"] == pytest.approx(0.020)
    values = layer_metrics.read_all({METRICS[k]: reader(k) for k in METRICS}, obs)
    for which in ("fwd", "remat", "bwd"):
        assert values[METRICS[which]] == pytest.approx(share(which))
    assert values[METRICS["none"]] == pytest.approx(share(""))
    assert sum(values[METRICS[k]] for k in TOP) == pytest.approx(100.0)
    assert values[METRICS["remat_attn"]] == pytest.approx(share(("remat", "stack/attn/mla_q")))
    assert values[METRICS["remat_mlp"]] == pytest.approx(share(
        ("remat", "stack/mlp/moe_experts"), ("remat", "stack/mlp")))
    assert (values[METRICS["remat_attn"]] + values[METRICS["remat_mlp"]]
            <= values[METRICS["remat"]])  # ``stack`` alone is in neither


@pytest.mark.parametrize("key", list(METRICS))
def test_a_readers_pattern_matches_the_event_it_quotes_and_no_other_pass(key):
    r = reader(key)
    assert (r["kind"], r["params"]["of"], r["layer"]) == (
        "trace_share", "busy_s", "models/llama step")
    pattern = r["params"]["pattern"]
    hits = lambda names: trace_reduce.matching(  # noqa: E731
        {n: [1.0, 1] for n in names}, pattern)[1]
    # its `what` quotes an event name of this PR's chip run, which its pattern matches
    assert hits([r["what"].split("(PR 50): ")[1]]) == 1
    both = lambda which, path: [op(0, which, path), op(1, which, path, scope_first=True),  # noqa: E731
                                op(2, which, path, kernel=True),
                                op(3, which, path, kernel=True, scope_first=True)]
    if key == "none":
        assert hits([op(0, "", ""), op(1, "", "stack/attn_gate")]) == 2
        assert hits(both("fwd", "stack") + both("bwd", "")) == 0
        return
    if key in TOP:
        others = [p for p in ("fwd", "remat", "bwd") if p != key]
        assert hits(both(key, "stack/mlp") + [op(4, key, "")]) == 5
        assert hits([n for p in others for n in both(p, "stack/mlp")] + [op(5, "", "")]) == 0
    else:
        scope, other = ("attn", "mlp") if key == "remat_attn" else ("mlp", "attn")
        own = [f"stack/{scope}", scope, f"stack/{scope}/moe_experts"]
        assert hits([n for path in own for n in both("remat", path)]) == 4 * len(own)
        wrong = [f"stack/{other}", "stack", f"stack/{scope}_gate", f"stack/x{scope}", ""]
        assert hits([n for path in wrong for n in both("remat", path) if path]
                    + [op(9, "remat", "")]) == 0
        assert hits([n for p in ("fwd", "bwd") for n in both(p, f"stack/{scope}")]) == 0
    # an op that only MENTIONS the attribute (an operand's name) is not counted
    assert hits(["%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %rt_pass.1, bf16[8]{0} %remat.2), "
                 "kind=kLoop, calls=%fc.9"]) == 0


def test_the_six_are_appended_for_the_eight_older_cells_and_the_manifest_is_sound():
    manifest = Manifest()
    assert manifest.problems() == []
    rows = manifest.doc["per_layer"]
    assert [m["name"] for m in rows[-len(METRICS):]] == list(METRICS.values())
    for m in rows[-len(METRICS):]:
        assert m["workloads"] == CELLS
        assert (m["layer"], m["source"], m["moves"], m["unit"], m["better"]) == (
            "models/llama step", "device_trace", "train_tok_s_chip", "%", "lower")
        assert not m["name"].startswith("scope.")
    for key in METRICS:
        assert re.compile(reader(key)["params"]["pattern"])


@pytest.mark.parametrize("cell", CELLS + [NINTH])
def test_a_cell_declares_the_pass_metrics_in_the_traced_run_only(cell):
    declared = Manifest().cell(cell)
    mine = set() if cell == NINTH else set(METRICS.values())
    assert mine == {m for m in declared.declared(True) if m.startswith("pass.")}
    assert not [m for m in declared.declared(False) if m.startswith("pass.")]


def test_summarize_tables_the_same_capture_by_pass(capture):
    s = profile.summarize(capture)
    by_pass = {r[0]: r for r in s["passes"]["pass"]}
    assert set(by_pass) == {"fwd", "remat", "bwd", ""}
    for which in by_pass:  # [pass, self s, n, % of busy]
        assert by_pass[which][3] == pytest.approx(share(which))
        assert by_pass[which][1] * 1e3 == pytest.approx(share(which) * 20.0 / 100)
    assert sum(r[3] for r in s["passes"]["pass"]) == pytest.approx(100.0)
    first = {(r[0], r[1]): r for r in s["passes"]["pass_first"]}  # [pass, first scope, s, n, %]
    assert first["remat", "stack"][2] * 1e3 == pytest.approx(3.5)
    assert first["bwd", ""][2] * 1e3 == pytest.approx(1.0) and first["bwd", "embed"][3] == 1
    assert first["fwd", "lm_head_loss"][4] == pytest.approx(10.0)
    assert first["", "stack"][2] * 1e3 == pytest.approx(0.5)  # a scope and no pass
    assert sum(r[4] for r in s["passes"]["pass_first"]) == pytest.approx(100.0)
    text = profile.render(s)
    assert "device time by pass" in text and "(no pass)" in text
    assert re.search(r"remat\s+stack\s+0\.00350", text)


def test_a_capture_whose_names_carry_no_pass_renders_no_pass_table():
    path = os.path.join(HERE, "testdata", "synthetic_tpu.xplane.pb")
    obs = {"trace": trace_reduce.reduce_trace(path, trace_reduce.load_profile("tpu"))}
    values = layer_metrics.read_all({METRICS[k]: reader(k) for k in METRICS}, obs)
    assert [values[METRICS[k]] for k in METRICS] == pytest.approx([0, 0, 0, 100.0, 0, 0])
