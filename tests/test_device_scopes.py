"""``tracing.device_scope``: a path in each op's own text (``rt_scope``), under
scan, checkpoint and a ``custom_vjp`` rule alike, and the program otherwise
as it was. Each debug preset's step is lowered once with scopes (the module's
``step_texts``) and once without. A capture's table by scope, and the host's
spans: ``tests/test_tracing_profile.py``."""

import collections
import contextlib
import dataclasses
import functools
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.observability import tracing


# ------------------------------------------- the scopes are text in the op
def _stripped(text: str) -> str:
    """Compiled HLO text without names, metadata, ``rt_scope`` and ``rt_pass``
    (``tests/test_device_passes.py``): what is left is the program."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    ours = r'rt_(scope|pass)="[^"]*"'
    text = re.sub(rf", frontend_attributes=\{{{ours}(,{ours})?\}}", "", text)
    text = re.sub(rf"{ours},|,{ours}", "", text)  # beside another attribute
    # the tables of files, functions and stack frames that metadata points into
    text = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames)$|^\d+ .*$", "", text)
    text = re.sub(r"%[\w.\-]+", "%", text)
    text = re.sub(r"[A-Za-z_][\w.\-]*: ", "", text)  # a computation's parameters
    text = re.sub(r"(HloModule|ENTRY|calls=|to_apply=|body=|condition=)\s*\S+", r"\1", text)
    return "\n".join(line for line in text.splitlines() if line.strip())


# every scope a model opens, outermost first: an instruction's ``op_name``
# holds the open ones among jax's own (``jvp(stack)/while/body/.../attn/mul``)
SCOPES = ("stack", "embed", "lm_head_loss", "index_loss", "attn", "mlp", "moe_route",
          "moe_dispatch", "moe_experts", "moe_combine", "moe_shared", "gdn_proj", "gdn_conv",
          "gdn_scan", "gdn_out", "mla_q", "mla_kv", "dsa_index", "dsa_select", "dsa_loss",
          "attn_gate", "mla_out", "prefill_chunk", "decode_step")
# model kind -> (its debug preset, paths its step must hold, changes to the
# preset). The held range's backward runs its branch again inside a
# ``custom_vjp`` rule: its dispatch, experts and combine are under ``stack/mlp``
# there too. The hybrid is lowered at a hidden width of one lane tile, where the
# held range's adds are the kernel ``moe_rows`` (at the preset's own width, a
# gather and a scatter-add: ``tests/test_hybrid_model.py`` reads its one step)
KINDS = {
    "dense": ("debug-128", {"embed", "lm_head_loss", "stack", "stack/attn", "stack/mlp"}, {}),
    "routed": ("llama-moe-debug", {"stack/mlp/moe_route", "stack/mlp/moe_dispatch",
                                   "stack/mlp/moe_experts", "stack/mlp/moe_combine"}, {}),
    "hybrid": ("hybrid-debug", {"stack/attn/gdn_proj", "stack/attn/gdn_conv",
                                "stack/attn/gdn_scan", "stack/attn/gdn_out",
                                "stack/attn/attn_gate", "stack/mlp/moe_shared",
                                "stack/mlp/moe_experts"}, {"hidden": 128}),
    "sparse": ("latent-sparse-debug", {"stack/attn/mla_q", "stack/attn/mla_kv",
                                       "stack/attn/mla_out", "stack/attn/dsa_index",
                                       "stack/attn/dsa_select", "stack/attn/dsa_loss",
                                       "stack/attn/attn_gate", "stack/mlp/moe_shared"}, {}),
}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def _lower_loss(preset="debug-128", **changes):
    from ray_tpu.models.llama import PRESETS, init_params, loss_fn

    cfg = dataclasses.replace(PRESETS[preset], dtype=jnp.float32, remat_policy="attn", **changes)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    return jax.jit(jax.grad(lambda p, b: loss_fn(p, b, cfg, chunk_tokens=32))
                   ).lower(params, batch).compile().as_text()


def _lower_mixed():
    from ray_tpu.llm import model as llm_model
    from ray_tpu.models.llama import PRESETS, init_params

    cfg = PRESETS["debug-128"]
    params = init_params(cfg, jax.random.PRNGKey(0))
    page, slots, max_pages = 16, 2, 4
    pages = llm_model.init_pages(cfg, slots + slots * max_pages, page)
    op = (jnp.zeros(max_pages, jnp.int32), jnp.zeros(16, jnp.int32), jnp.int32(0))
    z = lambda dt=jnp.int32: jnp.zeros(slots, dt)  # noqa: E731
    return llm_model.mixed_dispatch.lower(
        params, pages, (op,), jnp.zeros((slots, max_pages), jnp.int32), z(), z(),
        z(jnp.float32), z() - 1, z() + 4, jax.random.PRNGKey(0), config=cfg,
        page_size=page, n_steps=2, live_pages=2, prefill_live_pages=(1,)
    ).compile().as_text()


def _without_scopes(monkeypatch):
    """The helper switched off at every module that opens a scope."""
    import ray_tpu.llm.model
    import ray_tpu.models.gdn
    import ray_tpu.models.llama
    import ray_tpu.models.mla
    import ray_tpu.models.moe

    for module in (ray_tpu.models.llama, ray_tpu.models.moe, ray_tpu.models.mla,
                   ray_tpu.models.gdn, ray_tpu.llm.model):
        monkeypatch.setattr(module, "device_scope", lambda name: contextlib.nullcontext())
    # and no pass (``tracing.with_passes``): what is lowered is the plain program
    monkeypatch.setattr(ray_tpu.models.llama, "with_passes", lambda fn, has_aux=False: fn)
    jax.clear_caches()


@pytest.fixture(scope="module")
def step_texts():
    """kind -> the compiled text of its tiny step, compiled once a module."""
    texts = {}

    def get(kind):
        if kind not in texts:
            texts[kind] = _lower_loss(KINDS[kind][0], **KINDS[kind][2])
        return texts[kind]

    return get


def _scoped_instructions(text):
    """(name, opcode, rt_scope or None, the model scopes in op_name in order)
    of the instructions that compute."""
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(2) in ("parameter", "constant", "get-tuple-element", "tuple",
                                   "bitcast"):
            continue
        scope = re.search(r'rt_scope="([^"]*)"', line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        named = [part for part in re.split(r"[/()]", op_name.group(1)) if part in SCOPES] \
            if op_name else []
        yield m.group(1), m.group(2), scope and scope.group(1), named


# ------------------------------------------------ and the pass beside it
PASSES = ("fwd", "remat", "bwd")
_ATTRS = re.compile(r'frontend_attributes=\{(.*)\}')
# A Pallas kernel's body is left alone: its eqns keep what they were traced
# under (a backward rule is traced under its CALL's metadata, ``fwd``). On a
# chip the kernel is ONE custom call under the pass of its ``pallas_call`` eqn
# (``tests/test_chip_compile_steps.py``); interpreted on a CPU the body's ops
# are in the program, named ``<scope>/<kernel>/while/body/...``.
_KERNEL_BODY = re.compile(
    r"/(flash_|moe_t?gmm|moe_rows|gdn_|attn_win_|attn_sel_|dsa_index_|dsa_probs_)\w*/(while|cond)")


def _attributes(text):
    """(line, rt_pass or None, rt_scope or None, op_name or "") of every
    instruction that carries a frontend attribute or an ``op_name``."""
    for line in text.splitlines():
        attrs = _ATTRS.search(line)
        which = re.search(r'rt_pass="([^"]*)"', attrs.group(1)) if attrs else None
        scope = re.search(r'rt_scope="([^"]*)"', attrs.group(1)) if attrs else None
        op_name = re.search(r'op_name="([^"]*)"', line)
        if attrs or op_name:
            yield (line, which and which.group(1), scope and scope.group(1),
                   op_name.group(1) if op_name else "")


def _pass_of(op_name: str) -> str:
    """What jax's own name stack says of an op's pass."""
    if "rematted_computation" in op_name:
        return "remat"
    return "bwd" if "transpose(" in op_name else "fwd"


def check_passes(text: str, remat: bool = True) -> None:
    """Every op of a compiled step that a scope issued carries a pass, and the
    pass is what jax's own ``op_name`` says of the op."""
    rows = list(_attributes(text))
    scoped = [r for r in rows if r[2]]
    assert scoped and not [r[0][:200] for r in scoped if r[1] not in PASSES]
    seen = collections.Counter(r[1] for r in rows if r[1])
    assert seen["fwd"] and seen["bwd"]
    assert bool(seen["remat"]) == remat, seen
    # an instruction the compiler did not make (a product, a gather, a call)
    # keeps its own op_name and its own attributes: the two agree
    own = [r for r in rows if r[1] and r[3] and not _KERNEL_BODY.search(r[3]) and re.search(
        r" (dot|convolution|custom-call|gather|scatter|sort)\(", r[0])]
    assert len(own) > 10
    assert not [(r[1], r[3]) for r in own if r[1] != _pass_of(r[3])], (
        "outside an interpreted kernel's body (_KERNEL_BODY) a pass disagrees with op_name")
    # the scan over the blocks, forward and transposed (a loop the compiler
    # sank an op into carries that op's: the head loss's, on a CPU)
    loops = {r[1] for r in rows if " while(" in r[0] and r[1] == _pass_of(r[3])
             and (r[2] or "").startswith("stack")}
    assert {"fwd", "bwd"} <= loops
    # a fusion carries its root's: most agree with the op_name they kept
    fusions = [r for r in rows if r[1] and r[3] and " fusion(" in r[0]
               and not _KERNEL_BODY.search(r[3])]
    assert sum(r[1] == _pass_of(r[3]) for r in fusions) >= 0.9 * len(fusions)




# the sparse kind's cases are ``tests/test_device_scopes_sparse.py``'s: its step
# compiles twice (with the scopes and without) as the hybrid's does, and a file
# goes to one worker
HERE = [kind for kind in KINDS if kind != "sparse"]


@pytest.mark.parametrize("kind", ["hybrid"])
def test_every_scoped_op_carries_the_pass_its_op_name_says(step_texts, kind):
    """``tracing.with_passes`` on the two heavy kinds' steps, compiled here
    anyway; the light kinds, no remat and remat ``full``:
    ``tests/test_device_passes.py``."""
    check_passes(step_texts(kind))


@pytest.mark.parametrize("kind", HERE)
def test_every_op_a_model_scope_issued_carries_its_path(step_texts, kind):
    check_paths(step_texts(kind), kind)


def check_paths(text, kind):
    rows = list(_scoped_instructions(text))
    paths = {scope for _, _, scope, _ in rows if scope}
    assert KINDS[kind][1] <= paths, KINDS[kind][1] - paths
    assert all(set(path.split("/")) <= set(SCOPES) for path in paths), paths
    # products, loops and gathers that op_name puts in a scope: all carry it
    # (a kernel too: ``tests/test_chip_compile.py``, where one is a custom call)
    heavy = [r for r in rows if r[1] in ("dot", "convolution", "while", "dynamic-update-slice",
                                         "gather", "scatter", "sort") and r[3]]
    assert heavy and not [r for r in heavy if not r[2]]
    # a fusion carries the path of the instruction it was made around (its root
    # then), so one made around a bitcast, a broadcast or a copy of the
    # compiler's is bare whatever it holds: most are not
    fusions = [r for r in rows if r[1] == "fusion" and r[3]]
    assert len([r for r in fusions if r[2]]) >= 0.7 * len(fusions)
    # and the path agrees with op_name, under scan, checkpoint and each
    # custom_vjp rule alike: the same innermost scope, no scope op_name lacks
    # but the scan's own (op_name may repeat one or lose ``stack``; the path
    # holds each as opened)
    for name, opcode, scope, named in rows:
        if scope and named and opcode in ("dot", "convolution"):  # never the compiler's
            parts = scope.split("/")
            assert parts[-1] == named[-1] and set(named) <= set(parts) <= {"stack", *named}, (
                name, scope, named)


def test_the_head_loss_and_the_held_range_keep_their_scope_in_the_backward_rule(step_texts):
    text = step_texts("hybrid")
    back = [line for line in text.splitlines() if "transpose(jvp" in line]
    for path in ("lm_head_loss", "stack/mlp/moe_dispatch", "stack/mlp/moe_experts",
                 "stack/mlp/moe_combine"):
        assert any(f'rt_scope="{path}"' in line for line in back), path
    # the forward rule's own pass (the loss and its gradients in one scan)
    assert any('rt_scope="lm_head_loss"' in line and " while(" in line
               for line in text.splitlines())
    # the held range's adds at a width of one lane tile (``moe_rows``, interpreted
    # here, a custom call on a TPU): the forward one under ``moe_combine``, the
    # gather's gradient under ``moe_dispatch`` inside the backward rule, so the
    # two scope readers keep seeing them; the embedding table's gradient is the
    # same rule's call under ``embed``
    rows = [line for line in text.splitlines() if "/moe_rows/" in line and 'rt_scope="' in line]
    assert {(re.search(r'rt_scope="([^"]*)"', line).group(1), "transpose(jvp" in line)
            for line in rows} == {("stack/mlp/moe_combine", False), ("stack/mlp/moe_dispatch", True),
                                  ("embed", True)}


@pytest.mark.parametrize("kind", HERE + ["serving"])
def test_scopes_change_names_metadata_and_the_attribute_only(monkeypatch, step_texts, kind):
    check_scopes_alone(monkeypatch, step_texts, kind)


def check_scopes_alone(monkeypatch, step_texts, kind):
    lower = _lower_mixed if kind == "serving" else functools.partial(
        _lower_loss, KINDS[kind][0], **KINDS[kind][2])
    with_scopes = _lower_mixed() if kind == "serving" else step_texts(kind)
    for scope in (("prefill_chunk", "decode_step") if kind == "serving"
                  else ("attn", "mlp", "embed", "lm_head_loss")):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', with_scopes), scope
        assert re.search(rf'rt_scope="([^"]*/)?{scope}(/[^"]*)?"', with_scopes), scope
    _without_scopes(monkeypatch)
    without = lower()
    assert "rt_scope" not in without and "rt_pass" not in without
    assert not re.search(r'op_name="[^"]*[/(](attn|mlp|decode_step)[/)]', without)
    assert _stripped(with_scopes) == _stripped(without)
    assert with_scopes.count("custom-call") == without.count("custom-call")


def _paths(fn, *args) -> list:
    """rt_scope of every op of ``fn``'s lowering that has one, in order."""
    return re.findall(r'rt_scope = "([^"]*)"', jax.jit(fn).lower(*args).as_text())


def test_nested_scopes_give_paths_and_leave_none_behind():
    def fn(x):
        with tracing.device_scope("outer") as outer:
            x = jnp.sin(x)
            with tracing.device_scope("inner") as inner:
                x = jnp.cos(x)
            x = jnp.tan(x)
        assert (outer, inner) == ("outer", "outer/inner")
        return jnp.exp(x)  # after the block: no attribute

    text = jax.jit(fn).lower(jnp.ones(4)).as_text()
    assert re.findall(r'rt_scope = "([^"]*)"', text) == ["outer", "outer/inner", "outer"]
    assert "rt_scope" not in next(l for l in text.splitlines() if "exponential" in l)
    assert re.search(r'stablehlo\.cosine.*rt_scope = "outer/inner"', text)


def test_a_scope_follows_scan_checkpoint_and_a_custom_vjp_rule():
    @jax.custom_vjp
    def f(x, w):
        return jnp.tanh(x @ w)

    def f_bwd(res, g):
        x, w = res
        with tracing.device_scope("rule"):  # nests under the CALL's path
            gg = g * (1 - jnp.tanh(x @ w) ** 2)
        return gg @ w.T, x.T @ gg  # bare in the rule: the call's path

    f.defvjp(lambda x, w: (f(x, w), (x, w)), f_bwd)

    def loss(w, x):
        def body(c, wi):
            with tracing.device_scope("mlp"):
                c = jax.checkpoint(lambda c, wi: f(c, wi) + jnp.sin(c))(c, wi)
            return c, None

        with tracing.device_scope("stack"):
            c, _ = jax.lax.scan(body, x, w)
        return jnp.sum(c ** 2)

    text = jax.jit(jax.grad(loss)).lower(jnp.ones((3, 8, 8)), jnp.ones((4, 8))
                                         ).compile().as_text()
    by_path = collections.Counter(
        (m.group(2), scope.group(1)) for line in text.splitlines()
        if (m := _INSTRUCTION.match(line)) and (scope := re.search(r'rt_scope="([^"]*)"', line)))
    assert by_path[("dot", "stack/mlp/rule")] == 1   # the rule's own product
    assert by_path[("dot", "stack/mlp")] == 3        # forward, and the rule's two bare ones
    assert by_path[("cosine", "stack/mlp")] == 1     # sin's derivative, from the remat
    assert by_path[("while", "stack")] == 2          # the scan, forward and transposed
    assert not [k for k in by_path if not k[1].startswith("stack")]


def test_a_thread_or_a_jit_traced_inside_a_scope_leaks_none():
    double = jax.jit(lambda x: x * 2)
    seen = {}

    def elsewhere():
        seen["thread"] = _paths(lambda x: jnp.sin(x), jnp.ones(4))

    with tracing.device_scope("outer"):
        # traced first inside the scope, as a call of an outer program
        inside = _paths(lambda x: double(x) + 1, jnp.ones(4))
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    assert inside and set(inside) == {"outer"}
    assert seen["thread"] == []
    assert _paths(lambda x: double(x) + 1, jnp.ones(4)) == []
    assert "rt_scope" not in double.lower(jnp.ones(4)).as_text()
