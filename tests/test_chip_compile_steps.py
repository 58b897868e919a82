"""A debug preset's whole step, compiled by the chip's own compiler for a
described ``v5e:2x2`` with every kernel module steered to it: which scope each
Mosaic call carries, and that switching the scopes off changes no call. The
kernels one at a time at the cells' shapes, and the described chip itself
(``chip``): ``tests/test_chip_compile.py``. Two files so that ``--dist
loadfile`` can give them to two workers; each worker loads the TPU compiler's
library, which the driver's command allows (``ALLOW_MULTIPLE_LIBTPU_LOAD=1``;
without it the second to load skips its tests)."""

import jax
import jax.numpy as jnp
import pytest

from test_chip_compile import chip  # noqa: F401 - the fixture; sets TPU_LOG_DIR too

KERNEL_MODULES = ("ray_tpu.tpu", "ray_tpu.ops.attention", "ray_tpu.ops.grouped_matmul",
                  "ray_tpu.ops.sparse_index", "ray_tpu.ops.gated_delta",
                  "ray_tpu.ops.gdn_elementwise", "ray_tpu.models.gdn", "ray_tpu.ops.moe_rows",
                  "ray_tpu.ops.lightning_attention", "ray_tpu.ops.ssd",
                  "ray_tpu.ops.mamba_elementwise", "ray_tpu.models.mamba2",
                  "ray_tpu.ops.sconv_elementwise", "ray_tpu.models.short_conv")


def lowered_step(chip, cfg, rows=2, seq=256, chunk=128):
    """A configuration's whole step (loss and gradient), lowered for the
    described chip: ``.as_text()`` is what was traced,
    ``.compile().as_text()`` the chip's optimized program."""
    from ray_tpu.models.llama import init_params, loss_fn

    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)  # noqa: E731
    params = jax.tree.map(on, jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    batch = {"tokens": jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=chip)}
    return jax.jit(jax.grad(lambda p, b: loss_fn(p, b, cfg, chunk_tokens=chunk))
                   ).lower(params, batch)


def _tiny_step(chip, preset):
    """A debug preset's whole step under remat ``attn``."""
    import dataclasses

    from ray_tpu.models.llama import PRESETS

    return lowered_step(chip, dataclasses.replace(PRESETS[preset], remat_policy="attn"))


@pytest.mark.parametrize("preset,scopes", [
    # a width of one lane tile: the embedding table's gradient is the kernel
    # ``moe_rows``, called inside the lookup's backward rule under ``embed``
    ("debug-128", {"embed", "stack/attn"}),
    # ``hybrid-debug``'s DeltaNet heads are 16 wide, no lane tile: its conv and
    # gated norm take the plain functions by their shape, steered or not, so no
    # kernel reads under ``gdn_conv`` or ``gdn_out`` here (the cell's widths:
    # ``tests/test_chip_compile.py``'s ``gdn-conv-*`` / ``gdn-norm-*``)
    ("hybrid-debug", {"stack/attn", "stack/attn/gdn_scan", "stack/mlp/moe_experts"}),
    ("latent-sparse-debug", {"stack/attn", "stack/attn/dsa_index", "stack/attn/dsa_select",
                             "stack/attn/dsa_loss", "stack/mlp/moe_experts"}),
    # a layer that attends every causal key calls the plain kernels under a
    # scope of its own
    ("latent-full-debug", {"stack/attn/mla_full", "stack/mlp/moe_experts"})])
def test_a_step_compiles_with_every_kernel_under_its_scope_and_as_many_as_without(
        chip, monkeypatch, preset, scopes):
    """A dense, the hybrid and two latent steps, every kernel module steered to the chip
    (this process's backend is the CPU): each Mosaic call's own text holds
    ``rt_pass`` and ``rt_scope`` beside ``kernel_metadata``, as the op line
    prints them, the chip's compiler keeps every call that was traced, and as
    many are traced with ``device_scope`` and ``with_passes`` switched off
    (that form is lowered for the chip and not compiled a second time: the
    compiler dropped no call of the first)."""
    import contextlib
    import importlib
    import re
    import sys

    for name in KERNEL_MODULES:
        importlib.import_module(name)
        monkeypatch.setattr(sys.modules[name], "on_tpu", lambda: True)
    jax.clear_caches()
    lowered = _tiny_step(chip, preset)
    calls = [line for line in lowered.compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert lowered.as_text().count("@tpu_custom_call") == len(calls)
    attrs = [re.search(r'frontend_attributes=\{kernel_metadata=\{\},rt_pass="(fwd|remat|bwd)",'
                       r'rt_scope="([^"]*)"\}', line) for line in calls]
    assert calls and all(attrs)
    assert {m.group(2) for m in attrs} == scopes
    # every kernel under a pass (``tracing.with_passes``): a call is ONE
    # instruction here, under the pass of its ``pallas_call`` eqn, so the
    # backward kernels read ``bwd`` and none of them ``fwd``
    by_kernel = {}
    for line, m in zip(calls, attrs):
        kernel = re.sub(r"[.\d]+$", "", line.split(" = ")[0].strip().removeprefix("ROOT ").lstrip("%"))
        by_kernel.setdefault(kernel, set()).add(m.group(1))
    assert {m.group(1) for m in attrs} >= {"fwd", "bwd"}
    assert all(passes == {"bwd"} for kernel, passes in by_kernel.items() if "_bwd" in kernel), (
        by_kernel)
    assert all("bwd" not in passes for kernel, passes in by_kernel.items()
               if kernel.endswith("_fwd")), by_kernel
    for module in ("llama", "moe", "mla", "gdn"):
        monkeypatch.setattr(sys.modules[f"ray_tpu.models.{module}"], "device_scope",
                            lambda name: contextlib.nullcontext())
    monkeypatch.setattr(sys.modules["ray_tpu.models.llama"], "with_passes",
                        lambda fn, has_aux=False: fn)
    jax.clear_caches()
    without = _tiny_step(chip, preset).as_text()
    assert "rt_scope" not in without and "rt_pass" not in without
    assert without.count("@tpu_custom_call") == len(calls)
    jax.clear_caches()
