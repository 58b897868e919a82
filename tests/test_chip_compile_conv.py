"""The short-convolution preset's whole step (``conv-moe-debug`` at a width of one
lane tile, so that the conv's mix takes its kernel pair: two leading
dense layers under short convolutions, a period of roped per-head-normed
attention and three convs, a sigmoid router's held share, the tied table),
compiled by the chip's own compiler for a described ``v5e:2x2`` with every
kernel module steered to it, as ``tests/test_chip_compile_steps.py`` compiles
the older presets'. A file of its own so that ``--dist loadfile`` may give it
to another worker (each worker loads the TPU compiler's library, which the
driver's command allows: ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``; without it the
second to load skips its tests)."""

import collections
import dataclasses
import importlib
import re
import sys

import jax
import pytest

from test_chip_compile import chip  # noqa: F401 - the fixture; sets TPU_LOG_DIR too
from test_chip_compile_steps import KERNEL_MODULES, lowered_step


@pytest.mark.parametrize("remat", ["attn", "full"])
def test_the_conv_step_compiles_with_its_kernels_and_its_scopes(chip, monkeypatch, remat):  # noqa: F811
    """At a width of one lane tile the conv's mix is the kernel pair
    (``ops/sconv_elementwise.py``): ``sconv_fwd`` under ``sconv_mix`` in the
    forward pass and in the second run, ``sconv_bwd`` in the backward pass,
    once a conv layer of the program's text each; the step's other Mosaic
    calls are the attention layers', the experts' and the table's, each under
    its pass and its scope; the two products' scopes are on the chip's own ops,
    forward and backward, and under remat ``full`` (the cell's policy) in the
    second run too."""
    from ray_tpu.models.llama import PRESETS

    for name in KERNEL_MODULES:
        importlib.import_module(name)
        monkeypatch.setattr(sys.modules[name], "on_tpu", lambda: True)
    jax.clear_caches()
    cfg = dataclasses.replace(PRESETS["conv-moe-debug"], hidden=128, remat_policy=remat)
    lowered = lowered_step(chip, cfg)
    text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert lowered.as_text().count("@tpu_custom_call") == len(calls) > 0
    attrs = [re.search(r'rt_pass="(fwd|remat|bwd)",rt_scope="([^"]*)"', line) for line in calls]
    assert all(attrs)
    assert {m.group(2) for m in attrs} >= {"stack/attn", "stack/mlp/moe_experts",
                                           "stack/attn/sconv_mix"}
    kernel = lambda line: re.sub(r"[.\d]+$", "", line.split(" = ")[0].strip()  # noqa: E731
                                 .removeprefix("ROOT ").lstrip("%"))
    mix = collections.Counter((kernel(line), m.group(1)) for line, m in zip(calls, attrs)
                              if "sconv" in m.group(2))
    # the program's text holds a scanned period once: three conv slots, two leading layers
    layers = len(cfg.lead_pattern) + cfg.layer_pattern.count("sconv")
    assert mix == {("sconv_fwd", "fwd"): layers, ("sconv_fwd", "remat"): layers,
                   ("sconv_bwd", "bwd"): layers}, mix
    assert all(m.group(2) == "stack/attn/sconv_mix" for m in attrs if "sconv" in m.group(2))
    scoped = set(re.findall(r'rt_pass="(\w+)",rt_scope="stack/attn/(sconv_\w+)"', text))
    for scope in ("sconv_proj", "sconv_mix", "sconv_out"):
        assert {("fwd", scope), ("bwd", scope)} <= scoped, (scope, scoped)
    # what a policy makes again: under ``attn`` the stream is kept as the mixer's
    # output joins it, and the second run makes no out-projection
    assert (("remat", "sconv_out") in scoped) == (remat == "full"), scoped
    assert ("remat", "sconv_proj") in scoped and ("remat", "sconv_mix") in scoped
    jax.clear_caches()
