"""chip_smoke.py's phases at debug size on the CPU, and its command line.

The phase functions are the ones the chip run calls; only the preset, the
sizes and the expected platform differ. The command line itself cannot be
told to accept a CPU: here it must exit non-zero and never print a result.
"""

import os
import subprocess
import sys

import pytest

import ray_tpu

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def test_phases_on_cpu_at_debug_size(cluster):
    device = chip_smoke.phase_train(
        "debug-128", batch=2, seq=64, steps=2, seed=0, platform="cpu")
    assert device["platform"] == "cpu" and device["count"] >= 1
    # traced through the interpreter here, never natively
    assert device["kernel_traces"].get("flash_attention:interpret", 0) > 0
    served = chip_smoke.phase_serve(
        "debug-128",
        engine={"max_slots": 2, "max_len": 128, "page_size": 16,
                "prefill_chunk_size": 32, "decode_steps_per_dispatch": 4,
                "attention_impl": "auto"},
        n_requests=2, max_tokens=6, platform="cpu")
    assert served["platform"] == "cpu"


def test_a_phase_that_ran_on_the_wrong_platform_fails():
    with pytest.raises(chip_smoke.SmokeFailure, match="expected 1 x tpu"):
        chip_smoke._check_device(
            {"platform": "cpu", "kind": "cpu", "count": 1}, "tpu", 1)
    with pytest.raises(chip_smoke.SmokeFailure, match="natively"):
        chip_smoke._check_kernel(
            {"flash_attention:interpret": 3}, "flash_attention", "tpu")
    with pytest.raises(chip_smoke.SmokeFailure, match="natively"):
        chip_smoke._check_kernel(
            {"flash_attention:pallas": 3, "flash_attention:mha_reference": 1},
            "flash_attention", "tpu")


def test_command_line_refuses_a_host_without_a_chip():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "0 TPU chip(s)" in out.stderr
