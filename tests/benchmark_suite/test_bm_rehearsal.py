"""``benchmark.run --rehearse`` end to end on the CPU, every cell (the
four-chip one on four virtual devices), in both trace modes, as the driver runs it: a subprocess
whose LAST LINE of output — standard error and whatever the workers print
merged in — parses and passes the checker ``emit`` uses."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import result
from benchmark.manifest import ROOT, Manifest

MANIFEST = Manifest()
CELLS = [w["name"] for w in MANIFEST.doc["workloads"]]


def run(args, **kw):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=ROOT, env=env,
        text=True, timeout=420, **kw)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_last_line_of_the_combined_output_passes_the_checker(name, trace):
    out = run(["--workload", name, "--seed", str(2**31 + 17), "--seconds", "6",
               "--trace", str(trace), "--rehearse"],
              stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert out.returncode == 0, out.stdout[-3000:]
    last = out.stdout.rstrip("\n").rsplit("\n", 1)[-1]
    obj = json.loads(last)
    cell = MANIFEST.cell(name)
    assert result.check(obj, cell.declared(bool(trace)), trace=bool(trace),
                        chips=None, platform="cpu") == []
    assert obj["correct"] is True and obj["failed"] == 0 and obj["attempted"] > 0
    assert obj["rehearsal"] is True and obj["device"]["platform"] == "cpu"
    assert set(obj["metrics"]) == set(cell.declared(bool(trace)))


def test_without_a_chip_the_command_exits_non_zero_and_prints_no_result():
    name = MANIFEST.doc["workloads"][0]["name"]
    out = run(["--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"],
              capture_output=True)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "TPU chip" in out.stderr


def test_an_unknown_workload_exits_non_zero_and_prints_no_result():
    out = run(["--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
               "--trace", "0"], capture_output=True)
    assert out.returncode != 0 and '"correct"' not in out.stdout
