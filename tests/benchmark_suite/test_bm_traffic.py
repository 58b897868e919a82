"""The traffic generator: the same seed gives the same inputs, another seed
others of the same shape, and every mix a cell names has a generator."""

import json

import numpy as np
import pytest

from benchmark import traffic
from benchmark.manifest import Manifest

MIXES = sorted({w["traffic"] for w in Manifest().doc["workloads"]})
BIG = 2**31 + 11  # the driver's seeds pass 32 signed bits


def load(name):
    with open(Manifest.traffic_file(name)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_train_rows_come_from_the_seed_and_stay_in_the_vocabulary(name):
    mix = load(name)
    a = traffic.train_rows(mix, 92544, 2, BIG)
    assert a.shape == (2 * mix["rows_steps"], mix["seq"]) and a.dtype == np.int32
    assert (a == traffic.train_rows(mix, 92544, 2, BIG)).all()
    other = traffic.train_rows(mix, 92544, 2, 12)
    assert other.shape == a.shape and (a != other).any()
    assert a.min() >= 0 and a.max() < 92544


@pytest.mark.parametrize("name", MIXES)
def test_one_pass_of_the_rows_outlasts_the_longest_window(name):
    # 51 s is the longest run_seconds the contract allows; a v5e step of
    # these sizes takes 0.87 s and up (PERF.md), and the rows must not run out
    mix = load(name)
    assert mix["rows_steps"] * 0.2 >= 51


def test_a_rehearsal_shortens_the_rows_and_an_unknown_kind_is_an_error():
    mix = load(MIXES[0])
    assert traffic.train_rows(mix, 512, 4, 1, seq=64).shape == (4 * mix["rows_steps"], 64)
    with pytest.raises(ValueError, match="no generator"):
        traffic.train_rows({**mix, "kind": "open_poisson"}, 512, 4, 1)
