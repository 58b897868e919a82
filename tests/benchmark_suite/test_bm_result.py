"""result.emit: the one writer of the last line accepts a good object and
refuses each broken one, printing nothing for those."""

import copy
import json
import os

import pytest

from benchmark import result

E2E = {"train_tok_s_chip": "tokens/s/chip", "setup_s": "s"}
LAYER = {"device.idle_share.train": "%"}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 11_000_000_000}


def good(trace: bool) -> dict:
    declared = LAYER if trace else E2E
    obj = {"correct": True, "attempted": 200, "failed": 0,
           "metrics": {k: {"value": 12.5, "unit": u} for k, u in declared.items()},
           "device": dict(DEVICE)}
    if trace:
        obj["device"].update(busy_s=2.5, window_s=4.0)
        obj["breakdown"] = {"device_ops": [["fusion.1", 1.5]], "idle_gaps": []}
    return obj


def emit(obj, trace):
    r, w = os.pipe()
    try:
        code = result.emit(obj, LAYER if trace else E2E, trace=trace, chips=1,
                           platform="tpu", out_fd=w)
    finally:
        os.close(w)
    with os.fdopen(r) as f:
        return code, f.read()


@pytest.mark.parametrize("trace", [False, True])
def test_a_good_object_is_printed_as_one_line(trace):
    code, text = emit(good(trace), trace)
    assert code == 0 and text.endswith("\n") and text.count("\n") == 1
    assert json.loads(text) == good(trace)
    assert result.check(good(trace), LAYER if trace else E2E, trace=trace,
                        chips=1) == []


def _set(path, value):
    def change(obj):
        node = obj
        for key in path[:-1]:
            node = node[key]
        if value is KeyError:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return change


BROKEN = {
    "missing key": (False, _set(["device"], KeyError)),
    "missing correct": (False, _set(["correct"], KeyError)),
    "missing metric": (False, _set(["metrics", "setup_s"], KeyError)),
    "metric without unit": (False, _set(["metrics", "setup_s"], {"value": 3.0})),
    "wrong unit": (False, _set(["metrics", "setup_s", "unit"], "ms")),
    "nan value": (False, _set(["metrics", "train_tok_s_chip", "value"], float("nan"))),
    "infinite value": (False, _set(["metrics", "train_tok_s_chip", "value"], float("inf"))),
    "value not a number": (False, _set(["metrics", "train_tok_s_chip", "value"], "12")),
    "undeclared metric": (False, _set(["metrics", "extra"], {"value": 1.0, "unit": "s"})),
    "failed above attempted": (False, _set(["failed"], 201)),
    "correct not a bool": (False, _set(["correct"], "yes")),
    "wrong platform": (False, _set(["device", "platform"], "cpu")),
    "wrong chip count": (False, _set(["device", "count"], 4)),
    "no memory peak": (False, _set(["device", "memory_peak_bytes"], KeyError)),
    "traced without busy_s": (True, _set(["device", "busy_s"], KeyError)),
    "busy_s zero": (True, _set(["device", "busy_s"], 0.0)),
    "busy_s above window_s": (True, _set(["device", "busy_s"], 4.5)),
    "busy_s not finite": (True, _set(["device", "busy_s"], float("nan"))),
    "breakdown too long": (True, _set(["breakdown", "device_ops"],
                                      [["op", 0.1]] * 11)),
    "breakdown untraced": (False, _set(["breakdown"], {"device_ops": [], "idle_gaps": []})),
    "not an object": (False, None),
}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_a_broken_object_is_refused_and_nothing_is_printed(case, capfd):
    trace, change = BROKEN[case]
    obj = copy.deepcopy(good(trace))
    if change is None:
        obj = [obj]
    else:
        change(obj)
    code, text = emit(obj, trace)
    assert code == result.EXIT_NO_RESULT and text == ""
    assert "no result printed" in capfd.readouterr().err
