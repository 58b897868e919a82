"""Per-layer metrics: each has a reader file ``layer_metrics/<name>.json``
of a ``kind`` below, with its parameters. A reader takes what a traced
run observed (``obs``), its parameters and the metric's name, and returns
a number, or None where there was nothing to read.

``obs`` is a nest of dicts, addressed by dotted paths:
  timers.*           host-clock means of the train loop (ms)
  train.*            tokens/s, FLOPs per token, peak, chips
  trace.*            trace_reduce.reduce_trace's summary
"""

from __future__ import annotations

import math

from .trace_reduce import matching


def _get(obs: dict, path: str):
    node = obs
    for key in path.split("."):
        node = node[key]
    return node


def _product(obs: dict, paths) -> float:
    out = 1.0
    for p in paths:
        out *= p if isinstance(p, (int, float)) else _get(obs, p)
    return out


def ratio(obs: dict, p: dict, name: str):
    """prod(num) / prod(den) * scale; terms are obs paths or numbers."""
    den = _product(obs, p["den"])
    return None if den == 0 else _product(obs, p["num"]) / den * p.get("scale", 1.0)


def value(obs: dict, p: dict, name: str):
    """A number the run measured itself, times ``scale``."""
    return _get(obs, p["path"]) * p.get("scale", 1.0)


def trace_share(obs: dict, p: dict, name: str):
    """Self time on the op line of the ops whose name matches ``pattern``,
    as a percentage of ``of`` (``busy_s`` or ``window_s``), on the first
    device."""
    seconds, _ = matching(_get(obs, "trace.ops"), p["pattern"])
    whole = (_get(obs, "trace.busy_s_per_device")[0] if p["of"] == "busy_s"
             else _get(obs, "trace." + p["of"]))
    return None if whole == 0 else 100.0 * seconds / whole


def trace_union_share(obs: dict, p: dict, name: str):
    """Time in which an operation the reader's ``ops``/``start``/``done``
    patterns describe was running or in flight (``trace_reduce.inflight``:
    a union, so two at once count once), as a percentage of the window, on
    the first device. The union is taken where the trace is; this reads it."""
    window = _get(obs, "trace.window_s")
    return None if window == 0 else 100.0 * _get(obs, "trace.unions")[name] / window


def idle_share(obs: dict, p: dict, name: str):
    """1 - busy / window, in percent; busy is the union of the op line's
    intervals, the mean over devices."""
    trace = _get(obs, "trace")
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


KINDS = {"ratio": ratio, "value": value, "trace_share": trace_share,
         "trace_union_share": trace_union_share, "idle_share": idle_share}


def union_specs(readers: dict) -> dict:
    """What the trace reduction has to take a union of, by metric name: the
    parameters of every ``trace_union_share`` reader."""
    return {name: r["params"] for name, r in readers.items()
            if r["kind"] == "trace_union_share"}


def read_all(readers: dict, obs: dict) -> dict:
    """metric name -> value for every reader that found something."""
    out = {}
    for name, reader in readers.items():
        try:
            v = KINDS[reader["kind"]](obs, reader.get("params", {}), name)
        except KeyError as e:
            raise KeyError(f"layer metric {name}: nothing observed under {e}") from e
        if v is not None and math.isfinite(v):
            out[name] = float(v)
    return out
