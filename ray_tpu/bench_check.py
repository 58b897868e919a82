"""Bench-regression guard: diff two recorded benchmark results.

``python -m ray_tpu.bench_check BENCH_r05.json BENCH_r06.json`` compares
every shared numeric metric and exits non-zero when any regresses by
more than the threshold (default 10%) — so a silent drop like the
round-5 ``flash_fwdbwd_tflops_s4096`` 26.16 → 22.99 slide, or a metric
silently VANISHING (round 5's ``serve_p50_ttft_ms``, lost to a replica
startup failure), gets flagged at PR time instead of two rounds later.

Accepts either a bare metrics object (what ``cli bench core`` / ``cli
bench dag`` print) or a ``BENCH_rNN.json`` wrapper (metrics under ``"parsed"``).

Direction is inferred from the metric name: ``*_ms`` / ``*_pct`` /
latency-like metrics regress UP, throughput-like metrics regress DOWN;
bookkeeping fields (counts, config echoes, error strings) are skipped.
``cli bench core|dag --check-against FILE`` runs this over that suite's
slice of a recorded file. These are host rates; the device's numbers are
``benchmark/``'s and are compared by the driver, not here.
"""

from __future__ import annotations

import glob
import json
import os
import sys

# Metrics that describe the run, not its performance. Shed/offered
# counts from the overload bench are bookkeeping: protection ON sheds
# MORE than the unprotected baseline by design, so neither direction is
# a regression — goodput_frac and the fast-fail latency are the guarded
# numbers.
_SKIP_EXACT = {
    "n", "rc", "vs_baseline", "loss", "serve_requests", "serve_concurrency",
    "serve_decode_steps_per_dispatch",
    "serve_shed_requests", "serve_overload_offered", "serve_overload_completed",
    "serve_deadline_expired",
    # Speculative-bench bookkeeping: draft volume and dispatch counts
    # describe the run; accept_rate / tokens_per_dispatch / tok_s are
    # the guarded numbers.
    "spec_drafted_tokens", "spec_dispatches",
}
# "_cfg": config echoes (core-bench phase sizes etc.) — sizes are inputs,
# not results.
_SKIP_SUBSTR = ("error", "preset", "metric", "unit", "cmd", "tail", "_cfg")
# Throughput rates: ALWAYS higher-better, checked BEFORE the lower-better
# suffixes — "core_tasks_per_s" ends in "_s" but a drop in it is the
# regression, not an improvement. "_mb_s": transfer throughput in MB/s
# (kv_migration_mb_s), same shadowed-by-"_s" hazard. "_tok_s": token
# throughput — round-13 audit found a bare "..._tok_s" metric would be
# shadowed by the lower-better "_s" exactly like "_mb_s" was before
# PR 11 (existing names only dodge it by suffixing the cell, e.g.
# decode_tok_s_plain). "_tokens_per_dispatch": speculative-decoding
# amortization (emitted tokens per slot per verify forward).
_HIGHER_BETTER_SUFFIX = ("_per_s", "_per_sec", "_mb_s", "_tok_s",
                         "_tokens_per_dispatch")
# 0-1 ratios (cache hit rates, accept rates, fractions): higher-better
# AND compared in POINTS like _pct — a hit rate sliding 0.90 -> 0.45 is
# a 45-point collapse; 0.02 -> 0.01 is noise, not a 50% regression.
# "_accept_rate": the speculative drafter's 0-1 accept fraction.
# "_frac" covers the serve goodput/suffix fractions. "_parity": greedy
# byte-parity cells
# (spec_parity, serve_overload_parity, tenant_mixed_batch_parity) — a
# 1.0-or-broken invariant, so pointwise; any slip below 1.0 is the
# regression. Round-16 shadow audit: the new tenancy cells end in
# "_ms" (tenant_quiet_p95_ttft_ms*, adapter_hot_load_ms — lower-better,
# and "ttft" substring already matches the quiet-p95 pair), "_frac"
# (tenant_goodput_frac_* — pointwise), and "_parity"; none end in a
# bare "_s", so the pre-PR-11 "_mb_s" shadowing hazard doesn't apply.
_POINTWISE_RATE_SUFFIX = ("_hit_rate", "_accept_rate", "_frac", "_parity")
# MFU is a 0-1 fraction too, but its cell tag often FOLLOWS the unit
# ("mfu", "mfu_8b_proxy", "train_mfu_eager", "train_mfu_loop",
# "train_mfu_1b_seq8k"), so it is matched by substring, not suffix.
# "goodput_frac": same tag-after-unit shape — the round-16 audit found
# serve_goodput_frac_unprotected and tenant_goodput_frac_{hot,cold}
# fell out of the "_frac" suffix into a relative compare, where a
# CPU-sandbox 0.05 -> 0.04 wiggle reads as a 20% regression.
# Round-15 audit note: none of the mfu cells end in "_s"/"_ms", so the
# lower-better suffix table cannot shadow them (the pre-PR-11 "_mb_s"
# hazard) — but a relative compare would still flag a 0.0002-point CPU
# wiggle as a regression; points are the right scale.
_POINTWISE_RATE_SUBSTR = ("mfu", "goodput_frac")
# Round-19 shadow audit (fleet bench): ``serve_replica_promote_s`` /
# ``serve_replica_cold_start_s`` end in a bare "_s" → lower-better, the
# right call (promotion getting slower IS the regression the always-warm
# pool exists to prevent). ``fleet_broadcast_parity`` rides the
# "_parity" pointwise suffix (1.0-or-broken), ``fleet_goodput_frac_step``
# the "goodput_frac" substring (pointwise — a CPU-sandbox 0.05 wiggle
# must not read as a relative collapse), and
# ``serve_replica_promote_speedup`` falls through to the default
# higher-better. ``fleet_skipped``/per-cell ``*_skipped`` markers flow
# through _skip_prefixes like every other suite's.
# Pointwise cells that regress UP: still compared in points on the 0-1
# scale, but LOWER is better. Round-18 audit: before this table,
# ``loop_obs_overhead_frac`` (stall-recorder cost as a fraction of tick
# dispatch) fell into the pointwise branch and was guarded BACKWARDS —
# the "_frac" suffix check ran before the "overhead" substring, so a
# recorder cost blowup 0.01 -> 0.15 read as a 14-point improvement.
# "stall_wait": the dag loop's wait_up/wait_down stall split — a stage
# spending more of its tick blocked is the regression (the compute_frac
# cell stays higher-better pointwise via the plain "_frac" suffix).
_POINTWISE_DOWN_SUBSTR = ("overhead", "stall_wait")
# Lower is better. Peak-memory gauges count as regressions when they
# GROW >threshold (a quiet 2x pool blowup is exactly what they exist
# to catch). "_lag_steps": checkpoint lag (steps replayed after a
# preemption recovery) regresses UP — more lost work is worse.
# "fast_fail": the time-to-503 of a shed request (overload bench) —
# slower rejections are the regression the bound exists to prevent.
_LOWER_BETTER_SUFFIX = ("_ms", "_us", "_pct", "_bytes", "_s", "_lag_steps")
_LOWER_BETTER_SUBSTR = ("latency", "ttft", "overhead", "failed", "fast_fail")


def load_metrics(path: str) -> dict:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        data = data["parsed"]
    if not isinstance(data, dict):
        raise ValueError(f"{path}: expected a JSON object of metrics")
    return data


def _pointwise(name: str) -> bool:
    """0-1 fraction metrics compared in points (higher-better)."""
    return name.endswith(_POINTWISE_RATE_SUFFIX) or any(
        s in name for s in _POINTWISE_RATE_SUBSTR)


def _direction(name: str) -> str:
    """'up' = larger is better, 'down' = smaller is better."""
    if _pointwise(name):
        # Pointwise cells carry their own direction: fractions are
        # higher-better unless the name marks them as a cost/stall.
        return "down" if any(s in name for s in _POINTWISE_DOWN_SUBSTR) \
            else "up"
    if name.endswith(_HIGHER_BETTER_SUFFIX):
        return "up"
    if name.endswith(_LOWER_BETTER_SUFFIX) or any(
            s in name for s in _LOWER_BETTER_SUBSTR):
        return "down"
    return "up"


def _tracked(name: str, value) -> bool:
    if name in _SKIP_EXACT or any(s in name for s in _SKIP_SUBSTR):
        return False
    return isinstance(value, (int, float)) and not isinstance(value, bool)


def _skip_prefixes(new: dict) -> tuple:
    """``<prefix>_skipped: true`` markers: the run declares it
    INTENTIONALLY skipped every ``<prefix>*`` metric (e.g.
    ``core_scale_skipped`` under RAY_TPU_BENCH_SKIP_CORE_SCALE=1). Such
    metrics are reported as skipped, never as silently vanished."""
    return tuple(k[: -len("_skipped")] for k, v in new.items()
                 if k.endswith("_skipped") and v)


def compare(old: dict, new: dict, threshold: float = 0.10) -> dict:
    """Returns {"regressions": [...], "improvements": [...],
    "missing": [...], "skipped": [...], "ok": [...]} — each row a dict
    with metric, old, new, change (signed fraction, + = better).
    ``skipped`` rows are absences covered by a ``*_skipped`` marker in
    the new run (intentional, non-failing)."""
    out = {"regressions": [], "improvements": [], "missing": [],
           "skipped": [], "ok": []}
    skipped = _skip_prefixes(new)
    for name, ov in sorted(old.items()):
        if not _tracked(name, ov):
            continue
        nv = new.get(name)
        if not isinstance(nv, (int, float)) or isinstance(nv, bool):
            if skipped and name.startswith(skipped):
                out["skipped"].append({"metric": name, "old": ov, "new": None})
                continue
            # was measured, now gone: exactly the silent failure mode
            # this guard exists for
            out["missing"].append({"metric": name, "old": ov, "new": None})
            continue
        if _pointwise(name):
            # 0-1 rates compare in POINTS: the threshold is a point
            # budget on the 0-1 scale (0.10 = 10 points). Direction
            # comes from the name — overhead/stall fracs regress UP.
            delta = round(nv - ov, 4)
            better = delta if _direction(name) == "up" else -delta
            row = {"metric": name, "old": ov, "new": nv, "change": better}
            if better < -threshold:
                out["regressions"].append(row)
            elif better > threshold:
                out["improvements"].append(row)
            else:
                out["ok"].append(row)
            continue
        if ov == 0:
            continue
        if name.endswith("_pct") and abs(nv - ov) < 1.0:
            # percentages compare in POINTS: -0.14% -> -0.05% framework
            # overhead is noise, not a 64% regression
            out["ok"].append({"metric": name, "old": ov, "new": nv,
                              "change": 0.0})
            continue
        delta = (nv - ov) / abs(ov)
        better = delta if _direction(name) == "up" else -delta
        row = {"metric": name, "old": ov, "new": nv,
               "change": round(better, 4)}
        if better < -threshold:
            out["regressions"].append(row)
        elif better > threshold:
            out["improvements"].append(row)
        else:
            out["ok"].append(row)
    return out


def format_report(result: dict, old_path: str = "old", new_path: str = "new",
                  threshold: float = 0.10) -> str:
    lines = [f"bench_check: {old_path} -> {new_path} "
             f"(threshold {threshold:.0%})"]
    for row in result["regressions"]:
        lines.append(f"  REGRESSION  {row['metric']}: {row['old']} -> "
                     f"{row['new']} ({row['change']:+.1%})")
    for row in result["missing"]:
        lines.append(f"  MISSING     {row['metric']}: {row['old']} -> "
                     "absent in new run")
    for row in result.get("skipped", []):
        lines.append(f"  skipped     {row['metric']}: intentionally "
                     "skipped in new run (marker present)")
    for row in result["improvements"]:
        lines.append(f"  improved    {row['metric']}: {row['old']} -> "
                     f"{row['new']} ({row['change']:+.1%})")
    n_ok = len(result["ok"])
    lines.append(f"  {n_ok} metric(s) within threshold; "
                 f"{len(result['regressions'])} regression(s), "
                 f"{len(result['missing'])} missing")
    return "\n".join(lines)


def latest_bench_json(directory: str = ".") -> str | None:
    """Most recent recorded BENCH_r*.json in ``directory``."""
    paths = sorted(glob.glob(os.path.join(directory, "BENCH_r[0-9]*.json")))
    return paths[-1] if paths else None


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    threshold = 0.10
    paths = []
    it = iter(argv)
    for a in it:
        if a == "--threshold":
            threshold = float(next(it))
        elif a.startswith("--threshold="):
            threshold = float(a.split("=", 1)[1])
        else:
            paths.append(a)
    if len(paths) != 2:
        print("usage: python -m ray_tpu.bench_check OLD.json NEW.json "
              "[--threshold 0.10]", file=sys.stderr)
        return 2
    result = compare(load_metrics(paths[0]), load_metrics(paths[1]),
                     threshold=threshold)
    print(format_report(result, paths[0], paths[1], threshold))
    return 1 if result["regressions"] or result["missing"] else 0


if __name__ == "__main__":
    sys.exit(main())
