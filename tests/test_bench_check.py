"""Bench-regression guard (``python -m ray_tpu.bench_check``)."""

import json

from ray_tpu import bench_check


def test_direction_inference():
    assert bench_check._direction("serve_p50_ttft_ms") == "down"
    assert bench_check._direction("framework_overhead_pct") == "down"
    assert bench_check._direction("peak_hbm_used_bytes") == "down"
    assert bench_check._direction("flash_fwdbwd_tflops_s4096") == "up"
    assert bench_check._direction("raw_tokens_per_sec") == "up"
    # throughput rates trump the "_s" lower-better suffix
    assert bench_check._direction("core_tasks_per_s") == "up"
    assert bench_check._direction("core_actor_calls_per_s") == "up"
    assert bench_check._direction("core_obj_roundtrip_per_s") == "up"
    assert bench_check._direction("serve_tokens_per_sec") == "up"
    # lease-stage latencies stay lower-better
    assert bench_check._direction("core_lease_submit_to_lease_p50_ms") == "down"
    # round-8 dag metrics: dispatch overheads (µs) are lower-better,
    # decode/tick rates higher-better
    assert bench_check._direction("dag_tick_dispatch_overhead_us") == "down"
    assert bench_check._direction(
        "dag_tick_dispatch_overhead_dynamic_us") == "down"
    assert bench_check._direction("dag_loop_ticks_per_s") == "up"
    assert bench_check._direction("pp_decode_tok_s_dynamic") == "up"
    assert bench_check._direction("pp_decode_tok_s_compiled") == "up"


def test_dag_metrics_skip_markers():
    """pp decode cells may be intentionally skipped on hosts that can't
    run the pp shard_map — the markers route the absence to the
    non-failing skipped bucket, exactly like serve matrix cells."""
    old = {"pp_decode_tok_s_dynamic": 100.0, "pp_decode_tok_s_compiled": 120.0,
           "dag_tick_dispatch_overhead_us": 900.0}
    new = {"dag_tick_dispatch_overhead_us": 850.0,
           "pp_decode_tok_s_dynamic_skipped": True,
           "pp_decode_tok_s_compiled_skipped": True}
    result = bench_check.compare(old, new)
    assert not result["missing"]
    assert {r["metric"] for r in result["skipped"]} == {
        "pp_decode_tok_s_dynamic", "pp_decode_tok_s_compiled"}


def test_core_metrics_guarded():
    """ISSUE 6 satellite: a >10% core-metric drop or a silently-vanished
    core metric fails the bench; config echoes (_cfg) are never tracked."""
    old = {"core_tasks_per_s": 3439.4, "core_actor_calls_per_s": 1973.8,
           "core_obj_roundtrip_per_s": 27682.9, "core_tasks_cfg": 20000}
    # a 20% tasks drop regresses; cfg echo resized without complaint
    new = {"core_tasks_per_s": 2751.5, "core_actor_calls_per_s": 1990.0,
           "core_obj_roundtrip_per_s": 27000.0, "core_tasks_cfg": 50000}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["regressions"]} == {"core_tasks_per_s"}
    assert not result["missing"]
    # a vanished core metric is flagged even when the others improved
    new2 = {"core_tasks_per_s": 5000.0, "core_actor_calls_per_s": 2500.0}
    result2 = bench_check.compare(old, new2)
    assert {r["metric"] for r in result2["missing"]} == {
        "core_obj_roundtrip_per_s"}
    # an INCREASE in a rate is an improvement, never a regression
    assert {r["metric"] for r in result2["improvements"]} == {
        "core_tasks_per_s", "core_actor_calls_per_s"}


def test_core_scale_metric_directions():
    """ISSUE 14 recurring audit: the new creation/scale rates must never
    fall into the lower-better `_s` suffix (they end in `_per_s`), the
    pooled-spawn fraction is a pointwise higher-better rate, and the
    harness-size echoes (`_cfg`) are never tracked."""
    assert bench_check._direction("core_actor_creations_per_s") == "up"
    assert bench_check._direction("core_scale_tasks_per_s") == "up"
    assert bench_check._direction("core_scale_actor_creations_per_s") == "up"
    assert bench_check._direction("core_scale_pooled_spawn_frac") == "up"
    # spawn latencies stay lower-better
    assert bench_check._direction("core_lease_worker_spawn_p50_ms") == "down"
    for echo in ("core_scale_raylets_cfg", "core_scale_tasks_cfg",
                 "core_scale_actors_cfg", "core_zygote_pool_cfg",
                 "core_scale_pool_cfg", "core_scale_chaos_storm_cfg"):
        assert not bench_check._tracked(echo, 8)
    # ... and a real drop in the new rates is flagged as a regression
    old = {"core_actor_creations_per_s": 80.0, "core_scale_tasks_per_s": 2000.0}
    new = {"core_actor_creations_per_s": 40.0, "core_scale_tasks_per_s": 2100.0}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["regressions"]} == {
        "core_actor_creations_per_s"}


def test_core_scale_skip_marker():
    """`core_scale_skipped: true` (the 1-core-sandbox escape hatch)
    routes every absent core_scale_* cell to the non-failing skipped
    bucket instead of `missing`."""
    old = {"core_scale_tasks_per_s": 2372.8,
           "core_scale_actor_creations_per_s": 22.8,
           "core_scale_pooled_spawn_frac": 1.0,
           "core_tasks_per_s": 2000.0}
    new = {"core_scale_skipped": True, "core_tasks_per_s": 2100.0}
    result = bench_check.compare(old, new)
    assert not result["missing"]
    assert {r["metric"] for r in result["skipped"]} == {
        "core_scale_tasks_per_s", "core_scale_actor_creations_per_s",
        "core_scale_pooled_spawn_frac"}


def test_compare_flags_drops_and_missing():
    old = {"flash_fwdbwd_tflops_s4096": 26.16, "serve_p50_ttft_ms": 272.1,
           "value": 11363.9, "serve_preset": "llama3-1b", "n": 4}
    new = {"flash_fwdbwd_tflops_s4096": 22.99, "value": 11349.5,
           "serve_error": "TimeoutError: not healthy", "n": 5}
    result = bench_check.compare(old, new)
    regressed = {r["metric"] for r in result["regressions"]}
    assert regressed == {"flash_fwdbwd_tflops_s4096"}   # -12.1% > 10%
    missing = {r["metric"] for r in result["missing"]}
    assert missing == {"serve_p50_ttft_ms"}             # silently vanished
    ok = {r["metric"] for r in result["ok"]}
    assert ok == {"value"}                               # -0.1% is fine
    # non-numeric / bookkeeping fields never tracked
    assert not any("preset" in r["metric"] for rows in result.values()
                   for r in rows)


def test_matrix_metrics_directions():
    """ISSUE 7 satellite: every serve-matrix cell metric compares
    lower-better — `*_ttft_ms` and the new `*_itl_ms` inter-token
    latency both regress UP."""
    for cell in ("c8_short", "c8_2k", "c32_short", "c32_2k"):
        assert bench_check._direction(f"serve_{cell}_p50_ttft_ms") == "down"
        assert bench_check._direction(f"serve_{cell}_p95_ttft_ms") == "down"
        assert bench_check._direction(f"serve_{cell}_p95_itl_ms") == "down"
    old = {"serve_c32_2k_p95_itl_ms": 120.0, "serve_c32_2k_p95_ttft_ms": 800.0}
    worse = {"serve_c32_2k_p95_itl_ms": 200.0, "serve_c32_2k_p95_ttft_ms": 1200.0}
    result = bench_check.compare(old, worse)
    assert {r["metric"] for r in result["regressions"]} == set(old)
    better = {"serve_c32_2k_p95_itl_ms": 60.0, "serve_c32_2k_p95_ttft_ms": 500.0}
    result = bench_check.compare(old, better)
    assert {r["metric"] for r in result["improvements"]} == set(old)


def test_skipped_matrix_cells_not_missing(tmp_path):
    """A matrix cell the new run INTENTIONALLY skipped (its
    `serve_<cell>_skipped` marker is recorded) must not be flagged as a
    silently-vanished metric; an uncovered absence still is."""
    old = {"serve_c8_short_p50_ttft_ms": 150.0,
           "serve_c8_short_p95_itl_ms": 90.0,
           "serve_c32_2k_p95_ttft_ms": 900.0,
           "serve_p50_ttft_ms": 250.0}
    new = {"serve_c8_short_skipped": True,
           "serve_c32_2k_p95_ttft_ms": 850.0,
           "serve_p50_ttft_ms": 240.0}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["skipped"]} == {
        "serve_c8_short_p50_ttft_ms", "serve_c8_short_p95_itl_ms"}
    assert not result["missing"] and not result["regressions"]
    # a false marker covers nothing
    new_false = dict(new, serve_c8_short_skipped=False)
    result = bench_check.compare(old, new_false)
    assert {r["metric"] for r in result["missing"]} == {
        "serve_c8_short_p50_ttft_ms", "serve_c8_short_p95_itl_ms"}
    # and an absence without a marker still fails the CLI
    import json

    o, n = tmp_path / "o.json", tmp_path / "n.json"
    o.write_text(json.dumps(old))
    n.write_text(json.dumps(new))
    assert bench_check.main([str(o), str(n)]) == 0   # skipped: clean exit
    n.write_text(json.dumps({k: v for k, v in new.items()
                             if not k.endswith("_skipped")}))
    assert bench_check.main([str(o), str(n)]) == 1   # vanished: fails


def test_recovery_metrics_directions():
    """ISSUE 9 satellite: recovery SLOs are lower-better — seconds via
    the `_s` suffix, checkpoint lag via the new `_lag_steps` suffix, and
    failed-request counts via the `failed` substring."""
    assert bench_check._direction("recovery_train_resume_s") == "down"
    assert bench_check._direction("recovery_serve_reroute_s") == "down"
    assert bench_check._direction("recovery_ckpt_lag_steps") == "down"
    assert bench_check._direction("recovery_serve_failed_requests") == "down"
    old = {"recovery_train_resume_s": 2.0, "recovery_ckpt_lag_steps": 1.0}
    worse = {"recovery_train_resume_s": 4.0, "recovery_ckpt_lag_steps": 3.0}
    result = bench_check.compare(old, worse)
    assert {r["metric"] for r in result["regressions"]} == set(old)
    better = {"recovery_train_resume_s": 1.0, "recovery_ckpt_lag_steps": 0.0}
    result = bench_check.compare(old, better)
    # lag going to 0 is fine (0-new never regresses a lower-better)
    assert not result["regressions"]


def test_recovery_skip_markers_honored():
    """A recovery scenario that cannot run records `<metric>_skipped`
    markers — routed to the non-failing skipped bucket, exactly like the
    serve matrix cells; an uncovered absence still fails."""
    old = {"recovery_train_resume_s": 2.0, "recovery_serve_reroute_s": 0.8,
           "recovery_ckpt_lag_steps": 1.0}
    new = {"recovery_serve_reroute_s": 0.7,
           "recovery_train_resume_s_skipped": True,
           "recovery_ckpt_lag_steps_skipped": True}
    result = bench_check.compare(old, new)
    assert not result["missing"] and not result["regressions"]
    assert {r["metric"] for r in result["skipped"]} == {
        "recovery_train_resume_s", "recovery_ckpt_lag_steps"}
    # marker gone -> the absence is a failure again
    bare = {"recovery_serve_reroute_s": 0.7}
    result = bench_check.compare(old, bare)
    assert {r["metric"] for r in result["missing"]} == {
        "recovery_train_resume_s", "recovery_ckpt_lag_steps"}


def test_prefix_hit_rate_direction():
    # higher-better: more prompt pages served from the prefix cache
    assert bench_check._direction("serve_prefix_cache_hit_rate") == "up"
    assert bench_check._direction("serve_prefix_affinity_hit_rate") == "up"
    assert bench_check._direction("serve_prefill_suffix_frac") == "up"


def test_hit_rate_and_frac_compare_in_points():
    """ISSUE 10 satellite: 0-1 rate metrics (_hit_rate/_frac) compare
    higher-better in POINTS — small absolute moves on a tiny base are
    noise, big point drops fail, and a 0 -> positive move improves
    (the relative path would have skipped ov == 0 entirely)."""
    old = {"serve_prefix_cache_hit_rate": 0.02,
           "serve_prefix_affinity_hit_rate": 0.90}
    # 0.02 -> 0.01 is a -50% relative move but only -1 point: OK
    result = bench_check.compare(
        old, {"serve_prefix_cache_hit_rate": 0.01,
              "serve_prefix_affinity_hit_rate": 0.89})
    assert not result["regressions"] and not result["missing"]
    # a real point collapse regresses
    result = bench_check.compare(
        old, {"serve_prefix_cache_hit_rate": 0.02,
              "serve_prefix_affinity_hit_rate": 0.45})
    assert [r["metric"] for r in result["regressions"]] == [
        "serve_prefix_affinity_hit_rate"]
    assert result["regressions"][0]["change"] == -0.45
    # 0 -> 0.5 is an improvement, not an ov==0 skip
    result = bench_check.compare({"serve_prefix_cache_hit_rate": 0.0},
                                 {"serve_prefix_cache_hit_rate": 0.5})
    assert [r["metric"] for r in result["improvements"]] == [
        "serve_prefix_cache_hit_rate"]
    # skip markers cover rates too
    result = bench_check.compare(
        old, {"serve_prefix_cache_hit_rate_skipped": True,
              "serve_prefix_affinity_hit_rate": 0.9})
    assert not result["missing"]
    assert [r["metric"] for r in result["skipped"]] == [
        "serve_prefix_cache_hit_rate"]


def test_cached_cold_ttft_directions_and_markers():
    """The cached/cold serve TTFT cells are _ms lower-better metrics and
    honor their skip markers."""
    assert bench_check._direction("serve_ttft_cached_ms") == "down"
    assert bench_check._direction("serve_ttft_cold_ms") == "down"
    old = {"serve_ttft_cached_ms": 80.0, "serve_ttft_cold_ms": 400.0}
    result = bench_check.compare(old, {"serve_ttft_cached_ms": 300.0,
                                       "serve_ttft_cold_ms": 410.0})
    assert [r["metric"] for r in result["regressions"]] == [
        "serve_ttft_cached_ms"]
    result = bench_check.compare(old, {"serve_ttft_cached_skipped": True,
                                       "serve_ttft_cold_skipped": True})
    assert not result["missing"]
    assert {r["metric"] for r in result["skipped"]} == set(old)


def test_lower_better_regresses_up():
    old = {"serve_p50_ttft_ms": 272.1}
    new = {"serve_p50_ttft_ms": 320.0}
    result = bench_check.compare(old, new)
    assert [r["metric"] for r in result["regressions"]] == ["serve_p50_ttft_ms"]
    # and an improvement in latency is an improvement
    result = bench_check.compare(old, {"serve_p50_ttft_ms": 200.0})
    assert [r["metric"] for r in result["improvements"]] == ["serve_p50_ttft_ms"]


def test_cli_exit_codes_and_wrapper_format(tmp_path):
    """Accepts both bare metrics and the driver's BENCH_rNN wrapper;
    exit 1 on regression, 0 when clean."""
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(
        {"n": 4, "cmd": "python bench.py", "rc": 0,
         "parsed": {"flash_fwdbwd_tflops_s4096": 26.16}}))
    new.write_text(json.dumps({"flash_fwdbwd_tflops_s4096": 22.99}))
    assert bench_check.main([str(old), str(new)]) == 1
    # within a generous threshold the same pair passes
    assert bench_check.main([str(old), str(new), "--threshold", "0.2"]) == 0
    new.write_text(json.dumps({"flash_fwdbwd_tflops_s4096": 26.5}))
    assert bench_check.main([str(old), str(new)]) == 0
    assert bench_check.main([str(old)]) == 2  # usage error


def test_latest_bench_json(tmp_path):
    assert bench_check.latest_bench_json(str(tmp_path)) is None
    (tmp_path / "BENCH_r04.json").write_text("{}")
    (tmp_path / "BENCH_r05.json").write_text("{}")
    latest = bench_check.latest_bench_json(str(tmp_path))
    assert latest is not None and latest.endswith("BENCH_r05.json")


def test_migration_metrics_directions_and_markers():
    """Round-11 KV-migration cells: migrated TTFT is lower-better,
    kv_migration_mb_s is a throughput (the `_mb_s` suffix must trump
    the `_s` lower-better suffix), and the skip markers route chip-box
    absences to the non-failing skipped bucket."""
    assert bench_check._direction("serve_ttft_migrated_ms") == "down"
    assert bench_check._direction("serve_ttft_cold_ms") == "down"
    assert bench_check._direction("kv_migration_mb_s") == "up"
    assert bench_check._direction("serve_spill_migrations") == "up"

    old = {"serve_ttft_migrated_ms": 50.0, "serve_ttft_cold_ms": 300.0,
           "kv_migration_mb_s": 60.0}
    # regressions in the right directions
    worse = {"serve_ttft_migrated_ms": 80.0, "serve_ttft_cold_ms": 310.0,
             "kv_migration_mb_s": 20.0}
    result = bench_check.compare(old, worse)
    names = {r["metric"] for r in result["regressions"]}
    assert "serve_ttft_migrated_ms" in names
    assert "kv_migration_mb_s" in names
    # skip markers: intentionally absent cells are not "missing"
    skipped = {"serve_ttft_migrated_skipped": True,
               "kv_migration_mb_s_skipped": True,
               "serve_ttft_cold_ms": 290.0}
    result = bench_check.compare(old, skipped)
    assert not result["missing"]
    assert {r["metric"] for r in result["skipped"]} == {
        "serve_ttft_migrated_ms", "kv_migration_mb_s"}


def test_overload_metrics_directions_and_markers():
    """Round-12 overload cells (ISSUE 12 satellite): goodput fractions
    compare higher-better in POINTS (the `_frac` suffix), the shed
    fast-fail latency is lower-better (the `fast_fail` substring — an
    honest rejection must stay cheap), and the shed/expired COUNTS are
    bookkeeping (protection ON sheds more than the unprotected baseline
    by design, so neither direction is a regression)."""
    assert bench_check._direction("serve_goodput_frac") == "up"
    assert bench_check._direction("serve_goodput_frac_unprotected") == "up"
    assert bench_check._direction("serve_shed_fast_fail_p95_ms") == "down"
    assert bench_check._direction("serve_admitted_p95_ttft_ms") == "down"
    assert not bench_check._tracked("serve_shed_requests", 12)
    assert not bench_check._tracked("serve_deadline_expired", 3)
    assert not bench_check._tracked("serve_overload_offered", 160)
    assert not bench_check._tracked("serve_overload_completed", 80)
    assert not bench_check._tracked("serve_capacity_rps_cfg", 9.5)

    old = {"serve_goodput_frac": 0.62, "serve_shed_fast_fail_p95_ms": 40.0,
           "serve_admitted_p95_ttft_ms": 600.0, "serve_shed_requests": 50}
    # goodput collapse is a POINTS regression; slow sheds regress UP
    worse = {"serve_goodput_frac": 0.31,
             "serve_shed_fast_fail_p95_ms": 400.0,
             "serve_admitted_p95_ttft_ms": 2500.0,
             "serve_shed_requests": 5}
    result = bench_check.compare(old, worse)
    names = {r["metric"] for r in result["regressions"]}
    assert names == {"serve_goodput_frac", "serve_shed_fast_fail_p95_ms",
                     "serve_admitted_p95_ttft_ms"}
    # a goodput wobble inside the point budget is noise, not a 10%+ move
    result = bench_check.compare({"serve_goodput_frac": 0.62},
                                 {"serve_goodput_frac": 0.55})
    assert not result["regressions"]


def test_overload_skip_markers_honored():
    """`*_skipped` markers: the overload cells read as intentionally
    skipped, never as silently vanished (one marker covers the
    `_unprotected` variant of its metric too)."""
    old = {"serve_goodput_frac": 0.62, "serve_goodput_frac_unprotected": 0.2,
           "serve_shed_fast_fail_p95_ms": 40.0,
           "serve_admitted_p95_ttft_ms": 600.0}
    new = {"serve_goodput_frac_skipped": True,
           "serve_shed_fast_fail_p95_ms_skipped": True,
           "serve_admitted_p95_ttft_ms_skipped": True}
    result = bench_check.compare(old, new)
    assert not result["missing"], result["missing"]
    assert {r["metric"] for r in result["skipped"]} == set(old)


def test_speculative_metrics_directions():
    """Round-13 cells: decode tok/s higher-better, the accept rate is a
    pointwise 0-1 rate, and tokens-per-dispatch (amortized forwards)
    regresses DOWN — plus the audited "_tok_s" shadow: a bare token-
    throughput suffix must not fall into the lower-better "_s" bucket
    (the exact trap _mb_s hit before PR 11)."""
    assert bench_check._direction("decode_tok_s_plain") == "up"
    assert bench_check._direction("decode_tok_s_speculative") == "up"
    assert bench_check._direction("spec_tokens_per_dispatch") == "up"
    assert bench_check._direction("spec_accept_rate") == "up"
    assert bench_check._direction("spec_parity") == "up"
    # the audit find: metrics literally ending in _tok_s were shadowed
    assert bench_check._direction("pp_decode_tok_s") == "up"
    assert bench_check._direction("train_tok_s") == "up"
    # a tokens-per-dispatch slide is a regression, not an improvement
    old = {"spec_tokens_per_dispatch": 2.0, "decode_tok_s_speculative": 400.0}
    new = {"spec_tokens_per_dispatch": 1.1, "decode_tok_s_speculative": 430.0}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["regressions"]} == {
        "spec_tokens_per_dispatch"}


def test_spec_accept_rate_compares_in_points():
    """A 0.9 -> 0.45 accept-rate collapse is a 45-point regression; a
    0.02 -> 0.01 wiggle is noise, not a 50% drop."""
    result = bench_check.compare({"spec_accept_rate": 0.9},
                                 {"spec_accept_rate": 0.45})
    assert [r["metric"] for r in result["regressions"]] == [
        "spec_accept_rate"]
    result2 = bench_check.compare({"spec_accept_rate": 0.02},
                                  {"spec_accept_rate": 0.01})
    assert not result2["regressions"]
    # and 0 -> 0.5 counts as an improvement instead of an ov==0 skip
    result3 = bench_check.compare({"spec_accept_rate": 0.0},
                                  {"spec_accept_rate": 0.5})
    assert [r["metric"] for r in result3["improvements"]] == [
        "spec_accept_rate"]


def test_speculative_skip_markers_honored():
    """`*_skipped` markers: the
    absent cells land in the skipped bucket, never in missing; draft
    volume / dispatch counts are untracked bookkeeping."""
    old = {"decode_tok_s_plain": 600.0, "decode_tok_s_speculative": 380.0,
           "spec_accept_rate": 0.25, "spec_tokens_per_dispatch": 1.6,
           "spec_drafted_tokens": 1100, "spec_dispatches": 60,
           "spec_draft_k_cfg": 6}
    new = {"decode_tok_s_plain_skipped": True,
           "decode_tok_s_speculative_skipped": True,
           "spec_accept_rate_skipped": True,
           "spec_tokens_per_dispatch_skipped": True}
    result = bench_check.compare(old, new)
    assert not result["missing"] and not result["regressions"]
    assert {r["metric"] for r in result["skipped"]} == {
        "decode_tok_s_plain", "decode_tok_s_speculative",
        "spec_accept_rate", "spec_tokens_per_dispatch"}


def test_train_loop_metrics_directions():
    """Round-15 cells: dispatch overhead regresses UP (µs, and the
    "overhead" substring), MFU/overlap-frac are pointwise 0-1
    higher-better, tok/s cells ride the audited _tok_s suffix, and the
    ckpt save-block is a latency. Shadow audit: no train-loop cell ends
    in a bare "_s", so none can fall into the lower-better "_s" bucket
    (the pre-PR-11 _mb_s trap)."""
    assert bench_check._direction("train_step_dispatch_overhead_us") == "down"
    assert bench_check._direction(
        "train_step_dispatch_overhead_eager_us") == "down"
    assert bench_check._direction("train_mfu_eager") == "up"
    assert bench_check._direction("train_mfu_loop") == "up"
    assert bench_check._direction("train_mfu_1b_seq8k") == "up"
    assert bench_check._direction("mfu") == "up"
    assert bench_check._direction("mfu_8b_proxy") == "up"
    assert bench_check._direction("train_ckpt_overlap_frac") == "up"
    assert bench_check._direction("train_loop_tok_s") == "up"
    assert bench_check._direction("train_eager_tok_s") == "up"
    assert bench_check._direction("train_loop_ckpt_save_block_ms") == "down"
    # a dispatch-overhead GROWTH is the regression
    old = {"train_step_dispatch_overhead_us": 300.0,
           "train_ckpt_overlap_frac": 0.75}
    new = {"train_step_dispatch_overhead_us": 900.0,
           "train_ckpt_overlap_frac": 0.78}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["regressions"]} == {
        "train_step_dispatch_overhead_us"}


def test_mfu_compares_in_points():
    """MFU is a 0-1 fraction whose cell tag follows the unit
    (train_mfu_eager), so it is matched by SUBSTRING and compared in
    points: a 0.45 -> 0.30 collapse regresses, a CPU-sandbox
    0.00005 -> 0.00002 wiggle is noise — a relative compare would have
    flagged the wiggle as a 60% regression."""
    result = bench_check.compare({"train_mfu_loop": 0.45},
                                 {"train_mfu_loop": 0.30})
    assert [r["metric"] for r in result["regressions"]] == ["train_mfu_loop"]
    result2 = bench_check.compare({"train_mfu_loop": 5e-05},
                                  {"train_mfu_loop": 2e-05})
    assert not result2["regressions"]
    # config echoes stay untracked bookkeeping
    result3 = bench_check.compare({"train_loop_bench_ticks_cfg": 150},
                                  {"train_loop_bench_ticks_cfg": 50})
    assert not result3["regressions"] and not result3["missing"]


def test_train_loop_skip_markers_honored():
    """The three `*_skipped` markers: every train-loop cell lands in skipped, never missing."""
    old = {"train_step_dispatch_overhead_eager_us": 6400.0,
           "train_step_dispatch_overhead_us": 320.0,
           "train_mfu_eager": 5e-05, "train_mfu_loop": 6e-05,
           "train_ckpt_overlap_frac": 0.75}
    new = {"train_mfu_skipped": True,
           "train_step_dispatch_overhead_skipped": True,
           "train_ckpt_overlap_frac_skipped": True}
    result = bench_check.compare(old, new)
    assert not result["missing"] and not result["regressions"]
    assert {r["metric"] for r in result["skipped"]} == set(old)


def test_tenancy_metrics_directions():
    """Round-16 cells: the quiet-tenant p95 pair and the adapter hot-load
    are latencies ("_ms", plus the "ttft" substring on the p95 pair),
    goodput fractions are pointwise 0-1, and both parity cells ride the
    "_parity" suffix (1.0-or-broken invariants). Shadow audit: no
    tenancy cell ends in a bare "_s", so the lower-better "_s" bucket
    (the pre-PR-11 _mb_s trap) cannot shadow any of them."""
    assert bench_check._direction("tenant_quiet_p95_ttft_ms_solo") == "down"
    assert bench_check._direction("tenant_quiet_p95_ttft_ms_noisy") == "down"
    assert bench_check._direction("adapter_hot_load_ms") == "down"
    assert bench_check._direction("tenant_goodput_frac_hot") == "up"
    assert bench_check._direction("tenant_goodput_frac_cold") == "up"
    assert bench_check._direction("tenant_mixed_batch_parity") == "up"
    assert bench_check._direction("tenant_mixed_dispatch_parity") == "up"
    # a quiet-p95 GROWTH under the noisy storm is the regression the
    # isolation cells exist to catch
    old = {"tenant_quiet_p95_ttft_ms_noisy": 80.0,
           "tenant_goodput_frac_hot": 0.9}
    new = {"tenant_quiet_p95_ttft_ms_noisy": 160.0,
           "tenant_goodput_frac_hot": 0.92}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["regressions"]} == {
        "tenant_quiet_p95_ttft_ms_noisy"}


def test_tenancy_parity_and_goodput_compare_in_points():
    """A parity cell slipping 1.0 -> 0.0 (mixed batch no longer byte-
    identical) is a 100-point regression; a goodput 0.05 -> 0.04 wiggle
    is noise, not a 20% drop. Dispatch counts and storm sizes are _cfg
    bookkeeping, never tracked."""
    result = bench_check.compare({"tenant_mixed_batch_parity": 1.0},
                                 {"tenant_mixed_batch_parity": 0.0})
    assert [r["metric"] for r in result["regressions"]] == [
        "tenant_mixed_batch_parity"]
    result2 = bench_check.compare({"tenant_goodput_frac_cold": 0.05},
                                  {"tenant_goodput_frac_cold": 0.04})
    assert not result2["regressions"]
    result3 = bench_check.compare(
        {"tenant_mixed_decode_dispatches_cfg": 8,
         "tenant_storm_offered_cfg": 64,
         "tenant_noisy_quota_429_cfg": 12},
        {"tenant_mixed_decode_dispatches_cfg": 24,
         "tenant_storm_offered_cfg": 16,
         "tenant_noisy_quota_429_cfg": 0})
    assert not result3["regressions"] and not result3["missing"]


def test_tenancy_skip_markers_honored():
    """`*_skipped` markers: every tenancy cell lands in skipped, never
    missing (a marker covers the `_solo`/`_noisy`, `_hot`/`_cold`
    variants of its metric)."""
    old = {"tenant_quiet_p95_ttft_ms_solo": 60.0,
           "tenant_quiet_p95_ttft_ms_noisy": 66.0,
           "tenant_goodput_frac_hot": 0.9,
           "tenant_goodput_frac_cold": 0.7,
           "tenant_mixed_batch_parity": 1.0,
           "tenant_mixed_dispatch_parity": 1.0,
           "adapter_hot_load_ms": 50.0}
    new = {"tenant_quiet_p95_ttft_ms_skipped": True,
           "tenant_goodput_frac_skipped": True,
           "tenant_mixed_batch_parity_skipped": True,
           "tenant_mixed_dispatch_parity_skipped": True,
           "adapter_hot_load_ms_skipped": True}
    result = bench_check.compare(old, new)
    assert not result["missing"] and not result["regressions"]
    assert {r["metric"] for r in result["skipped"]} == set(old)


def test_round18_obs_metric_directions():
    """Round-18 shadow-suffix audit: pointwise cells now carry their own
    direction. Before _POINTWISE_DOWN_SUBSTR, the "_frac" suffix check
    ran ahead of the "overhead" substring, so the recorder-cost gate
    loop_obs_overhead_frac was guarded BACKWARDS (a cost blowup read as
    an improvement). Stall WAIT splits regress up; compute split stays
    higher-better; raw per-tick cells end in "_us" (lower-better)."""
    assert bench_check._pointwise("loop_obs_overhead_frac")
    assert bench_check._direction("loop_obs_overhead_frac") == "down"
    assert bench_check._direction("dag_loop_stall_wait_up_frac") == "down"
    assert bench_check._direction("dag_loop_stall_wait_down_frac") == "down"
    assert bench_check._direction("dag_loop_stall_compute_frac") == "up"
    assert bench_check._direction("loop_obs_tick_recording_us") == "down"
    assert bench_check._direction("loop_obs_tick_baseline_us") == "down"
    # representative earlier names keep their directions (shadow audit)
    assert bench_check._direction("kv_migration_mb_s") == "up"
    assert bench_check._direction("dag_tick_dispatch_overhead_us") == "down"
    assert bench_check._direction("tenant_goodput_frac_hot") == "up"
    assert bench_check._direction("train_ckpt_overlap_frac") == "up"
    assert bench_check._direction("serve_goodput_frac_unprotected") == "up"


def test_obs_overhead_frac_regresses_up_in_points():
    """The recorder-cost fraction compares in POINTS and lower-better:
    0.01 -> 0.18 is a 17-point cost blowup (regression); the inverse is
    an improvement; a 2-point compute-frac wiggle stays within budget."""
    old = {"loop_obs_overhead_frac": 0.01,
           "dag_loop_stall_wait_up_frac": 0.20,
           "dag_loop_stall_compute_frac": 0.60}
    new = {"loop_obs_overhead_frac": 0.18,
           "dag_loop_stall_wait_up_frac": 0.35,
           "dag_loop_stall_compute_frac": 0.58}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["regressions"]} == {
        "loop_obs_overhead_frac", "dag_loop_stall_wait_up_frac"}
    assert {r["metric"] for r in result["ok"]} == {
        "dag_loop_stall_compute_frac"}
    result2 = bench_check.compare(
        {"loop_obs_overhead_frac": 0.18}, {"loop_obs_overhead_frac": 0.01})
    assert {r["metric"] for r in result2["improvements"]} == {
        "loop_obs_overhead_frac"}


def test_fleet_metrics_directions():
    """Round-19 cells: standby promote and cold start are wall-clock
    seconds (the bare "_s" suffix, lower-better), the promote speedup is
    a ratio (higher-better default), broadcast parity rides the
    "_parity" suffix and the step goodput the "goodput_frac" substring —
    both pointwise 0-1 higher-better. Shadow audit: "speedup" must NOT
    fall into the lower-better "_s" bucket."""
    assert bench_check._direction("serve_replica_cold_start_s") == "down"
    assert bench_check._direction("serve_replica_promote_s") == "down"
    assert bench_check._direction("serve_replica_promote_speedup") == "up"
    assert bench_check._pointwise("fleet_broadcast_parity")
    assert bench_check._direction("fleet_broadcast_parity") == "up"
    assert bench_check._pointwise("fleet_goodput_frac_step")
    assert bench_check._direction("fleet_goodput_frac_step") == "up"
    # A promote-time blowup (warm pool no longer warm) and a speedup
    # collapse are exactly the regressions these cells exist to catch.
    old = {"serve_replica_promote_s": 0.005,
           "serve_replica_promote_speedup": 600.0}
    new = {"serve_replica_promote_s": 0.5,
           "serve_replica_promote_speedup": 7.0}
    result = bench_check.compare(old, new)
    assert {r["metric"] for r in result["regressions"]} == set(old)


def test_fleet_parity_and_goodput_compare_in_points():
    """Parity 1.0 -> 0.0 (broadcast no longer byte-identical) is a
    100-point regression; a small goodput wiggle through the step is
    noise; warm-pool/step bookkeeping (_cfg) is never tracked."""
    result = bench_check.compare({"fleet_broadcast_parity": 1.0},
                                 {"fleet_broadcast_parity": 0.0})
    assert [r["metric"] for r in result["regressions"]] == [
        "fleet_broadcast_parity"]
    result2 = bench_check.compare({"fleet_goodput_frac_step": 0.30},
                                  {"fleet_goodput_frac_step": 0.27})
    assert not result2["regressions"]
    result3 = bench_check.compare(
        {"fleet_standby_warm_cfg": True, "fleet_step_offered_cfg": 24,
         "fleet_step_promote_path_cfg": "host",
         "fleet_broadcast_bytes_cfg": 429137, "fleet_step_running_cfg": 2},
        {"fleet_step_offered_cfg": 12})
    assert not result3["regressions"] and not result3["missing"]


def test_fleet_skip_markers_honored():
    """The `fleet_skipped` prefix marker covers every fleet_* cell and
    the per-metric markers cover the serve_replica_* cells — skipped,
    never missing."""
    old = {"serve_replica_cold_start_s": 3.4,
           "serve_replica_promote_s": 0.004,
           "serve_replica_promote_speedup": 800.0,
           "fleet_broadcast_parity": 1.0,
           "fleet_goodput_frac_step": 0.3}
    new = {"fleet_skipped": True,
           "serve_replica_cold_start_s_skipped": True,
           "serve_replica_promote_s_skipped": True,
           "serve_replica_promote_speedup_skipped": True}
    result = bench_check.compare(old, new)
    assert not result["missing"] and not result["regressions"]
    assert {r["metric"] for r in result["skipped"]} == set(old)
