"""Compiled-loop training (round 15): the structured step spec rides the
persistent graph (``train/loop.py``) — loop-vs-eager byte parity,
checkpoint-commit overlap, and chaos-killed stage recovery from the
GCS-registered async checkpoint."""

import os

import pytest

from ray_tpu.train import (
    DataParallelTrainer,
    FailureConfig,
    RunConfig,
    ScalingConfig,
    TrainLoopConfig,
)


def _make_fns(slow=False):
    """Closure-built step spec fns: cloudpickle ships closures by VALUE,
    so the stage actors never need this test module importable."""
    import numpy as np

    def init_fn(config):
        rng = np.random.default_rng(config.get("seed", 0))
        return {"w": rng.standard_normal(config.get("dim", 64)), "count": 0}

    def data_fn(config):
        def gen():
            rng = np.random.default_rng(123)
            while True:
                yield rng.standard_normal(config.get("dim", 64))
        return gen()

    def step_fn(state, batch):
        if slow:
            import time

            time.sleep(0.05)
        w = state["w"] - 0.01 * (state["w"] - batch)
        count = state["count"] + 1
        loss = float(np.square(w - batch).mean())
        return ({"w": w, "count": count},
                {"loss": loss, "step": count - 1, "count": count})

    return init_fn, data_fn, step_fn


def _spec(num_steps=6, snapshot_every=2, hook=None, slow=False, credits=2):
    init_fn, data_fn, step_fn = _make_fns(slow=slow)
    return TrainLoopConfig(
        step_fn=step_fn, init_fn=init_fn, data_fn=data_fn,
        num_steps=num_steps, snapshot_every=snapshot_every,
        credits=credits, stage_init_hook=hook)


def _fit(tmp_path, name, use_loop, spec, max_failures=0, config=None):
    trainer = DataParallelTrainer(
        spec,
        train_loop_config=config or {"seed": 7},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name=name, storage_path=str(tmp_path),
                             failure_config=FailureConfig(
                                 max_failures=max_failures)),
        use_compiled_loop=use_loop,
    )
    return trainer.fit()


def test_loop_vs_eager_byte_parity(ray_cluster, tmp_path):
    """The parity contract: both drive modes run the SAME stage actors
    in the SAME order, so at a fixed seed the step metrics AND the final
    committed state are byte-identical — the compiled loop changes the
    dispatch path, never the math."""
    from ray_tpu.resilience.checkpoint import load_checkpoint

    spec_e = _spec(num_steps=6, snapshot_every=2)
    spec_l = _spec(num_steps=6, snapshot_every=2)
    res_e = _fit(tmp_path, "tl_parity_eager", False, spec_e)
    res_l = _fit(tmp_path, "tl_parity_loop", True, spec_l)
    assert res_e.error is None, res_e.error
    assert res_l.error is None, res_l.error
    assert len(res_e.metrics_history) == 6
    # metrics byte-identical, step for step
    assert res_l.metrics_history == res_e.metrics_history
    assert res_e.loop_stats["mode"] == "eager"
    assert res_l.loop_stats["mode"] == "loop"
    # final committed state byte-identical
    assert res_e.checkpoint is not None and res_l.checkpoint is not None
    tree_e, meta_e = load_checkpoint(res_e.checkpoint.path)
    tree_l, meta_l = load_checkpoint(res_l.checkpoint.path)
    assert meta_e["step"] == meta_l["step"] == 5
    assert tree_e["count"] == tree_l["count"] == 6
    assert tree_e["w"].tobytes() == tree_l["w"].tobytes()


def _record_windows(monkeypatch):
    """Keep what each drive reports per step: ``(step_window,
    ckpt_window)`` on the host's clock, by mode. The controller runs in
    this process, so its runner can be wrapped here."""
    from ray_tpu.train import controller as controller_mod

    runs: dict[str, list] = {}

    class Recording(controller_mod.TrainLoopRunner):
        def run(self, on_report):
            mode = "loop" if self.use_compiled_loop else "eager"

            def tee(entry):
                runs.setdefault(mode, []).append(
                    (tuple(entry["step_window"]),
                     tuple(entry["ckpt_window"])))
                on_report(entry)

            return super().run(tee)

    monkeypatch.setattr(controller_mod, "TrainLoopRunner", Recording)
    return runs


def _steps_started_during_previous_commit(windows) -> int:
    return sum(1 for (_, commit), (step, _) in zip(windows, windows[1:])
               if step[0] < commit[1])


def test_ckpt_commit_overlaps_compute(ray_cluster, tmp_path, monkeypatch):
    """The checkpoint stage commits while the step stage computes the
    NEXT steps (pipelined over the ring credits), while the eager drive —
    one serialized dispatch chain per step — cannot. Asserted on the
    ORDER of what the stages stamped, never on how long anything took:
    under a loaded host a commit may take seconds and that is no fault."""
    runs = _record_windows(monkeypatch)
    cfg = {"seed": 7, "dim": 1 << 18}  # ~2 MB f64 state: a real commit
    spec_l = _spec(num_steps=6, snapshot_every=1, slow=True, credits=4)
    res_l = _fit(tmp_path, "tl_overlap_loop", True, spec_l, config=cfg)
    assert res_l.error is None, res_l.error
    stats = res_l.loop_stats
    assert stats["ckpt_commits"] == 6
    loop = runs["loop"]
    assert len(loop) == 6
    # dataflow: a commit starts after the step that made its snapshot
    # ended, and commits are serial, in step order
    for step, commit in loop:
        assert step[0] <= step[1] <= commit[0] <= commit[1], loop
    for (_, commit), (_, later) in zip(loop, loop[1:]):
        assert commit[1] <= later[0], loop
    # The step never blocked on the write: a later step STARTED while
    # the commit before it was still going (replaces, and is stronger
    # than, `ckpt_save_block_ms < 1000.0`, which bounded a duration).
    assert _steps_started_during_previous_commit(loop) >= 1, loop
    assert stats["train_ckpt_overlap_frac"] is not None
    assert stats["train_ckpt_overlap_frac"] > 0.0, stats

    spec_e = _spec(num_steps=6, snapshot_every=1, slow=True, credits=4)
    res_e = _fit(tmp_path, "tl_overlap_eager", False, spec_e, config=cfg)
    assert res_e.error is None, res_e.error
    # eager serializes commit against the next dispatch: every step
    # starts after the commit before it ended, so zero overlap, exactly
    eager = runs["eager"]
    assert len(eager) == 6
    assert _steps_started_during_previous_commit(eager) == 0, eager
    assert res_e.loop_stats["train_ckpt_overlap_frac"] == 0.0


def _chaos_hook(marker_path):
    def hook(stage_name, config):
        if stage_name != "step" or os.path.exists(marker_path):
            return
        open(marker_path, "w").write("x")
        from ray_tpu import chaos as _chaos

        plan = {"name": "train-step-kill", "faults": [
            {"kind": "kill_loop_stage", "nth": 4, "max_injections": 1}]}
        _chaos.install(_chaos.FaultPlan.from_dict(plan), 0, publish=False)
    return hook


@pytest.mark.chaos
def test_step_stage_death_resumes_from_gcs_ckpt(ray_cluster, tmp_path):
    """kill_loop_stage fired inside the TRAIN-STEP stage mid-run: the
    loop tears down within the dag-loop cascade bounds, the controller's
    failure policy restarts the stage group, and the resumed attempt
    continues from the latest GCS-registered async checkpoint — the
    ckpt lag is bounded by snapshot_every + the in-flight credit window.
    RecoveryVerifier must come back green."""
    from ray_tpu.chaos.verifier import RecoveryVerifier

    verifier = RecoveryVerifier(timeout_s=60)
    baseline = verifier.snapshot_baseline()

    marker = str(tmp_path / "chaos_installed_once")
    spec = _spec(num_steps=8, snapshot_every=1, hook=_chaos_hook(marker),
                 credits=2)
    res = _fit(tmp_path, "tl_chaos", True, spec, max_failures=1)
    assert res.error is None, res.error
    # the run completed all 8 global steps across the two attempts:
    # `count` rides the checkpointed state, so a lossless resume ends at
    # exactly 8 regardless of how many steps replayed
    assert res.metrics_history[-1]["count"] == 8
    # exactly one recovery, stamped and resumed
    assert len(res.recovery_events) == 1
    ev = res.recovery_events[0]
    assert ev["resume_path"], "resume did not come from a registered ckpt"
    assert ev["resumed_clock"] is not None
    # ckpt lag bound: the kill fired at the 4th step tick (steps 0-2
    # complete); the committed horizon can trail by at most the credit
    # window, so the resumed attempt restarts no earlier than step 1
    assert ev["resume_step"] is not None and ev["resume_step"] >= 1, ev
    assert ev["resume_step"] <= 4, ev
    result = verifier.verify(baseline)
    assert result.ok, result.violations


def test_loop_spec_requires_structured_mode(ray_cluster, tmp_path):
    """A closure train_fn with use_compiled_loop=True is ignored (eager
    closure mode stays the default fallback path untouched)."""
    from ray_tpu import train

    def train_fn(config):
        train.report({"ok": 1})

    trainer = DataParallelTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="tl_closure", storage_path=str(tmp_path)),
        use_compiled_loop=True,
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics == {"ok": 1}
    assert result.loop_stats is None
