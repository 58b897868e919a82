#!/usr/bin/env python3
"""The quickest proof that ray_tpu's two main paths start on the chip.

    python chip_smoke.py             # one TPU chip: train, then serve
    python chip_smoke.py --chips 4   # one four-chip host: the cross-chip paths only

Default (what the driver runs): ``JaxTrainer.fit`` takes a few optimizer
steps of full-width llama3-1b (batch 8 x 2048, no depth cut, seeded random
weights) in a worker that leased ``{"TPU": 1}``; then
``serve.run(build_llm_app("llama3-1b", ...))`` answers ``/v1/completions``
through the HTTP proxy from a replica that leased the same chip. Each phase
checks what came out (platform, finite falling loss, token counts, greedy
determinism, the Pallas kernels compiled natively) and any failure exits
non-zero. The last line of stdout is the contract's
``{"ok": true, "device": {"platform", "kind", "count"}}`` as the worker
that held the chip reported it.

This process is the ray_tpu driver and never opens the chip: ``main``
pins it to the CPU before anything imports jax, and only workers that
lease ``TPU`` (pin removed through their runtime env) touch a device.
With no ``TPU`` resource in the cluster it exits at once.

The phase functions take the model preset, sizes and the EXPECTED
platform as arguments so ``tests/test_chip_smoke.py`` can drive them at
``debug-128`` on the CPU; the command line has no way to accept a CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
import urllib.request

PRESET = "llama3-1b"
TRAIN = {"batch": 8, "seq": 2048, "steps": 4}
SERVE = {"max_slots": 8, "max_len": 2560, "page_size": 64,
         "prefill_chunk_size": 256, "decode_steps_per_dispatch": 32,
         "attention_impl": "auto"}
# --chips 4: |loss(fsdp=2 x tp=2) - loss(one device)| at every step. bf16
# params, f32 loss; the two differ in reduction order only (1e-4 at loss
# ~12 on a v5e 2x2, 1e-3 at debug size on virtual CPU devices).
MESH_LOSS_ATOL = 0.01
TIME_LIMIT_S = 1150  # the contract's 1200 s, with room to stop the cluster


class SmokeFailure(Exception):
    """A phase ran but what came out is wrong."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _lease(platform: str, chips: int) -> tuple[dict, dict | None]:
    """(resources, runtime_env) of a worker that computes on ``platform``.
    A chip worker leases ``TPU`` and drops the CPU pin every worker
    otherwise starts with."""
    if platform == "tpu":
        return ({"CPU": 1, "TPU": chips},
                {"env_vars": {"JAX_PLATFORMS": None}})
    return {"CPU": 1}, None


def _check_device(device: dict, platform: str, count: int) -> None:
    """The worker saw ``platform``, and on the chip exactly the chips it
    leased (a CPU worker's device count is whatever XLA_FLAGS gives it)."""
    _check(device["platform"] == platform
           and (device["count"] == count or platform != "tpu"),
           f"worker ran on {device['count']} x {device['platform']} "
           f"({device['kind']}), expected {count} x {platform}")


def _check_kernel(traces: dict, kernel: str, platform: str) -> None:
    """On the chip ``kernel`` was traced, and only ever to its native
    Pallas lowering (never the interpreter, never a reference swap);
    anywhere else it was never lowered natively."""
    native = traces.get(f"{kernel}:pallas", 0)
    other = {k: v for k, v in traces.items()
             if k.startswith(kernel + ":") and k != f"{kernel}:pallas"}
    if platform == "tpu":
        _check(native > 0 and not other,
               f"{kernel} did not compile natively on the chip: {traces}")
    else:
        _check(native == 0, f"{kernel} lowered natively off-chip: {traces}")


def _await_chips_returned(total: float, timeout_s: float = 120.0) -> None:
    """The raylet hands ``TPU`` back only once the worker that held it is
    dead; wait for that before the next phase leases the chip."""
    import ray_tpu

    if not total:
        return
    deadline = time.monotonic() + timeout_s
    while ray_tpu.available_resources().get("TPU", 0.0) < total:
        _check(time.monotonic() < deadline,
               f"chip not returned {timeout_s:.0f}s after its phase ended")
        time.sleep(0.5)


# --------------------------------------------------------------- train
def _make_train_step(cfg, mesh, opt):
    import functools

    import jax
    import optax

    from ray_tpu.models import loss_fn

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, mesh=mesh, chunk_tokens=2048)
        )(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step


def _run_steps(cfg, mesh, tokens, steps: int, seed: int) -> dict:
    """Place seeded params on ``mesh``, compile the train step ahead of
    time (so the program text can be searched for the flash kernels) and
    take ``steps`` optimizer steps on the one batch."""
    import jax
    import optax

    from ray_tpu.models import init_params, param_axes
    from ray_tpu.parallel.sharding import logical_sharding, shard_params

    params = shard_params(init_params(cfg, jax.random.PRNGKey(seed)),
                          param_axes(cfg), mesh)
    opt = optax.adafactor(1e-3)
    opt_state = jax.jit(opt.init)(params)
    # per parameter: its shape, its bytes, and the bytes each device holds
    placement = [(leaf.shape, leaf.nbytes,
                  {s.device.id: s.data.nbytes for s in leaf.addressable_shards})
                 for leaf in jax.tree.leaves(params)]
    bytes_in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                    for d in mesh.devices.flat]
    batch = {"tokens": jax.device_put(
        tokens, logical_sharding(mesh, ("batch", None)))}
    t0 = time.perf_counter()
    compiled = _make_train_step(cfg, mesh, opt).lower(
        params, opt_state, batch).compile()
    compile_s = time.perf_counter() - t0
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch)
        losses.append(float(jax.device_get(loss)))  # the completion fence
        step_s.append(time.perf_counter() - t0)
    return {"losses": losses, "step_s": step_s, "compile_s": compile_s,
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
            "placement": placement, "bytes_in_use": bytes_in_use,
            "final_norm": params["final_norm"]}


def _seeded_batch(config: dict):
    """The one batch, through the Data path the trainer hands its workers."""
    import numpy as np

    from ray_tpu import train

    shard = train.get_dataset_shard("train")
    host = next(iter(shard.iter_batches(batch_size=config["batch"],
                                        drop_last=True)))
    return np.asarray(host["tokens"], np.int32)


def _train_loop(config: dict) -> None:
    """Runs in the train worker that leased the chip."""
    import dataclasses
    import tempfile

    from ray_tpu import train
    from ray_tpu.models import PRESETS
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.tpu import device_report, leased_devices
    from ray_tpu.train import Checkpoint, save_pytree

    cfg = dataclasses.replace(PRESETS[config["preset"]], remat_policy="attn")
    mesh = create_mesh(MeshConfig(dp=1), devices=leased_devices()[:1])
    out = _run_steps(cfg, mesh, _seeded_batch(config), config["steps"],
                     config["seed"])
    final_norm = out.pop("final_norm")
    out.pop("placement")
    with tempfile.TemporaryDirectory() as d:
        save_pytree({"step": config["steps"], "final_norm": final_norm}, d)
        train.report({**out, "device": device_report()},
                     checkpoint=Checkpoint.from_directory(d))


def _fit(train_fn, name: str, preset: str, batch: int, seq: int, steps: int,
         seed: int, platform: str, chips: int) -> dict:
    """``JaxTrainer.fit`` of ``train_fn`` on one worker; its last report."""
    import numpy as np

    from ray_tpu import data
    from ray_tpu.models import PRESETS
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # Tokens from --seed, inside THIS model's vocabulary (an id past it is
    # an out-of-range gather: nan loss on the small presets).
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, PRESETS[preset].vocab_size,
                          size=(2 * batch, seq), dtype=np.int32)
    resources, runtime_env = _lease(platform, chips)
    result = JaxTrainer(
        train_fn,
        train_loop_config={"preset": preset, "batch": batch, "seq": seq,
                           "steps": steps, "seed": seed},
        scaling_config=ScalingConfig(num_workers=1,
                                     resources_per_worker=resources,
                                     worker_runtime_env=runtime_env),
        run_config=RunConfig(name=name,
                             storage_path="/tmp/ray_tpu/chip_smoke"),
        datasets={"train": data.from_numpy(tokens, column="tokens")},
    ).fit()
    if result.error is not None:
        raise result.error
    _check(result.metrics is not None, f"{name}: the worker reported nothing")
    return {**result.metrics, "checkpoint": result.checkpoint}


def phase_train(preset: str, *, batch: int, seq: int, steps: int, seed: int,
                platform: str) -> dict:
    t0 = time.monotonic()
    m = _fit(_train_loop, "chip_smoke_train", preset, batch, seq, steps,
             seed, platform, chips=1)
    losses = m["losses"]
    print(f"train: {preset} batch {batch}x{seq}, {steps} steps, loss "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}; compile "
          f"{m['compile_s']:.1f}s, steps {[round(s, 2) for s in m['step_s']]}s, "
          f"peak HBM {max(m['device']['peak_bytes_in_use']) / 2**30:.2f} GiB, "
          f"{m['tpu_custom_calls']} tpu_custom_call; phase "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    _check_device(m["device"], platform, 1)
    _check(all(x == x and abs(x) != float("inf") for x in losses),
           f"train loss not finite: {losses}")
    _check(losses[-1] < losses[0], f"train loss did not fall: {losses}")
    # the compiled step holds the flash kernels iff it ran on the chip
    _check((m["tpu_custom_calls"] > 0) == (platform == "tpu"),
           f"{m['tpu_custom_calls']} tpu_custom_call in the compiled train "
           f"step on {platform}")
    _check_kernel(m["device"]["kernel_traces"], "flash_attention", platform)
    ckpt = m["checkpoint"]
    _check(ckpt is not None and os.path.isdir(ckpt.path)
           and bool(os.listdir(ckpt.path)),
           f"no checkpoint came back through train.report: {ckpt}")
    return m["device"]


# --------------------------------------------------------------- serve
def _complete(addr: str, prompt: str, max_tokens: int, stream: bool):
    """One greedy ``/v1/completions`` request through the HTTP proxy.
    Returns (text, tokens returned)."""
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": stream}).encode()
    req = urllib.request.Request(addr + "/v1/completions", data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        if not stream:
            out = json.loads(resp.read())
            _check("error" not in out, f"completion error: {out}")
            return out["choices"][0]["text"], out["usage"]["completion_tokens"]
        pieces = []
        for line in resp:
            line = line.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                pieces.append(json.loads(line[6:])["choices"][0]["text"])
        return "".join(pieces), len(pieces)


def phase_serve(preset: str, *, engine: dict, n_requests: int,
                max_tokens: int, platform: str) -> dict:
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.util.state import list_errors

    resources, runtime_env = _lease(platform, 1)
    app = build_llm_app(
        preset, **engine,
        ray_actor_options={"resources": resources, "runtime_env": runtime_env})
    t0 = time.monotonic()
    # ONE serve.run, no retry: a first replica that dies is a failure.
    handle = serve.run(app, name="chip-smoke", timeout_s=600.0)
    start_s = time.monotonic() - t0
    status = serve.status()["chip-smoke"]
    failures = ([f"{d}: {st['last_start_failure']}" for d, st in status.items()
                 if st.get("last_start_failure")]
                + [e.get("message", "") for e in
                   list_errors(error_type="replica_start_failure")])
    print(f"serve: replica start {start_s:.1f}s", flush=True)
    _check(not failures, f"replica start failures: {failures}")

    addr = serve.http_address()
    # Shorter than one page: nothing is served from the prefix cache, so
    # the repeated prompt runs the very same programs on the same inputs.
    prompts = [f"chip smoke request {i}: " + "abcdefgh" * (1 + i)
               for i in range(max(1, n_requests - 1))]
    t0 = time.monotonic()
    first = _complete(addr, prompts[0], max_tokens, stream=False)
    print(f"serve: first request (compiles prefill + decode) "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    t0 = time.monotonic()
    # The rest alternate not streamed / streamed. The last one repeats the
    # first prompt, streamed where the first was not.
    rest = prompts[1:] + prompts[:1]
    answers = [first] + [
        _complete(addr, p, max_tokens, stream=(len(rest) - 1 - i) % 2 == 0)
        for i, p in enumerate(rest)]
    counts = [n for _, n in answers]
    print(f"serve: {len(answers)} requests, tokens returned {counts}; "
          f"{len(answers) - 1} warm requests {time.monotonic() - t0:.1f}s",
          flush=True)
    _check(all(n == max_tokens for n in counts),
           f"asked {max_tokens} tokens of every request, got {counts}")
    _check(answers[-1][0] == answers[0][0],
           f"same prompt, different greedy text: {answers[0][0]!r} vs "
           f"{answers[-1][0]!r}")

    m = handle.options(method_name="engine_metrics").remote().result(timeout=60)
    device = m["device"]
    print(f"serve: attention_impl auto -> {m['attention_impl']}, kernel traces "
          f"{device['kernel_traces']}, peak HBM "
          f"{max(device['peak_bytes_in_use']) / 2**30:.2f} GiB", flush=True)
    _check_device(device, platform, 1)
    _check(m["attention_impl"] == ("paged" if platform == "tpu" else "dense"),
           f"attention_impl auto resolved to {m['attention_impl']} on {platform}")
    if platform == "tpu":
        _check_kernel(device["kernel_traces"], "paged_decode_attention", platform)
    serve.shutdown()
    return device


# ------------------------------------------------------ --chips 4 only
def _mesh_train_loop(config: dict) -> None:
    """Three steps on fsdp=2 x tp=2 over the four leased chips, then the
    same three steps on a one-device mesh, in this one worker."""
    import dataclasses

    from ray_tpu import train
    from ray_tpu.models import PRESETS
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.tpu import device_report, leased_devices

    cfg = dataclasses.replace(PRESETS[config["preset"]], remat_policy="attn")
    tokens = _seeded_batch(config)
    devices = leased_devices()
    sharded = _run_steps(cfg, create_mesh(MeshConfig(fsdp=2, tp=2)), tokens,
                         config["steps"], config["seed"])
    single = _run_steps(cfg, create_mesh(MeshConfig(dp=1), devices=devices[:1]),
                        tokens, config["steps"], config["seed"])
    for out in (sharded, single):
        out.pop("final_norm")
    train.report({"sharded": sharded, "single": single,
                  "device": device_report()})


def phase_mesh_train(preset: str, *, batch: int, seq: int, steps: int,
                     seed: int, platform: str) -> dict:
    t0 = time.monotonic()
    m = _fit(_mesh_train_loop, "chip_smoke_mesh", preset, batch, seq, steps,
             seed, platform, chips=4)
    sharded, single = m["sharded"], m["single"]
    diffs = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    print(f"mesh train: fsdp=2 x tp=2 losses {sharded['losses']} vs one "
          f"device {single['losses']} (max |diff| {max(diffs):.4f}, tolerance "
          f"{MESH_LOSS_ATOL}); compile {sharded['compile_s']:.1f}s / "
          f"{single['compile_s']:.1f}s, steps "
          f"{[round(x, 2) for x in sharded['step_s']]}s / "
          f"{[round(x, 2) for x in single['step_s']]}s; bytes_in_use per "
          f"device with params "
          f"and optimizer placed {sharded['bytes_in_use']}; phase "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    _check_device(m["device"], platform, 4)
    _check(max(diffs) <= MESH_LOSS_ATOL,
           f"mesh and one-device losses differ by {max(diffs)}")
    _check((sharded["tpu_custom_calls"] > 0) == (platform == "tpu"),
           f"{sharded['tpu_custom_calls']} tpu_custom_call in the mesh step")
    total = sum(nbytes for _, nbytes, _ in sharded["placement"])
    for shape, _, held in sharded["placement"]:
        _check(len(held) == 4, f"a parameter of shape {shape} sits on {held}")
    first = min(sharded["placement"][0][2])
    on_first = sum(held[first] for _, _, held in sharded["placement"])
    used = sharded["bytes_in_use"]
    print(f"mesh train: {on_first / total:.3f} of the parameter bytes on the "
          f"first device", flush=True)
    _check(on_first <= 0.5 * total,
           f"{on_first} of {total} parameter bytes sit on the first device")
    if platform == "tpu":  # the CPU backend reports no memory statistics
        _check(min(used) > 0 and max(used) <= 1.5 * min(used),
               f"model not spread over four devices: bytes_in_use {used}")
    return m["device"]


def _tp_tokens(preset: str, prompts: list, max_new: int, seed: int) -> dict:
    """Runs in a worker that leased four chips: greedy tokens of the same
    seeded weights from a tensor_parallel=4 engine and a one-device one.

    Both engines run in float32, widths unchanged. In bf16 the two
    programs round partial sums in a different order, and a greedy argmax
    over the 128k near-uniform logits of random weights flips at a near
    tie (seen at token 8 of prompt 0 on four virtual CPU devices); in
    float32 at full matmul precision (no operand is rounded to bf16 on
    the way into the MXU) the order costs ~1e-7, so identical ids are a
    fair demand and a difference means the sharding is wrong."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.engine import InferenceEngine, Request
    from ray_tpu.models import PRESETS, init_params
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.tpu import device_report, leased_devices

    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = dataclasses.replace(PRESETS[preset], dtype=jnp.float32)
    devices = leased_devices()
    out = {}
    for name, mesh in (("tp4", create_mesh(MeshConfig(tp=4))),
                       ("tp1", create_mesh(MeshConfig(tp=1), devices=devices[:1]))):
        eng = InferenceEngine(
            cfg, init_params(cfg, jax.random.PRNGKey(seed)), mesh=mesh,
            max_slots=len(prompts), max_len=256, page_size=64,
            prefill_chunk_size=64, decode_steps_per_dispatch=8, seed=seed)
        reqs = [Request(f"{name}-{i}", list(p), max_new)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        while not all(r.done for r in reqs):
            eng.step()
        out[name] = [list(r.generated) for r in reqs]
        out[name + "_impl"] = eng.attention_impl
    out["device"] = device_report()
    return out


def phase_tp_engine(preset: str, *, n_prompts: int, max_new: int, seed: int,
                    platform: str) -> None:
    import numpy as np

    import ray_tpu
    from ray_tpu.models import PRESETS

    t0 = time.monotonic()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, PRESETS[preset].vocab_size, size=24 + 8 * i).tolist()
               for i in range(n_prompts)]
    resources, runtime_env = _lease(platform, 4)
    task = ray_tpu.remote(resources=resources, runtime_env=runtime_env)(_tp_tokens)
    out = ray_tpu.get(task.remote(preset, prompts, max_new, seed), timeout=900)
    print(f"tp engine: tensor_parallel=4 ({out['tp4_impl']}) {out['tp4']} vs "
          f"one device ({out['tp1_impl']}) {out['tp1']}; phase "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    _check_device(out["device"], platform, 4)
    _check(all(len(t) == max_new for t in out["tp4"] + out["tp1"]),
           "an engine returned fewer tokens than asked")
    _check(out["tp4"] == out["tp1"],
           "tensor_parallel=4 and one-device greedy tokens differ")
    if platform == "tpu":
        _check(out["tp4_impl"] == out["tp1_impl"] == "paged",
               f"engines resolved {out['tp4_impl']}/{out['tp1_impl']}")
        _check_kernel(out["device"]["kernel_traces"], "paged_decode_attention",
                      platform)


class _OneChip:
    """Actor that leases one chip and says what it sees."""

    def look(self) -> dict:
        import jax
        import jax.numpy as jnp

        from ray_tpu.tpu import ENV_VISIBLE_CHIPS, leased_devices

        devices = leased_devices()
        x = jnp.arange(8.0)
        fds = "/proc/self/fd"
        links = [os.readlink(os.path.join(fds, f)) for f in os.listdir(fds)
                 if os.path.islink(os.path.join(fds, f))]
        return {"pid": os.getpid(),
                "visible": os.environ.get(ENV_VISIBLE_CHIPS),
                # the chip's device file, which one process at a time can open
                "open": sorted(f for f in links if f.startswith(
                    ("/dev/vfio/", "/dev/accel")) and f[-1].isdigit()),
                "devices": [(d.platform, d.id, str(getattr(d, "coords", None)))
                            for d in devices],
                "sum": float(jax.device_get((x * x).sum()))}


def phase_two_actors(platform: str) -> None:
    import ray_tpu

    t0 = time.monotonic()
    resources, runtime_env = _lease(platform, 1)
    cls = ray_tpu.remote(resources=resources, runtime_env=runtime_env)(_OneChip)
    actors = [cls.remote(), cls.remote()]
    # both calls in flight at once: each actor holds its chip while the
    # other opens its own
    a, b = ray_tpu.get([x.look.remote() for x in actors], timeout=300)
    print(f"two actors: {a} | {b}; phase {time.monotonic() - t0:.1f}s",
          flush=True)
    for seen in (a, b):
        _check(len(seen["devices"]) == 1 and seen["sum"] == 140.0,
               f"a one-chip actor saw {seen}")
        _check(seen["devices"][0][0] == platform, f"actor ran on {seen}")
    # Each process numbers its one chip 0, so the device ids cannot tell
    # them apart: the lease's chip index and the device file held open do.
    _check(a["pid"] != b["pid"] and a["visible"] != b["visible"]
           and (a["open"] != b["open"] or platform != "tpu"),
           f"two one-chip leases share a chip: {a} | {b}")
    for x in actors:
        ray_tpu.kill(x)


# ---------------------------------------------------------------- main
def _driver_backends() -> list[str]:
    """Backends JAX has created in THIS process (none if jax was never
    imported or never asked for devices)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return sorted(getattr(xb, "_backends", None) or {}) if xb else []


def main(argv: list[str] | None = None) -> int:
    # Before anything imports jax: this process must never open the chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1: train then serve on one chip (default). "
                             "4: only the cross-chip paths of a 4-chip host")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    def _out_of_time(signum, frame):
        raise TimeoutError(f"chip_smoke.py ran past {TIME_LIMIT_S}s")

    signal.signal(signal.SIGALRM, _out_of_time)
    signal.alarm(TIME_LIMIT_S)

    from ray_tpu.native.store import ensure_built
    from ray_tpu.tpu import compile_cache_env

    lib, built = ensure_built()
    print(f"native store: {os.path.basename(lib)} "
          f"{'built from source in this run' if built else 'found, built earlier from this source'}",
          flush=True)
    cache = compile_cache_env(os.environ)  # workers inherit it
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({'cold' if not n_cached else 'warm'}, "
          f"{n_cached} entries)", flush=True)

    import ray_tpu

    t_start = time.monotonic()
    ray_tpu.init(num_cpus=8)
    try:
        chips = ray_tpu.cluster_resources().get("TPU", 0.0)
        print(f"cluster resources: TPU={chips:g}", flush=True)
        if chips < args.chips:
            print(f"chip_smoke: this host exposes {chips:g} TPU chip(s), "
                  f"--chips {args.chips} needs {args.chips}; nothing was run",
                  file=sys.stderr)
            return 1
        if args.chips == 1:
            device = phase_train(PRESET, **TRAIN, seed=args.seed, platform="tpu")
            _await_chips_returned(chips)
            served = phase_serve(PRESET, engine=SERVE, n_requests=5,
                                 max_tokens=48, platform="tpu")
            _check(served["kind"] == device["kind"],
                   f"phases ran on different devices: {device} / {served}")
            _await_chips_returned(chips)
        else:
            device = phase_mesh_train(PRESET, batch=TRAIN["batch"],
                                      seq=TRAIN["seq"], steps=3,
                                      seed=args.seed, platform="tpu")
            _await_chips_returned(chips)
            phase_tp_engine(PRESET, n_prompts=4, max_new=16, seed=args.seed,
                            platform="tpu")
            _await_chips_returned(chips)
            phase_two_actors("tpu")
            _await_chips_returned(chips)
    finally:
        signal.alarm(0)
        ray_tpu.shutdown()
    backends = _driver_backends()
    _check(all(b == "cpu" for b in backends),
           f"the driver process created JAX backends {backends}")
    n_now = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"total {time.monotonic() - t_start:.1f}s; compile cache now "
          f"{n_now} entries; driver JAX backends {backends}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
