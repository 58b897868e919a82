"""Multi-host LLM engine: one inference engine spanning hosts.

Reference: ``python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:117-168`` — the reference places TP×PP vLLM engines across
nodes via placement-group bundles (STRICT_PACK when the engine fits one
node, PACK otherwise). TPU redesign (SURVEY.md §7.1): one
``EngineShardWorker`` actor per host, bootstrapped with
``jax.distributed.initialize`` (the same SPMD↔actor bridge Train uses,
``train/worker_group.py``), each holding a ``LocalEngineExecutor`` built
over the GLOBAL mesh. The engine scheduler stays wherever the Serve
replica lives and fans each step plan out to every shard; every shard
executes the SAME jitted program in the same order, and XLA inserts the
tensor-parallel collectives over ICI/DCN. Only small host arrays (block
tables, token ids) cross the actor boundary — the params and KV pages
never leave the shards.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any

import numpy as np

from ..core import api as ray
from ..observability.tracing import annotate


class EngineShardWorker:
    """Actor hosting one process (one host's chips) of the sharded engine."""

    def __init__(self, rank: int, world: int):
        self.rank = rank
        self.world = world
        self.executor = None

    def coordinator_address(self) -> str:
        from ..parallel.distributed import pick_coordinator_address

        return pick_coordinator_address()

    def init_distributed(self, coordinator: str) -> int:
        from ..parallel.distributed import initialize_process

        return initialize_process(coordinator, self.world, self.rank)

    def build(self, config, *, max_slots: int, num_pages: int, page_size: int,
              tp: int | None = None, pp: int | None = None, seed: int = 0,
              attention_impl: str = "auto", lora_config=None) -> int:
        """Create the executor over the global mesh (all hosts' devices).
        Default tp = every device in the group (pure TP); pass ``pp`` to
        stage layers across hosts instead.
        ``attention_impl="auto"`` resolves per shard exactly as on a
        single host: the paged kernel shard_maps over the kv-head/tp
        axis, rides the pp tick loop's staging carry, and on composed
        pp x tp meshes runs inside the flattened {"pp","tp"} manual
        region (round 15) — no mesh shape resolves dense on a TPU
        backend anymore. ``lora_config`` builds the device-resident
        adapter stacks on every shard (pp-sharded over the layer axis
        on pipeline meshes)."""
        import jax

        from ..parallel import MeshConfig, create_mesh
        from .executor import LocalEngineExecutor

        n = len(jax.devices())
        pp = pp or 1
        # tp composes inside pp stages (partial-manual shard_map in
        # pp_model.py); with pp given, default tp fills the remaining
        # devices. Pure TP (pp=1) defaults to tp over every device.
        tp = tp or max(1, n // pp)
        mesh = create_mesh(MeshConfig(tp=tp, pp=pp, dp=max(1, n // (tp * pp))))
        self.executor = LocalEngineExecutor(
            config, max_slots=max_slots, num_pages=num_pages,
            page_size=page_size, mesh=mesh, seed=seed,
            attention_impl=attention_impl, lora_config=lora_config,
        )
        return n

    # ------------------------------------------------ executor operations
    def tick(self, plan):
        """Compiled-loop entry point: ONE method drives every engine
        operation so a resident loop executor (dag/loop.py) can stream
        step plans over a channel with zero per-tick RPC. ``plan`` is
        ``(method_name, args)``; per-channel FIFO ordering preserves the
        SPMD invariant exactly like per-caller actor ordering did."""
        method, args = plan
        return getattr(self, method)(*args)

    def prefill(self, block_table, tokens, start_pos, handle, take,
                lora_slot=0) -> bool:
        self.executor.prefill(block_table, tokens, start_pos, handle, take,
                              lora_slot=lora_slot)
        return True

    def drop_handle(self, handle) -> bool:
        self.executor.drop_handle(handle)
        return True

    def install_adapter(self, slot, arrays) -> bool:
        self.executor.install_adapter(slot, arrays)
        return True

    def sample_first(self, handles, temps):
        return self.executor.sample_first(handles, temps)

    def decode(self, block_tables, tokens, pos, temps, eos_ids, remaining,
               n_steps, lora_idx=None):
        return self.executor.decode(
            block_tables, tokens, pos, temps, eos_ids, remaining, n_steps,
            lora_idx=lora_idx)

    def supports_mixed(self) -> bool:
        return bool(self.executor is not None
                    and self.executor.supports_mixed_dispatch)

    def supports_spec(self) -> bool:
        return bool(self.executor is not None
                    and self.executor.supports_speculation)

    def verify(self, block_tables, tokens_mat, pos, temps, eos_ids,
               remaining):
        """Speculative verify on this shard: every shard scores the same
        drafted batch (SPMD), so the fan-out's first result is the
        group's answer."""
        return self.executor.verify(block_tables, tokens_mat, pos, temps,
                                    eos_ids, remaining)

    def supports_cow(self) -> bool:
        return bool(self.executor is not None
                    and self.executor.supports_prefix_cow)

    def copy_pages(self, src, dst) -> bool:
        self.executor.copy_pages(src, dst)
        return True

    def supports_migration(self) -> bool:
        """KV page export/import. Single-host groups only for now: a
        multi-process mesh shards the pool across hosts, so one shard
        cannot materialize the full [L, m, ...] page payload (residue —
        a per-shard chunked wire format would lift this)."""
        return bool(self.executor is not None and self.world == 1
                    and getattr(self.executor, "supports_kv_migration",
                                False))

    def export_pages(self, page_ids):
        return self.executor.export_pages(page_ids)

    def import_pages(self, page_ids, data) -> bool:
        self.executor.import_pages(page_ids, data)
        return True

    def mixed(self, prefill_plans, block_tables, tokens, pos, temps, eos_ids,
              remaining, n_steps, lora_idx=None):
        return self.executor.mixed(
            prefill_plans, block_tables, tokens, pos, temps, eos_ids,
            remaining, n_steps, lora_idx=lora_idx)


class ShardedEngineExecutor:
    """Driver-side executor fanning every operation out to the shard
    actors (duck-types ``LocalEngineExecutor``).

    Two dispatch modes, IDENTICAL program sequence per shard (the SPMD
    invariant) in both:

      * **dynamic** (default off the pp path): one actor call per shard
        per operation — per-caller actor ordering sequences the shards.
        Every steady-state decode burst pays the full submit→lease→push
        RPC path per shard.
      * **compiled loop** (``use_compiled_loop=True``; the default the
        pp tick path gets from ``create_sharded_executor``): ONE
        owner-side submit per shard installs a resident
        ``EngineShardWorker.tick`` executor (``dag/loop.py``), and every
        operation afterwards is a channel write — ``put((method, args))``
        — with results streamed back in order. Zero per-tick task
        submission, RPC, or lease traffic at steady state; channel FIFO
        ordering replaces actor-call ordering. Fire-and-forget
        operations (prefill chunks, drop_handle) pipeline up to the
        loop's credits ahead of their results, mirroring the dynamic
        ``_dispatch``'s pure-dispatch behavior.
    """

    def __init__(self, shards: list, pg=None, use_compiled_loop: bool = False):
        self.shards = shards
        self._pg = pg
        self._pending: list = []  # in-flight async dispatches (prefill/drop)
        self._loop = None
        self._loop_pending = 0    # loop results put but not yet consumed
        self.sync_s = 0.0  # seconds blocked on the shards' results (_blocked)
        self.use_compiled_loop = use_compiled_loop
        # Set after build() by create_sharded_executor: whether every
        # shard's local executor takes the fused mixed entry point /
        # the COW prefix-sharing ops / the KV-migration page ops.
        self.supports_mixed_dispatch = False
        self.supports_prefix_cow = False
        self.supports_kv_migration = False
        self.supports_speculation = False
        # Serializes each operation's whole per-shard dispatch sequence:
        # KV imports/exports arrive on REQUEST threads while the engine
        # loop keeps fanning steps out, and an interleave inside one
        # operation's shard sequence would break the SPMD program-order
        # invariant (and corrupt the compiled loop's channel FIFO).
        self._dispatch_lock = threading.RLock()

    # ---------------------------------------------------- compiled loop
    def _ensure_loop(self):
        if self._loop is None:
            from ..dag import InputNode, MultiOutputNode, compile_loop

            with InputNode() as inp:
                outs = [s.tick.bind(inp) for s in self.shards]
            graph = outs[0] if len(outs) == 1 else MultiOutputNode(outs)
            self._loop = compile_loop(graph)
        return self._loop

    @property
    def loop_ticks(self) -> int:
        """Engine ticks streamed through the compiled loop so far."""
        return self._loop._gets if self._loop is not None else 0

    def _loop_put(self, method: str, *args) -> None:
        self._ensure_loop().put((method, tuple(args)), timeout=300.0)
        self._loop_pending += 1

    def _loop_drain(self, keep_last: bool, timeout: float = 300.0):
        """Consume queued results in order; returns the LAST one (the
        per-shard result list) when ``keep_last``."""
        last = None
        while self._loop_pending:
            self._loop_pending -= 1
            got = self._loop.get(timeout=timeout)
            if keep_last and not self._loop_pending:
                last = got if isinstance(got, tuple) else (got,)
        return last

    # --------------------------------------------------------- dispatch
    def _dispatch(self, method: str, *args) -> None:
        """Fire-and-forget to every shard: ordering (actor-call or loop
        channel FIFO) keeps the program sequence identical on all
        shards, so prefill chunks need no host sync (mirroring
        LocalEngineExecutor's pure-dispatch prefill — one blocking round
        trip per CHUNK would wreck TTFT). Errors surface at the next
        sync point."""
        with self._dispatch_lock:
            if self.use_compiled_loop:
                self._loop_put(method, *args)
                return
            self._pending.extend(
                getattr(s, method).remote(*args) for s in self.shards)

    @contextlib.contextmanager
    def _blocked(self):
        """The engine's thread waiting for its shards' results: what
        ``LocalEngineExecutor._sync`` is on one host. ``engine.sync`` on the
        profiler's trace, its seconds in ``sync_s`` (the engine's
        ``step_sync_ms_sum``); each shard's own process has the device's
        ``engine.dispatch`` / ``engine.sync``."""
        t0 = time.monotonic()
        try:
            with annotate("engine.sync", shards=len(self.shards)):
                yield
        finally:
            self.sync_s += time.monotonic() - t0

    def _sync(self, timeout: float = 300.0) -> None:
        with self._dispatch_lock, self._blocked():
            if self.use_compiled_loop:
                self._loop_drain(keep_last=False, timeout=timeout)
                return
            if self._pending:
                pending, self._pending = self._pending, []
                ray.get(pending, timeout=timeout)

    def _all(self, method: str, *args, timeout: float = 300.0):
        with self._dispatch_lock:
            self._sync(timeout)
            with self._blocked():
                if self.use_compiled_loop:
                    self._loop_put(method, *args)
                    return list(self._loop_drain(keep_last=True, timeout=timeout))
                refs = [getattr(s, method).remote(*args) for s in self.shards]
                return ray.get(refs, timeout=timeout)

    def prefill(self, block_table, tokens, start_pos, handle, take,
                lora_slot: int = 0) -> None:
        self._dispatch("prefill", block_table, tokens, start_pos, handle,
                       take, int(lora_slot))

    def drop_handle(self, handle) -> None:
        self._dispatch("drop_handle", handle)

    def copy_pages(self, src, dst) -> None:
        """COW fork fan-out: rides the ordered dispatch stream, so every
        shard copies the page before the chunk that writes into it."""
        self._dispatch("copy_pages",
                       [int(s) for s in src], [int(d) for d in dst])

    def export_pages(self, page_ids) -> dict:
        """KV-migration export: one shard's full-pool gather (single-host
        groups — see ``EngineShardWorker.supports_migration``). Rides the
        ordered stream so every prior prefill write is visible."""
        return self._all("export_pages", [int(p) for p in page_ids])[0]

    def import_pages(self, page_ids, data) -> None:
        """KV-migration import fan-out, ordered with the dispatch stream
        so no shard can read the pages before the scatter lands."""
        self._dispatch("import_pages", [int(p) for p in page_ids],
                       {k: np.asarray(v) for k, v in data.items()})

    def install_adapter(self, slot, arrays) -> None:
        """LoRA fan-out: the adapter's padded A/B arrays land on every
        shard's device stack, ORDERED with the prefill/decode stream so
        no shard can run a step before the adapter its plan references
        is installed."""
        self._dispatch("install_adapter", int(slot),
                       {k: np.asarray(v) for k, v in arrays.items()})

    def sample_first(self, handles, temps) -> np.ndarray:
        return self._all("sample_first", list(handles), temps)[0]

    def decode(self, block_tables, tokens, pos, temps, eos_ids, remaining,
               n_steps, lora_idx=None) -> np.ndarray:
        return self._all(
            "decode", block_tables, tokens, pos, temps, eos_ids, remaining,
            n_steps, lora_idx)[0]

    def verify(self, block_tables, tokens_mat, pos, temps, eos_ids,
               remaining):
        """Speculative verify fan-out: every shard runs the SAME verify
        program in sequence with the rest of the dispatch stream (SPMD
        invariant), over actor calls or the compiled loop's channel
        identically; shard 0's (tokens, live) is the group's result."""
        return self._all(
            "verify", block_tables, tokens_mat, pos, temps, eos_ids,
            remaining)[0]

    def mixed(self, prefill_plans, block_tables, tokens, pos, temps, eos_ids,
              remaining, n_steps, lora_idx=None) -> np.ndarray:
        """Fused prefill+decode step on every shard: each shard stashes
        final-chunk hiddens under the same handles, so a later
        ``sample_first`` fan-out finds them everywhere (the SPMD
        invariant — identical program sequence per shard)."""
        return self._all(
            "mixed", prefill_plans, block_tables, tokens, pos, temps,
            eos_ids, remaining, n_steps, lora_idx)[0]

    def shutdown(self) -> None:
        if self._loop is not None:
            try:
                self._loop.teardown(timeout=10.0)
            except Exception:
                pass
            self._loop = None
        for s in self.shards:
            try:
                ray.kill(s)
            except Exception:
                pass
        if self._pg is not None:
            from ..util import remove_placement_group

            try:
                remove_placement_group(self._pg)
            except Exception:
                pass


def create_sharded_executor(
    config,
    num_hosts: int,
    *,
    max_slots: int,
    num_pages: int,
    page_size: int,
    tp: int | None = None,
    pp: int | None = None,
    seed: int = 0,
    bundle_resources: dict | None = None,
    topology: str | None = None,
    strategy: str | None = None,
    runtime_env: dict | None = None,
    attention_impl: str = "auto",
    lora_config=None,
    use_compiled_loop: bool | None = None,
) -> ShardedEngineExecutor:
    """Place one shard actor per host and bootstrap the group.

    ``bundle_resources``: per-host bundle (e.g. ``{"TPU": 4, "CPU": 1}``).
    ``topology``: TPU slice type (e.g. ``v5litepod-16``) — claims the
    slice-head resource on bundle 0 so the whole slice is ours atomically.
    ``strategy``: placement strategy; defaults to the reference's choice —
    STRICT_PACK for a single-host engine, PACK across hosts
    (``vllm_models.py:131-168``).
    ``use_compiled_loop``: drive the steady-state engine tick path
    through a persistent compiled loop (``dag/loop.py``) instead of one
    actor RPC per shard per operation. Default: ON for pipeline meshes
    (``pp`` > 1) — the per-tick dispatch overhead the static schedule
    exists to kill — OFF otherwise (pass ``True`` to force it anywhere).
    """
    if use_compiled_loop is None:
        use_compiled_loop = bool(pp and pp > 1)
    from ..util import PlacementGroupSchedulingStrategy, placement_group, remove_placement_group

    res = dict(bundle_resources or {"CPU": 1.0})
    bundles = [dict(res) for _ in range(num_hosts)]
    if topology:
        bundles[0][f"TPU-{topology}-head"] = 1.0
    strategy = strategy or ("STRICT_PACK" if num_hosts == 1 else "PACK")
    pg = placement_group(bundles, strategy=strategy)
    if not pg.wait(timeout_seconds=120.0):
        remove_placement_group(pg)
        raise TimeoutError(
            f"placement group for {num_hosts} engine shards not ready in 120s")
    actor_cls = ray.remote(EngineShardWorker)
    shards = [
        actor_cls.options(
            resources=dict(bundles[i]),
            scheduling_strategy=PlacementGroupSchedulingStrategy(
                placement_group=pg, placement_group_bundle_index=i),
            runtime_env=runtime_env,
        ).remote(i, num_hosts)
        for i in range(num_hosts)
    ]
    executor = ShardedEngineExecutor(shards, pg,
                                     use_compiled_loop=use_compiled_loop)
    try:
        coordinator = ray.get(shards[0].coordinator_address.remote(), timeout=120)
        ray.get([s.init_distributed.remote(coordinator) for s in shards],
                timeout=300)
        ray.get([
            s.build.remote(config, max_slots=max_slots, num_pages=num_pages,
                           page_size=page_size, tp=tp, pp=pp, seed=seed,
                           attention_impl=attention_impl,
                           lora_config=lora_config)
            for s in shards
        ], timeout=600)
        executor.supports_mixed_dispatch = bool(ray.get(
            shards[0].supports_mixed.remote(), timeout=60))
        executor.supports_prefix_cow = bool(ray.get(
            shards[0].supports_cow.remote(), timeout=60))
        executor.supports_kv_migration = bool(ray.get(
            shards[0].supports_migration.remote(), timeout=60))
        executor.supports_speculation = bool(ray.get(
            shards[0].supports_spec.remote(), timeout=60))
        if use_compiled_loop:
            # Install the resident tick executors NOW (one submit per
            # shard — the last tasks this executor ever submits).
            executor._ensure_loop()
    except Exception:
        executor.shutdown()
        raise
    return executor
