"""Engine executors: the device half of the inference engine.

The ``InferenceEngine`` (engine.py) is a host-side scheduler — slots,
pages, prefix cache, admission. Every device interaction goes through an
executor with three operations:

  * ``prefill(block_table, tokens, start_pos, handle, take)`` — run one
    page-aligned prompt chunk; stash the last real position's hidden
    state under ``handle`` (device-resident; no host sync).
  * ``sample_first(handles, temps)`` — batched first-token sampling for
    the stashed hiddens (ONE host sync for a burst of prefills).
  * ``decode(block_tables, tokens, pos, temps, eos_ids, remaining, K)``
    — K fused decode+sample steps, one dispatch, one sync.

``LocalEngineExecutor`` runs on this process's devices (optionally a
mesh: tensor-parallel over local chips, or a global multi-process mesh
after ``jax.distributed.initialize`` — the params/pages are sharded, the
SAME jitted programs run SPMD, XLA inserts the collectives). The
multi-host fan-out lives in ``multihost.py``; the reference gets this
split from vLLM's worker/executor architecture
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py``).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import LlamaConfig, PRESETS, init_params
from ..observability.tracing import annotate
from ..tpu import leased_devices, on_tpu
from .model import (copy_pages, decode_loop, init_pages, mixed_dispatch,
                    prefill_chunk, sample_first_batch, verify_block,
                    write_pages)


def resolve_attention_impl(attention_impl: str = "auto", mesh=None,
                           backend: str | None = None) -> str:
    """Resolve ``attention_impl`` to a concrete decode path.

    ``"auto"`` picks the v2 staging-buffer Pallas kernel (``"paged"``)
    whenever a TPU backend is present — per-slot-proportional HBM traffic
    is the point of the paged design — and falls back to the bucketed
    dense gather (``"dense"``) only when the backend is not a TPU
    (interpret-mode decode is far slower than the dense gather on CPU —
    tests force ``"paged"`` explicitly to exercise the kernel).

    Every mesh shape takes the kernel: tensor-parallel meshes
    shard_map it over the KV-head axis (round 5), pure-pp meshes thread
    the v2 staging carry per stage (round 8,
    ``pp_model.pp_decode_loop``), and composed pp×tp meshes (round 15)
    run the decode loop as ONE flattened manual region over both axes —
    pp manual on layers, tp manual on KV heads — so the kernel runs on
    each shard's local heads and the old "resolves dense on exactly the
    mesh a real v5p slice uses" cliff is gone.
    """
    if attention_impl not in ("auto", "paged", "dense"):
        raise ValueError(f"unknown attention_impl {attention_impl!r}")
    if attention_impl != "auto":
        return attention_impl
    # The same test the kernel uses to compile natively.
    on_chip = on_tpu() if backend is None else backend == "tpu"
    return "paged" if on_chip else "dense"


class LocalEngineExecutor:
    """Params, page pool, PRNG key and jitted programs on this process's
    devices. With ``mesh``, params/pages shard over it (tp axis) and — for
    a multi-process mesh — sampled-token outputs are pinned to a
    replicated sharding so every process can read them without a gather."""

    def __init__(
        self,
        config: LlamaConfig | str,
        params=None,
        *,
        max_slots: int,
        num_pages: int,
        page_size: int,
        mesh=None,
        seed: int = 0,
        attention_impl: str = "auto",
        lora_config=None,
    ):
        self.config = PRESETS[config] if isinstance(config, str) else config
        leased_devices()  # a replica that leased chips must not place on the CPU
        if params is None:
            params = init_params(self.config, jax.random.PRNGKey(seed))
        self.mesh = mesh
        self.max_slots = max_slots
        self.page_size = page_size
        # "paged" = v2 staging-buffer Pallas kernel (pool read-only per
        # K-step dispatch, token carry folded into the online softmax,
        # one batched commit scatter per dispatch — HBM per step
        # proportional to per-SLOT live context); "dense" = bucketed
        # gather (cost tracks the batch-MAX live context); "auto" =
        # paged on TPU backends, dense elsewhere (resolve_attention_impl).
        self.attention_impl = resolve_attention_impl(attention_impl, mesh)
        self.paged_attention = self.attention_impl == "paged"
        # shard_map the kernel over tp when the pool is head-sharded;
        # single-axis (dp-only) meshes keep the plain call. pp meshes
        # (pure OR composed with tp) never use it: the pp decode loop is
        # itself the manual region — flattened over {"pp","tp"} when tp
        # composes (round 15) — and calls the kernel on local arrays.
        self._attn_mesh = (
            mesh if self.paged_attention and mesh is not None
            and mesh.shape.get("pp", 1) == 1
            and mesh.shape.get("tp", 1) > 1 else None)
        pages = init_pages(self.config, num_pages, page_size)
        self._replicated = None
        self._pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        if self._pp > 1:
            # Pipeline-parallel: layers (params AND page pool) shard over
            # the pp axis; shard_map programs in pp_model.py rotate
            # activations stage->stage (ref vllm_models.py:117-168 PP).
            # tp COMPOSES inside the stages: dense programs stay manual
            # over pp only (tp auto — XLA partitions from the params'
            # shardings), while the PAGED decode loop flattens to one
            # manual region over {"pp","tp"} (round 15) because the
            # Pallas kernel cannot sit under an auto-tp partition — the
            # reference runs TP x PP engines via vLLM (vllm_models.py:117).
            from jax.sharding import NamedSharding, PartitionSpec

            from ..models.llama import param_axes
            from ..parallel.sharding import logical_sharding, shard_params

            tp = mesh.shape.get("tp", 1)
            if tp > 1 and self.config.n_kv_heads % tp:
                raise ValueError(
                    f"n_kv_heads={self.config.n_kv_heads} not divisible by tp={tp}")
            if self.config.n_layers % self._pp:
                raise ValueError(
                    f"n_layers={self.config.n_layers} not divisible by pp={self._pp}")
            if max_slots % self._pp:
                raise ValueError(
                    f"max_slots={max_slots} not divisible by pp={self._pp} "
                    "(decode pipelines over slot groups)")
            rep = NamedSharding(mesh, PartitionSpec())
            # param_axes maps "layers"->pp and heads/mlp/vocab->tp, so the
            # stacked layer arrays come out sharded over BOTH axes.
            params = shard_params(params, param_axes(self.config), mesh)
            self._pages_sharding = logical_sharding(
                mesh, ("layers", None, "kv_heads", None, "head_dim"))
            pages = jax.device_put(
                pages, {"k": self._pages_sharding, "v": self._pages_sharding})
            self._replicated = rep
        elif mesh is not None:
            # Tensor-parallel: params shard by the model's logical axes
            # (heads/kv_heads/mlp -> tp), the page pool by kv_heads; the
            # same jitted programs then run SPMD with XLA collectives
            # (the multi-chip path the reference gets from vLLM TP).
            from jax.sharding import NamedSharding, PartitionSpec

            from ..models.llama import param_axes
            from ..parallel.sharding import logical_sharding, shard_params

            tp = mesh.shape.get("tp", 1)
            if self.config.n_kv_heads % tp:
                raise ValueError(
                    f"n_kv_heads={self.config.n_kv_heads} not divisible by tp={tp}")
            params = shard_params(params, param_axes(self.config), mesh)
            self._pages_sharding = logical_sharding(
                mesh, ("layers", None, "kv_heads", None, "head_dim"))
            pages = jax.device_put(
                pages, {"k": self._pages_sharding, "v": self._pages_sharding})
            self._replicated = NamedSharding(mesh, PartitionSpec())
        self.lora_config = lora_config
        self.lora_stack = None
        if lora_config is not None:
            if mesh is not None and mesh.shape.get("tp", 1) > 1:
                raise ValueError("lora serving does not shard stacks over "
                                 "tp (adapters are head-stacked; use pp or "
                                 "a single device)")
            from .lora import init_lora_stack

            self.lora_stack = init_lora_stack(
                self.config, lora_config.max_loras, lora_config.max_rank)
            if self._pp > 1:
                # Stacks shard over pp on their LAYER axis, exactly like
                # params["layers"], so pp_model's local layer indices
                # address the local stack shard directly (round 8:
                # LoRA threads through the pp pipeline).
                from jax.sharding import NamedSharding, PartitionSpec

                lora_sharding = NamedSharding(mesh, PartitionSpec("pp"))
                self.lora_stack = {
                    k: jax.device_put(v, lora_sharding)
                    for k, v in self.lora_stack.items()}
            elif mesh is not None:
                self.lora_stack = jax.device_put(
                    self.lora_stack, self._replicated)
        self.params = params
        self.pages = pages
        self._key = jax.random.PRNGKey(seed ^ 0x5EED)
        # handle -> device hidden state [E] awaiting first-token sampling
        self._hidden: dict[int, Any] = {}
        self.sync_s = 0.0  # seconds the host blocked on device results (_sync)
        # Serializes every read/replace of self.pages: migration imports
        # and exports run on REQUEST threads while the engine loop keeps
        # dispatching (donating the pool buffer each step) — without the
        # lock an exporter could np.asarray a just-donated (deleted)
        # buffer, or an import could race a decode's donation.
        self._pages_lock = threading.RLock()

        if self._pp > 1:
            # pp programs define their shardings via shard_map out_specs
            # (pages staged over pp, tokens/hidden/key replicated).
            from .pp_model import pp_decode_loop, pp_prefill_chunk, pp_prefill_chunks

            self._key = jax.device_put(self._key, self._replicated)
            self._prefill = functools.partial(pp_prefill_chunk, mesh=mesh)
            self._prefill_many = functools.partial(pp_prefill_chunks, mesh=mesh)
            self._decode_loop = functools.partial(pp_decode_loop, mesh=mesh)
            self._sample_first = jax.jit(
                sample_first_batch.__wrapped__,
                out_shardings=(self._replicated, self._replicated))
            # pp prefill scatters rows at (page, offset) granularity
            # since round 15, so partial-block COW sharing works here
            # too: the fork copy is a page-axis gather/scatter XLA
            # partitions per layer shard without any manual region.
            pg = {"k": self._pages_sharding, "v": self._pages_sharding}
            self._copy_pages = jax.jit(
                copy_pages.__wrapped__, donate_argnames=("pages",),
                out_shardings=pg)
            # pp pools shard layers across the pipeline's manual region;
            # the host-array export/import path below assumes the whole
            # [L, P, ...] pool is addressable — KV migration stays off
            # (the one residue of this round, noted in ROADMAP).
            self._write_pages = None
            # Speculative verify doesn't thread the pp tick loop yet
            # (the staged-per-stage carry would need a per-stage verify
            # program) — pp engines decode plain.
            self._verify = None
        elif self._replicated is not None:
            # Re-jit the model programs with EXPLICIT output shardings:
            # token/key/hidden outputs pinned replicated — on a
            # multi-process mesh an output with an arbitrary XLA-chosen
            # sharding cannot be np.asarray'd (or indexed) by every
            # process; replicated outputs can. Pages keep their kv_heads
            # sharding and stay donated.
            rep = self._replicated
            pg = {"k": self._pages_sharding, "v": self._pages_sharding}
            self._decode_loop = jax.jit(
                decode_loop.__wrapped__,
                static_argnames=("config", "page_size", "n_steps", "paged",
                                 "live_pages", "attn_mesh"),
                donate_argnames=("pages",),
                out_shardings=(rep, rep, pg),
            )
            self._sample_first = jax.jit(
                sample_first_batch.__wrapped__, out_shardings=(rep, rep))
            self._prefill = jax.jit(
                prefill_chunk.__wrapped__,
                static_argnames=("config", "page_size", "live_pages"),
                donate_argnames=("pages",),
                out_shardings=(pg, rep),
            )
            self._mixed = jax.jit(
                mixed_dispatch.__wrapped__,
                static_argnames=("config", "page_size", "n_steps", "paged",
                                 "live_pages", "prefill_live_pages",
                                 "attn_mesh"),
                donate_argnames=("pages",),
                out_shardings=(rep, rep, pg, rep),
            )
            self._copy_pages = jax.jit(
                copy_pages.__wrapped__, donate_argnames=("pages",),
                out_shardings=pg)
            self._write_pages = jax.jit(
                write_pages.__wrapped__, donate_argnames=("pages",),
                out_shardings=pg)
            self._verify = jax.jit(
                verify_block.__wrapped__,
                static_argnames=("config", "page_size", "n_draft", "paged",
                                 "live_pages", "attn_mesh"),
                donate_argnames=("pages",),
                out_shardings=(rep, rep, rep, pg),
            )
        else:
            self._decode_loop = decode_loop
            self._sample_first = sample_first_batch
            self._prefill = prefill_chunk
            self._mixed = mixed_dispatch
            self._copy_pages = copy_pages
            self._write_pages = write_pages
            self._verify = verify_block

    def _put(self, x: np.ndarray):
        """Host input -> device, replicated over the mesh when present (a
        multi-process jit requires global inputs, not bare numpy)."""
        if self._replicated is not None:
            return jax.device_put(x, self._replicated)
        return jnp.asarray(x)

    @staticmethod
    def _bucket_pages(needed: int, max_pages: int) -> int:
        """Round a live-page requirement up to a power of two (≥ 8), so
        the static ``live_pages`` cap takes O(log(max_pages)) distinct
        values — bounding recompiles while keeping attention cost
        proportional to live context rather than pool capacity."""
        b = 8
        while b < needed:
            b *= 2
        return min(b, max_pages)

    @property
    def supports_weight_residency(self) -> bool:
        """Host-tier weight demotion/promotion (``llm/weights.py``):
        single-device executors only — mesh-sharded params own their
        placement and a plain ``device_put`` would lose it."""
        return self._replicated is None

    def install_adapter(self, slot: int, arrays: dict) -> None:
        """Write one adapter's padded A/B arrays into stack slot ``slot``
        (the ``LoRAManager``'s device hook). Arrays ride ``_put`` so a
        mesh-sharded stack (pp) takes them as replicated global inputs."""
        from .lora import _install

        self.lora_stack = _install(
            self.lora_stack, self._put(np.int32(slot)),
            {k: self._put(np.asarray(v)) for k, v in arrays.items()})

    def _sync(self, *arrays) -> tuple:
        """Device results to host arrays: THE place the engine's host
        thread blocks on the device. ``engine.sync`` on the profiler's
        trace; its seconds add up in ``sync_s`` (always on: the engine
        splits a step into host time and this wait from it)."""
        t0 = time.monotonic()
        with annotate("engine.sync"):
            out = tuple(np.asarray(a) for a in arrays)
        self.sync_s += time.monotonic() - t0
        return out

    # ------------------------------------------------------------- operations
    def prefill(self, block_table: np.ndarray, tokens: np.ndarray,
                start_pos: int, handle: int | None, take: int,
                lora_slot: int = 0) -> None:
        if self._pp > 1:
            kwargs = {}
            if self.lora_stack is not None:
                kwargs["lora"] = self.lora_stack
                kwargs["lora_slot"] = self._put(np.int32(lora_slot))
        else:
            # Context gathered is [0, start_pos): cap the gather width.
            kwargs = {"live_pages": self._bucket_pages(
                -(-int(start_pos) // self.page_size), block_table.shape[0])}
            if self.lora_stack is not None:
                kwargs["lora"] = self.lora_stack
                kwargs["lora_slot"] = self._put(np.int32(lora_slot))
        with annotate("engine.dispatch", kind="prefill", prefill_tokens=take), \
                self._pages_lock:
            self.pages, hidden = self._prefill(
                self.params, self.pages,
                self._put(block_table.astype(np.int32)),
                self._put(tokens.astype(np.int32)),
                self._put(np.int32(start_pos)),
                config=self.config, page_size=self.page_size, **kwargs,
            )
        if handle is not None:  # final chunk: stash for first-token sampling
            self._hidden[handle] = hidden[take - 1]

    @property
    def pipelined_prefill_depth(self) -> int:
        """Max consecutive chunks one prefill dispatch pipelines (1 = no
        pipelining). Longer wavefronts amortize the (pp-1)-tick warmup:
        stage utilization is m/(m+pp-1), so 8 chunks through 2 stages
        runs at 89% vs 67% for 2."""
        return max(self._pp, 8) if self._pp > 1 else 1

    def prefill_many(self, block_table: np.ndarray, tokens_m: np.ndarray,
                     start_pos: int, handle: int | None, take: int) -> None:
        """``m`` consecutive same-size chunks of ONE sequence in a single
        chunk-pipelined dispatch (``pp_model.pp_prefill_chunks``); when
        ``handle`` is set, the LAST chunk's position ``take - 1`` hidden
        is stashed for first-token sampling."""
        with annotate("engine.dispatch", kind="prefill",
                      prefill_tokens=int(tokens_m.size)), self._pages_lock:
            self.pages, hiddens = self._prefill_many(
                self.params, self.pages,
                self._put(block_table.astype(np.int32)),
                self._put(tokens_m.astype(np.int32)),
                self._put(np.int32(start_pos)),
                config=self.config, page_size=self.page_size,
            )
        if handle is not None:
            self._hidden[handle] = hiddens[-1][take - 1]

    def drop_handle(self, handle: int) -> None:
        self._hidden.pop(handle, None)

    def sample_first(self, handles: list[int], temps: np.ndarray) -> np.ndarray:
        """One dispatch + one sync for every pending first token. Pads to
        ``max_slots`` so the program compiles once, not per batch size."""
        m = len(handles)
        stack = [self._hidden.pop(h) for h in handles]
        hiddens = jnp.stack(stack + [stack[0]] * (self.max_slots - m))
        padded = np.zeros(self.max_slots, np.float32)
        padded[:m] = temps[:m]
        with annotate("engine.dispatch", kind="flush", first_tokens=m):
            toks, self._key = self._sample_first(
                hiddens, self.params["lm_head"], self._put(padded), self._key)
        return self._sync(toks)[0][:m]

    def _decode_kwargs(self, pos: np.ndarray, n_steps: int,
                       block_tables: np.ndarray, lora_idx) -> dict:
        """Static decode kwargs shared by ``decode`` and ``mixed``."""
        if self.paged_attention:
            # The kernel only reads POOL context [0, pos): tokens
            # generated mid-dispatch ride the staging carry, so the
            # page bound ignores n_steps entirely — a strictly
            # tighter grid than the dense bound below.
            needed = max(1, (int(pos.max()) + self.page_size - 1)
                         // self.page_size)
        else:
            # Dense attends in-pool: positions reach
            # max(pos) + n_steps - 1 by the last fused step.
            needed = (int(pos.max()) + n_steps - 1) // self.page_size + 1
        kwargs = {
            "paged": self.paged_attention,
            "live_pages": self._bucket_pages(needed, block_tables.shape[1]),
            "attn_mesh": self._attn_mesh,
        }
        if self.lora_stack is not None:
            kwargs["lora"] = self.lora_stack
            kwargs["lora_idx"] = self._put(
                (lora_idx if lora_idx is not None
                 else np.zeros(block_tables.shape[0], np.int32)).astype(np.int32))
        return kwargs

    def decode(self, block_tables: np.ndarray, tokens: np.ndarray,
               pos: np.ndarray, temps: np.ndarray, eos_ids: np.ndarray,
               remaining: np.ndarray, n_steps: int,
               lora_idx: np.ndarray | None = None) -> np.ndarray:
        if self._pp > 1:
            kwargs = {}
            if self.paged_attention:
                # Same pool-context-only bound as the unpipelined paged
                # path: staged tokens ride the per-stage carry, so the
                # kernel grid ignores n_steps entirely.
                needed = max(1, (int(pos.max()) + self.page_size - 1)
                             // self.page_size)
                kwargs["paged"] = True
                kwargs["live_pages"] = self._bucket_pages(
                    needed, block_tables.shape[1])
            if self.lora_stack is not None:
                kwargs["lora"] = self.lora_stack
                kwargs["lora_idx"] = self._put(
                    (lora_idx if lora_idx is not None
                     else np.zeros(block_tables.shape[0], np.int32)
                     ).astype(np.int32))
        else:
            kwargs = self._decode_kwargs(pos, n_steps, block_tables, lora_idx)
        with annotate("engine.dispatch", kind="decode", K=n_steps), \
                self._pages_lock:
            toks, self._key, self.pages = self._decode_loop(
                self.params, self.pages,
                self._put(block_tables.astype(np.int32)),
                self._put(tokens.astype(np.int32)),
                self._put(pos.astype(np.int32)),
                self._put(temps.astype(np.float32)),
                self._put(eos_ids.astype(np.int32)),
                self._put(remaining.astype(np.int32)),
                self._key, config=self.config, page_size=self.page_size,
                n_steps=n_steps, **kwargs,
            )
        return self._sync(toks)[0]  # [n_steps, slots] — the one sync

    @property
    def supports_speculation(self) -> bool:
        """Speculative verify dispatch (``model.verify_block``): off the
        pp path (the per-stage tick loop doesn't thread the verify
        program yet) and without a LoRA stack (the chunk forward doesn't
        carry per-slot adapter deltas — those slots decode plain)."""
        return self._verify is not None and self.lora_stack is None

    def verify(self, block_tables: np.ndarray, tokens_mat: np.ndarray,
               pos: np.ndarray, temps: np.ndarray, eos_ids: np.ndarray,
               remaining: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Score one drafted continuation per slot in ONE dispatch.

        tokens_mat: [slots, K+1] int32 — column 0 the current token,
        columns 1..K the draft (-1 pads). Returns ``(tokens [K+1,
        slots], live [K+1, slots])`` — the emitted-token matrix and its
        per-step liveness mask (see ``model.verify_block``)."""
        assert self.supports_speculation
        n_draft = int(tokens_mat.shape[1]) - 1
        # The verify forward reads POOL context [0, pos) only — chunk
        # tokens ride the staging carry — so the page bound ignores the
        # draft depth, like the paged decode bound.
        needed = max(1, (int(pos.max()) + self.page_size - 1)
                     // self.page_size)
        with annotate("engine.dispatch", kind="verify", K=n_draft), \
                self._pages_lock:
            toks, live, self._key, self.pages = self._verify(
                self.params, self.pages,
                self._put(block_tables.astype(np.int32)),
                self._put(tokens_mat.astype(np.int32)),
                self._put(pos.astype(np.int32)),
                self._put(temps.astype(np.float32)),
                self._put(eos_ids.astype(np.int32)),
                self._put(remaining.astype(np.int32)),
                self._key, config=self.config, page_size=self.page_size,
                n_draft=n_draft, paged=self.paged_attention,
                live_pages=self._bucket_pages(needed, block_tables.shape[1]),
                attn_mesh=self._attn_mesh,
            )
        return self._sync(toks, live)

    @property
    def supports_prefix_cow(self) -> bool:
        """Copy-on-write prefix sharing: needs ``copy_pages`` plus the
        row-granular prefill scatter (mid-page suffix starts). Both hold
        on every path since round 15 — pp prefill writes rows at
        ``(page, offset)`` granularity now, so a mid-page suffix start
        no longer clobbers a COW fork's copied prefix rows."""
        return self._copy_pages is not None

    def copy_pages(self, src, dst) -> None:
        """Fork shared pages: device-copies pages ``src`` onto ``dst``
        (all layers, one dispatch). Ordered with the prefill/decode
        stream — the engine calls it immediately before the first chunk
        that writes into the fork."""
        with self._pages_lock:
            self.pages = self._copy_pages(
                self.pages, self._put(np.asarray(src, np.int32)),
                self._put(np.asarray(dst, np.int32)))

    # --------------------------------------------------------- KV migration
    @property
    def supports_kv_migration(self) -> bool:
        """Page export/import for KV migration (disaggregated serving,
        spill migration, tiered host-RAM KV). Available off the pp path —
        pp pools shard layers across the pipeline stages, so the
        host-array gather/scatter below cannot address the whole pool."""
        return self._write_pages is not None

    def export_pages(self, page_ids) -> dict:
        """Device→host gather of the named pages' K/V across every
        layer: the wire payload of a KV migration chunk. The caller must
        hold refcounts on the pages (the engine pins them) so the
        allocator cannot recycle them mid-pull.

        Returns ``{"k", "v"}`` host arrays of shape [L, m, KH, page, D].
        """
        ids = np.asarray(page_ids, np.int32)
        with self._pages_lock:
            k = self.pages["k"][:, ids]
            v = self.pages["v"][:, ids]
            return {"k": np.asarray(k), "v": np.asarray(v)}

    def import_pages(self, page_ids, data) -> None:
        """Host→device scatter of migrated page contents into freshly
        reserved pages (one page-granular write on the donated pool —
        never pool-sized). Thread-safe against the engine loop via the
        pages lock; the destination pages are allocator-reserved, so the
        write is disjoint from every live block table by construction."""
        with self._pages_lock:
            self.pages = self._write_pages(
                self.pages, self._put(np.asarray(page_ids, np.int32)),
                self._put(np.asarray(data["k"])),
                self._put(np.asarray(data["v"])))

    @property
    def supports_mixed_dispatch(self) -> bool:
        """Mixed (prefill+decode fused) dispatch: available off the pp
        path (the pp tick loop doesn't thread the fused program yet).
        With a LoRA stack the DECODE half of the fused program carries
        per-slot adapter deltas (``_decode_kwargs`` threads lora/
        lora_idx), so mixed-adapter decode batches still run in ONE
        dispatch; only adapter-bound PREFILL stays on the legacy chunk
        path (the fused prefill ops don't carry per-op slot plumbing —
        the engine's plan selector excludes those prompts)."""
        return self._pp == 1

    def mixed(self, prefill_plans: list, block_tables: np.ndarray,
              tokens: np.ndarray, pos: np.ndarray, temps: np.ndarray,
              eos_ids: np.ndarray, remaining: np.ndarray, n_steps: int,
              lora_idx: np.ndarray | None = None) -> np.ndarray:
        """ONE dispatch carrying the full decode burst plus up to the
        engine's prefill token budget of prompt chunks.

        prefill_plans: list of dicts ``{"block_table", "tokens",
        "start_pos", "handle", "take"}`` — page-aligned chunks of DISTINCT
        admitted prompts; a plan with a ``handle`` is its prompt's final
        chunk and stashes position ``take - 1``'s hidden state for
        first-token sampling, exactly like ``prefill``.
        """
        assert self.supports_mixed_dispatch
        ops = []
        op_live = []
        for p in prefill_plans:
            bt = np.asarray(p["block_table"], np.int32)
            ops.append((self._put(bt),
                        self._put(np.asarray(p["tokens"], np.int32)),
                        self._put(np.int32(p["start_pos"]))))
            op_live.append(self._bucket_pages(
                -(-int(p["start_pos"]) // self.page_size), bt.shape[0]))
        kwargs = self._decode_kwargs(pos, n_steps, block_tables, lora_idx)
        with annotate("engine.dispatch", kind="mixed", K=n_steps,
                      prefill_tokens=sum(int(p["take"]) for p in prefill_plans)), \
                self._pages_lock:
            toks, self._key, self.pages, hiddens = self._mixed(
                self.params, self.pages, tuple(ops),
                self._put(block_tables.astype(np.int32)),
                self._put(tokens.astype(np.int32)),
                self._put(pos.astype(np.int32)),
                self._put(temps.astype(np.float32)),
                self._put(eos_ids.astype(np.int32)),
                self._put(remaining.astype(np.int32)),
                self._key, config=self.config, page_size=self.page_size,
                n_steps=n_steps, prefill_live_pages=tuple(op_live), **kwargs,
            )
        for p, hidden in zip(prefill_plans, hiddens):
            if p.get("handle") is not None:
                self._hidden[p["handle"]] = hidden[p["take"] - 1]
        return self._sync(toks)[0]  # [n_steps, slots] — still the one sync

    @property
    def lm_head(self):
        return self.params["lm_head"]
