"""Continuous-batching inference engine over a paged KV cache.

The scheduler half of what the reference delegates to vLLM
(``AsyncLLMEngine`` in ``python/ray/llm/_internal/serve/deployments/llm/
vllm/vllm_engine.py:250``): requests arrive at any time, **chunked
prefill** interleaves with batched decode (bounding TTFT impact on
running streams), finished sequences free their pages immediately, and
hash-matched prompt prefixes reuse previously computed pages without
recomputation — full token blocks AND partial tail blocks, shared
read-only with copy-on-write forking at the first conflicting write
(vLLM/SGLang-style block-level prefix caching).

TPU shape discipline: decode always runs the full ``[max_slots]`` batch
(inactive slots write to private trash pages — branchless, one compiled
program for every occupancy), and prefill chunks are fixed-size buckets so
XLA compiles one program per bucket, not per prompt length.
"""

from __future__ import annotations

import hashlib
import itertools
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any

import logging

import numpy as np

from ..models.llama import LlamaConfig, PRESETS
from ..observability import loop_recorder
from ..observability.tracing import annotate
from .executor import LocalEngineExecutor

logger = logging.getLogger(__name__)


@dataclass
class Request:
    request_id: str
    prompt: list[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: int | None = None
    stop_ids: list[int] = field(default_factory=list)
    # LoRA adapter id (None/"" = base model); resolved to a device stack
    # slot at admission (reference: per-request `model` routing through
    # serve's multiplexed LoRA deployments)
    model: str | None = None
    # runtime state
    generated: list[int] = field(default_factory=list)
    slot: int = -1
    pos: int = 0                 # next position to write
    prefill_pos: int = 0         # prompt tokens already prefilled
    block_table: list[int] = field(default_factory=list)
    done: bool = False
    finish_reason: str = ""
    lora_slot: int = 0
    arrived_at: float = field(default_factory=time.monotonic)
    arrived_wall: float = field(default_factory=time.time)
    admitted_at: float | None = None   # monotonic, when a slot was taken
    prefill_chunks: int = 0            # chunks its prompt was prefilled in
    first_token_at: float | None = None
    first_token_wall: float | None = None
    cached_prefix_tokens: int = 0
    # Prefix sharing state: the first `shared_pages` block-table entries
    # are refcounted read-only cache pages; `cow_page` is the page
    # reserved at admission to receive the COW fork of a shared partial
    # tail block the suffix will write into (None once forked/unused).
    shared_pages: int = 0
    partial_len: int = 0
    cow_page: int | None = None
    # Disaggregated serving: a prefill-pool request computes its prompt's
    # KV and retires WITHOUT sampling (finish_reason "prefilled") — the
    # pages enter the prefix trie and ship to a decode replica instead.
    # ``pin_for_export`` keeps the retired pages refcounted until the
    # migration exporter releases them (``release_export_pins``), so
    # pool pressure can never recycle a page mid-transfer.
    prefill_only: bool = False
    pin_for_export: bool = False
    export_pinned: list[int] = field(default_factory=list)
    # End-to-end deadline (absolute wall clock, ``time.time()`` scale),
    # threaded from the serve proxy: a request that expires while still
    # WAITING fails fast without ever touching the engine; one that
    # expires mid-prefill/mid-decode is aborted and its pages freed the
    # same tick. None = never expires.
    deadline: float | None = None
    # Trace context ({"trace_id", "span_id"}) captured from the submitting
    # thread at add_request: the engine loop runs detached, so prefill/
    # decode spans parent onto this instead of any thread-local state.
    trace: dict | None = None
    # Speculative-decoding accounting (drafted tokens verified for this
    # request, drafts accepted, verify rounds that rolled a draft back)
    # — the per-request view behind the llm.speculate span.
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_rollbacks: int = 0
    # Flight recorder (observability/loop_recorder.py): a bounded,
    # always-on event timeline — admission, prefix hits, COW forks,
    # prefill chunks, first token, per-token ITL, speculation rounds,
    # migrations, shed/deadline, retire. On SLO breach it dumps ONCE as
    # a ``llm.request_timeline`` span (see ``InferenceEngine.
    # dump_timeline``).
    timeline: "object" = None

    def __post_init__(self):
        if self.timeline is None:
            from ..observability.loop_recorder import RequestTimeline

            self.timeline = RequestTimeline()


class QueueFullError(RuntimeError):
    """The engine's bounded admission queue (``max_queued_requests``)
    refused the request — overload protection's per-replica backpressure.
    Carries the HTTP shape the serve proxy answers with (503 +
    Retry-After) so the shed is honest and fast."""

    http_status = "503 Service Unavailable"
    reason = "replica_queue_full"

    def __init__(self, message: str, retry_after: int = 1):
        super().__init__(message)
        self.retry_after = max(1, int(retry_after))


class AdmissionFailed(RuntimeError):
    """The engine settled the request at admission without running it:
    its adapter failed to load, it named a model on an engine without an
    adapter manager, or its pages could not be had. The cause is in the
    replica's log; the serve proxy answers with this status."""

    http_status = "400 Bad Request"
    reason = "admission_failed"
    retry_after = None  # not a shed: retrying the same request fails again

    def __init__(self, request: "Request"):
        super().__init__(
            f"request {request.request_id} was refused at admission"
            + (f": model {request.model!r} is unknown to this engine or its "
               f"adapter failed to load" if request.model else ""))


class PageAllocator:
    """Page pool bookkeeping: free list, per-page refcounts, and a prefix
    TRIE keyed on token-block chain hashes (pages are immutable once
    cached, so a page whose chain matches can be shared read-only between
    sequences — the reference's automatic prefix caching, block-level as
    in vLLM/SGLang).

    The trie has two kinds of entries:

      * **full-block nodes** (``prefix_map``: chain hash -> page id) with
        parent/children edges, matched block-by-block by
        ``match_prefix``;
      * **partial tail blocks** (``_partials``: the raw token tuple of a
        sequence's last, partially-filled page, keyed under its parent
        node) matched by longest-common-prefix on ``match_partial`` — the
        reader maps the page read-only and COW-forks it (``fork``) before
        its first write lands mid-page.

    Eviction is LRU over refcount-0 cached pages only (shared-page pins
    always survive pressure), preferring LEAF entries so interior chain
    nodes outlive their extensions; evicting an interior node unlinks its
    now-unreachable cached descendants back to the free list.
    """

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self.free: list[int] = list(range(num_pages))
        self.refcount: dict[int, int] = {}
        # chain-hash of tokens[0:(i+1)*page] -> page_id, + LRU stamps for
        # eviction of refcount-0 cached pages.
        self.prefix_map: dict[bytes, int] = {}
        self.page_hash: dict[int, bytes] = {}
        self.last_used: dict[int, float] = {}
        # Trie edges over chain hashes (a chain hash IS a path identity,
        # so nodes are keyed by it directly; parents may be virtual —
        # the adapter-scoped root hash has no page).
        self._children: dict[bytes, set[bytes]] = {}
        self._parent: dict[bytes, bytes] = {}
        # Partial tail blocks: parent chain hash -> {token tuple: page_id}
        self._partials: dict[bytes, dict[tuple, int]] = {}
        self._partial_pages: dict[int, tuple[bytes, tuple]] = {}
        # Tiered-KV hook: called as ``on_evict(page_id, chain_hash)`` for
        # every cached FULL-block page about to be recycled (the victim
        # and its unreachable cached descendants), BEFORE its data is
        # reused — the engine spills the page to host RAM keyed by its
        # chain hash so a future match_prefix can restore it.
        self.on_evict = None

    def available(self) -> int:
        return len(self.free) + sum(
            1 for p in self.page_hash if self.refcount.get(p, 0) == 0
        ) + sum(
            1 for p in self._partial_pages if self.refcount.get(p, 0) == 0
        )

    def alloc(self, n: int) -> list[int] | None:
        if self.available() < n:
            return None
        out = []
        for _ in range(n):
            if self.free:
                pid = self.free.pop()
            else:
                pid = self._evict_one()
            self.refcount[pid] = 1
            out.append(pid)
        return out

    def fork(self, page_id: int) -> int | None:
        """COW fork: allocate a fresh page to receive a copy of shared
        ``page_id`` (the caller device-copies the rows and swaps its own
        table entry). Exactly one page — the shared original keeps its
        refcount and cache entries untouched for its other readers."""
        got = self.alloc(1)
        return got[0] if got is not None else None

    def _unlink(self, page_id: int) -> None:
        """Drop every cache entry for ``page_id`` (full-block node edges
        or partial-tail entry). The page itself is NOT freed."""
        h = self.page_hash.pop(page_id, None)
        if h is not None:
            self.prefix_map.pop(h, None)
            parent = self._parent.pop(h, None)
            if parent is not None and parent in self._children:
                self._children[parent].discard(h)
                if not self._children[parent]:
                    del self._children[parent]
        entry = self._partial_pages.pop(page_id, None)
        if entry is not None:
            parent, key = entry
            sub = self._partials.get(parent)
            if sub is not None:
                sub.pop(key, None)
                if not sub:
                    del self._partials[parent]

    def _evict_one(self) -> int:
        """LRU victim among refcount-0 cached pages, leaf entries first.
        Evicting an interior chain node also unlinks its (unreachable)
        cached descendants back to the free list."""
        best = None
        for h, p in self.prefix_map.items():
            if self.refcount.get(p, 0):
                continue
            leaf = 0 if (h not in self._children
                         and h not in self._partials) else 1
            key = (leaf, self.last_used.get(p, 0.0))
            if best is None or key < best[0]:
                best = (key, p, h)
        for p in self._partial_pages:
            if self.refcount.get(p, 0):
                continue
            key = (0, self.last_used.get(p, 0.0))
            if best is None or key < best[0]:
                best = (key, p, None)
        _, victim, victim_hash = best
        if self.on_evict is not None and victim_hash is not None:
            # Spill BEFORE unlink/reuse: the page still holds valid K/V.
            self.on_evict(victim, victim_hash)
        descendants = []
        if victim_hash is not None and victim_hash in self._children:
            stack = [victim_hash]
            while stack:
                h = stack.pop()
                stack.extend(self._children.pop(h, ()))
                for key, p in self._partials.pop(h, {}).items():
                    descendants.append(p)
                    self._partial_pages.pop(p, None)
                if h != victim_hash:
                    p = self.prefix_map.pop(h, None)
                    self._parent.pop(h, None)
                    if p is not None:
                        if self.on_evict is not None:
                            self.on_evict(p, h)
                        self.page_hash.pop(p, None)
                        descendants.append(p)
        self._unlink(victim)
        for p in descendants:
            # Unreachable now; cached refcount-0 descendants go straight
            # back to the pool, pinned ones free on their final release.
            if not self.refcount.get(p, 0) and p != victim \
                    and p not in self.free:
                self.free.append(p)
        return victim

    def share(self, page_id: int) -> None:
        self.refcount[page_id] = self.refcount.get(page_id, 0) + 1
        self.last_used[page_id] = time.monotonic()

    def release(self, page_id: int) -> None:
        count = self.refcount.get(page_id, 1) - 1
        self.refcount[page_id] = count
        if count <= 0:
            self.refcount.pop(page_id, None)
            if page_id in self.page_hash or page_id in self._partial_pages:
                self.last_used[page_id] = time.monotonic()  # evictable, cached
            else:
                self.free.append(page_id)

    def register_prefix(self, page_id: int, chain_hash: bytes,
                        parent_hash: bytes = b"") -> None:
        if chain_hash in self.prefix_map or page_id in self.page_hash \
                or page_id in self._partial_pages:
            return
        self.prefix_map[chain_hash] = page_id
        self.page_hash[page_id] = chain_hash
        self.last_used[page_id] = time.monotonic()
        self._parent[chain_hash] = parent_hash
        self._children.setdefault(parent_hash, set()).add(chain_hash)

    def register_partial(self, parent_hash: bytes, tokens: tuple,
                         page_id: int) -> None:
        """Cache a sequence's partially-filled tail page: ``tokens`` are
        the page's valid rows, stored raw so match_partial can take a
        shorter common prefix than the producer wrote."""
        if not tokens or page_id in self.page_hash \
                or page_id in self._partial_pages:
            return
        sub = self._partials.setdefault(parent_hash, {})
        if tokens in sub:
            return
        sub[tokens] = page_id
        self._partial_pages[page_id] = (parent_hash, tokens)
        self.last_used[page_id] = time.monotonic()

    def lookup_prefix(self, chain_hash: bytes) -> int | None:
        return self.prefix_map.get(chain_hash)

    def match_prefix(self, chain_hashes: list[bytes]) -> list[int]:
        """Longest cached chain: one page per matched full block, in
        order, stopping at the first miss."""
        hits: list[int] = []
        for h in chain_hashes:
            pid = self.prefix_map.get(h)
            if pid is None:
                break
            hits.append(pid)
        return hits

    def match_partial(self, parent_hash: bytes, tokens: tuple,
                      cap: int) -> tuple[int, int] | None:
        """Best partial tail-block under ``parent_hash``: the entry with
        the longest common prefix against ``tokens``, capped at ``cap``
        rows (the caller caps so at least one prompt token is always
        computed). Returns ``(page_id, matched_len)`` or None."""
        best = None
        for entry, pid in self._partials.get(parent_hash, {}).items():
            n = 0
            for a, b in zip(entry, tokens):
                if a != b:
                    break
                n += 1
            n = min(n, cap)
            if n > 0 and (best is None or n > best[1]):
                best = (pid, n)
        return best


class InferenceEngine:
    """Paged-KV engine: this class is the host-side SCHEDULER (slots,
    pages, prefix cache, admission); every device interaction goes through
    an executor — ``LocalEngineExecutor`` for this process's devices
    (optionally a tp mesh), or a multi-host fan-out (``multihost.py``).
    ``add_request``/``cancel`` are thread-safe; ``step`` must be called
    from one driver thread (the serving replica's engine loop)."""

    def __init__(
        self,
        config: LlamaConfig | str = "debug",
        params=None,
        *,
        max_slots: int = 8,
        max_len: int = 512,
        page_size: int = 16,
        num_pages: int | None = None,
        prefill_chunk_size: int = 128,
        decode_steps_per_dispatch: int = 8,
        enable_prefix_cache: bool = True,
        mesh=None,
        executor=None,
        seed: int = 0,
        attention_impl: str = "auto",
        lora_config=None,
        prefill_token_budget: int | None = None,
        max_prefill_seqs_per_step: int = 2,
        decode_starvation_limit: int = 8,
        host_kv_cache_pages: int = 0,
        max_queued_requests: int = 0,
        admission_watermark_pages: int | None = None,
        speculation_config=None,
    ):
        self.config = PRESETS[config] if isinstance(config, str) else config
        self.mesh = mesh
        self.max_slots = max_slots
        self.page_size = page_size
        assert max_len % page_size == 0, "max_len must be a multiple of page_size"
        self.max_len = max_len
        self.max_pages_per_seq = max_len // page_size
        self.prefill_chunk_size = min(prefill_chunk_size, max_len)
        assert self.prefill_chunk_size % page_size == 0
        self.enable_prefix_cache = enable_prefix_cache
        # Decode steps fused into one device dispatch (lax.scan): a host
        # sync costs a dispatch round trip, so the engine syncs once per
        # K tokens instead of once per token.
        self.decode_steps_per_dispatch = max(1, decode_steps_per_dispatch)
        # Token-budget mixed dispatch (Sarathi/vLLM chunked-prefill
        # scheduling): each step carries the full decode batch PLUS up to
        # `prefill_token_budget` prompt tokens (≤ `max_prefill_seqs_per_step`
        # distinct prompts) in ONE fused dispatch, so a long prompt no
        # longer head-of-line-blocks running streams. Default budget = one
        # prefill chunk per step; 0 = legacy strict prefill-first
        # schedule. `decode_starvation_limit` guards the FALLBACK path
        # (pp meshes, LoRA stacks — no fused entry point): after that many
        # consecutive prefill-only steps with live decoders, one decode
        # burst is forced (0 disables the guard).
        if prefill_token_budget is None:
            prefill_token_budget = self.prefill_chunk_size
        self.prefill_token_budget = (
            max(page_size, prefill_token_budget) if prefill_token_budget else 0)
        self.max_prefill_seqs_per_step = max(1, max_prefill_seqs_per_step)
        self.decode_starvation_limit = max(0, decode_starvation_limit)
        self._starved_steps = 0
        self.num_pages = self.total_pages(max_slots, max_len, page_size, num_pages)
        if executor is None:
            executor = LocalEngineExecutor(
                self.config, params, max_slots=max_slots,
                num_pages=self.num_pages, page_size=page_size, mesh=mesh,
                seed=seed, attention_impl=attention_impl,
                lora_config=lora_config,
            )
        self.executor = executor
        # Resolved decode path ("paged" = v2 staging-buffer kernel: pool
        # read-only per K-step dispatch, one commit scatter at the
        # dispatch boundary; "dense" = bucketed gather). "auto" resolves
        # per backend/mesh in executor.resolve_attention_impl.
        self.attention_impl = getattr(executor, "attention_impl", "dense")
        # Speculative decoding (ROADMAP 5): a host-side drafter proposes
        # K tokens per active slot each decode tick and ONE verify
        # dispatch scores all K+1 positions (model.verify_block). None =
        # plain decode, bit-for-bit the pre-speculation path.
        from .speculative import SpeculationConfig

        self.speculation = SpeculationConfig.normalize(speculation_config)
        self._drafter = (self.speculation.build_drafter()
                         if self.speculation is not None else None)
        self.lora_manager = None
        if lora_config is not None:
            from .lora import LoRAManager

            self.lora_manager = LoRAManager(
                self.config, lora_config, executor.install_adapter)
        self._lora_idx = np.zeros(max_slots, np.int32)
        self.allocator = PageAllocator(self.num_pages)
        # Trash pages 0..max_slots-1 are permanently owned by their slot.
        for s in range(max_slots):
            self.allocator.free.remove(s)
        self._free_slots = list(range(max_slots))
        # Overload protection: bound on requests WAITING for admission
        # (0 = unbounded) — over it add_request sheds with QueueFullError
        # instead of letting the queue (and every waiter's TTFT) grow
        # without limit; and the admission watermark — extra free-page
        # headroom admission preserves on top of each request's
        # worst-case reservation (admission reserves prompt+max_tokens
        # growth up front, so a RUNNING slot can never hit a mid-decode
        # allocation failure; the watermark additionally keeps headroom
        # for in-flight KV imports/migrations).
        self.max_queued_requests = max(0, max_queued_requests)
        if admission_watermark_pages is None:
            from ..core.config import get_config

            admission_watermark_pages = \
                get_config().serve_admission_watermark_pages
        self.admission_watermark_pages = max(0, admission_watermark_pages)
        self._active: dict[int, Request] = {}       # decoding
        self._prefilling: deque[Request] = deque()  # admitted, chunks pending
        # Prefilled requests awaiting their (batched) first-token sample:
        # a burst of arrivals costs ONE sampling sync, not one each.
        self._pending_first: list[tuple[Request, Any]] = []
        self._waiting: deque[Request] = deque()
        self._lock = threading.Lock()
        self._counter = itertools.count()
        self._handle_counter = itertools.count(1)
        # Host-side mirrors of decode-step inputs. Block tables default to
        # the slot's trash page so inactive slots never corrupt live pages.
        self._tokens = np.zeros(max_slots, np.int32)
        self._pos = np.zeros(max_slots, np.int32)
        self._block_tables = np.tile(
            np.arange(max_slots, dtype=np.int32)[:, None], (1, self.max_pages_per_seq)
        )
        # Copy-on-write prefix sharing (partial tail blocks) needs the
        # executor's page-copy op + row-granular prefill writes; full
        # page-aligned block sharing works everywhere.
        self._cow_enabled = (enable_prefix_cache and
                             getattr(executor, "supports_prefix_cow", False))
        # Tiered KV (host-RAM spill tier under the device page pool):
        # refcount-0 trie pages about to be evicted export to a bounded
        # host cache keyed by chain hash, and a future match_prefix miss
        # restores them into fresh pages instead of recomputing. 0
        # disables the tier (evicted pages just die, as before).
        self.host_kv_cache_pages = max(0, host_kv_cache_pages)
        self._host_kv: "OrderedDict[bytes, dict]" = OrderedDict()
        if self.host_kv_cache_pages and enable_prefix_cache and \
                getattr(executor, "supports_kv_migration", False):
            self.allocator.on_evict = self._spill_page_to_host
        self.metrics = {"prefix_hit_pages": 0, "prefix_lookup_pages": 0,
                        # True-reuse accounting: prompt tokens served from
                        # shared pages (full blocks + partial tails) vs
                        # prompt tokens admitted, and COW fork count.
                        "prefix_cached_tokens": 0, "prompt_tokens": 0,
                        "cow_forks": 0,
                        "prefill_chunks": 0,
                        "decode_steps": 0, "decode_dispatches": 0,
                        # Where a step's time goes, always on: engine steps
                        # that had work, their wall time split into the
                        # wait for device results (the executor's
                        # ``engine.sync``) and everything else on the host;
                        # and arrival-to-admission wait per admitted request.
                        "steps": 0, "step_host_ms_sum": 0.0,
                        "step_sync_ms_sum": 0.0,
                        "queue_wait_ms_sum": 0.0, "queue_wait_count": 0,
                        # Per-step schedule mix: how many engine steps ran
                        # fused prefill+decode vs either alone (plus
                        # first-token flush-only steps).
                        "engine_step_mix": {"mixed": 0, "prefill": 0,
                                            "decode": 0, "flush": 0},
                        # Steps where live decode streams waited behind a
                        # prefill-only dispatch (0 under mixed dispatch —
                        # the number the token budget exists to kill).
                        "decode_stall_steps": 0,
                        # Engine operations streamed through a persistent
                        # compiled loop (dag/loop.py) instead of per-tick
                        # actor RPC — nonzero exactly when the executor
                        # drives a loop (sharded pp path).
                        "dag_loop_ticks": 0,
                        # KV-page migration (disaggregated serving / spill
                        # migration): pages shipped out of / into this
                        # engine's pool, migration round counts, import
                        # bytes, and reservation failures that fell back
                        # to a cold prefill.
                        "kv_pages_exported": 0, "kv_pages_imported": 0,
                        "kv_migrations_out": 0, "kv_migrations_in": 0,
                        "kv_import_failures": 0, "kv_import_bytes": 0,
                        # Tiered KV: evicted trie pages spilled to host
                        # RAM and pages restored from it on a later hit.
                        "host_kv_spilled_pages": 0,
                        "host_kv_restored_pages": 0,
                        # Overload protection: deadline expiries by where
                        # the request was (queued = never touched the
                        # engine; running = aborted mid-prefill/decode,
                        # pages freed the same tick), bounded-queue sheds,
                        # and admission-watermark refusals (the request
                        # stays queued, never bounces to the client).
                        "deadline_expired_queued": 0,
                        "deadline_expired_running": 0,
                        "queue_rejects": 0,
                        "admission_rejects": 0,
                        "admission_failed": 0,
                        # Tenancy: admissions deferred because every
                        # HBM-resident adapter was pinned by an in-flight
                        # request (the request waits, it is not failed).
                        "adapter_defers": 0,
                        # Speculative decoding: drafted tokens sent to
                        # verification, drafts the target accepted,
                        # tokens emitted by verify dispatches, verify
                        # dispatch count, and slot-rounds that discarded
                        # at least one drafted token (the rollback — its
                        # staged K/V committed to the trash page, never
                        # a pool page).
                        "spec_drafted_tokens": 0,
                        "spec_accepted_tokens": 0,
                        "spec_emitted_tokens": 0,
                        "spec_dispatches": 0,
                        # (dispatch, active slot) pairs — the
                        # denominator of spec_tokens_per_dispatch, so
                        # the ratio is per-sequence per-forward (1.0 =
                        # plain decode), independent of batch size.
                        "spec_slot_rounds": 0,
                        "spec_rollbacks": 0,
                        # Weight residency (always-warm fleet): demotions
                        # of the weight pytree to host RAM, promotions
                        # back to device (device_put, not a reload), and
                        # the last promotion's wall time.
                        "weights_demoted": 0, "weights_promoted": 0,
                        "weight_promote_ms": 0.0,
                        # Flight recorder: request timelines dumped as
                        # llm.request_timeline spans on SLO breach
                        # (deadline expiry, shed, TTFT-SLO breach) —
                        # at most one dump per request.
                        "timeline_dumps": 0}
        # Last few breach dumps, for serve.status() "last-breach" rows
        # (the full event payload lives in the span store).
        self._breach_samples: deque[dict] = deque(maxlen=8)
        # Weight residency (always-warm fleet): the host-RAM copy of the
        # weight pytree while demoted. The lock serializes demote /
        # promote against each other and against admission's lazy
        # re-promotion.
        self._host_params = None
        self._residency_lock = threading.Lock()

    @staticmethod
    def total_pages(max_slots: int, max_len: int, page_size: int,
                    num_pages: int | None = None) -> int:
        """Pool size: per-slot trash pages + usable pages (default: enough
        for every slot to hold a full-length sequence). Exposed so a
        remote executor (multi-host shards) can be pre-built with the same
        geometry the engine will assume."""
        usable = num_pages if num_pages is not None else max_slots * (max_len // page_size)
        return max_slots + usable

    # ------------------------------------------------------------- admission
    def add_request(self, request: Request) -> None:
        if len(request.prompt) >= self.max_len:
            raise ValueError(
                f"prompt of {len(request.prompt)} tokens >= max_len {self.max_len}"
            )
        if not request.prompt:
            raise ValueError("empty prompt")
        if request.trace is None:
            from ..observability import tracing

            request.trace = tracing.current_wire()
        # Scale-to-zero wake: the first request onto a demoted engine
        # promotes the host-resident weights before it queues, so its
        # TTFT carries the device_put, not a crash or a cold load.
        self._ensure_weights_resident()
        with self._lock:
            if self.max_queued_requests and \
                    len(self._waiting) >= self.max_queued_requests:
                self.metrics["queue_rejects"] += 1
                err = QueueFullError(
                    f"engine admission queue is full "
                    f"({len(self._waiting)} waiting, bound "
                    f"{self.max_queued_requests})",
                    retry_after=self._queue_retry_after_locked())
                request.timeline.add(loop_recorder.EV_SHED, 0)
                self.dump_timeline(request, "shed_queue_full")
                raise err
            request.timeline.add(loop_recorder.EV_ADMIT, len(request.prompt),
                                 now=request.arrived_wall)
            self._waiting.append(request)

    def _queue_retry_after_locked(self) -> int:
        """Retry-After for a replica-queue shed: the waiting backlog over
        the concurrency the engine actually serves (its slots)."""
        backlog = len(self._waiting) + len(self._prefilling) + \
            len(self._active) + 1
        return max(1, min(60, -(-backlog // max(1, self.max_slots))))

    def cancel(self, request_id: str) -> None:
        with self._lock:
            keep: deque[Request] = deque()
            for r in self._waiting:
                if r.request_id == request_id:
                    r.done, r.finish_reason = True, "cancelled"
                else:
                    keep.append(r)
            self._waiting = keep
            keep = deque()
            for r in self._prefilling:
                if r.request_id == request_id:
                    r.done, r.finish_reason = True, "cancelled"
                    self._retire_locked(r)
                else:
                    keep.append(r)
            self._prefilling = keep
            for slot, r in list(self._active.items()):
                if r.request_id == request_id:
                    r.done, r.finish_reason = True, "cancelled"
                    self._retire_locked(r)
            for r, _h in self._pending_first:
                if r.request_id == request_id and not r.done:
                    r.done, r.finish_reason = True, "cancelled"
                    self._retire_locked(r)  # flush skips done entries

    @property
    def has_work(self) -> bool:
        with self._lock:
            return bool(self._waiting or self._prefilling or self._active
                        or self._pending_first)

    def _retire_locked(self, r: Request) -> None:
        """Free the request's slot and pages (idempotent). Full PROMPT
        pages enter the prefix cache instead of the free list."""
        if r.slot != -1 or r.block_table:
            # First retire only (the guard is the idempotence condition
            # below): close the flight-recorder timeline.
            r.timeline.add(loop_recorder.EV_RETIRE, len(r.generated))
        if r.slot >= 0 and r.slot in self._active:
            self._active.pop(r.slot, None)
            self._free_slots.append(r.slot)
            self._block_tables[r.slot, :] = r.slot  # back to trash page
            self._lora_idx[r.slot] = 0
            # Reset the host pos mirror too: the executor's live_pages
            # bucket is max over ALL slots, and a stale 8k pos from a
            # retired request would inflate every later batch's
            # attention width for the engine's lifetime.
            self._pos[r.slot] = 0
        elif r.slot >= 0 and r.slot in self._free_slots:
            pass  # already retired
        elif r.slot >= 0:
            self._free_slots.append(r.slot)
            self._block_tables[r.slot, :] = r.slot
            self._pos[r.slot] = 0
        if r.lora_slot and self.lora_manager is not None:
            self.lora_manager.release(r.lora_slot)
            r.lora_slot = 0
        if r.block_table:
            if r.pin_for_export and not r.export_pinned:
                # Migration source: keep one ref per page past retire so
                # the exporter can finish streaming them; released by
                # release_export_pins once the transfer ends.
                for pid in r.block_table:
                    self.allocator.share(pid)
                r.export_pinned = list(r.block_table)
            if self.enable_prefix_cache and r.finish_reason != "admission_failed":
                # Register only pages whose K/V was actually COMPUTED: a
                # cancel mid-prefill leaves later prompt pages holding
                # garbage — caching them would poison future prefix hits.
                # The chain now covers the FULL sequence (prompt +
                # generated tokens — their K/V is a pure function of the
                # token ids, which the chain hash captures), so multi-turn
                # follow-ups whose prompt embeds the previous answer hit
                # too. The last generated token's K/V is never written
                # (it was emitted, not fed back), hence the -1.
                ps = self.page_size
                seq = list(r.prompt) + list(r.generated)
                if r.prefill_pos < len(r.prompt):
                    valid = r.prefill_pos  # cancelled mid-prefill
                else:
                    valid = len(r.prompt) + max(0, len(r.generated) - 1)
                valid = min(valid, len(r.block_table) * ps)
                full_pages = valid // ps
                h = hashlib.sha1()
                # Adapter-specific K/V must never be shared across models
                h.update((r.model or "").encode())
                parent = h.digest()
                for i in range(full_pages):
                    h.update(bytes(np.asarray(
                        seq[i * ps:(i + 1) * ps], np.int32).tobytes()))
                    self.allocator.register_prefix(
                        r.block_table[i], h.digest(), parent)
                    parent = h.digest()
                if self._cow_enabled and full_pages < len(r.block_table):
                    # Partial tail block: cache the raw token run so a
                    # follow-up can map the page read-only and COW-fork
                    # it at its first mid-page write.
                    tail = tuple(int(t) for t in seq[full_pages * ps:valid])
                    if tail:
                        self.allocator.register_partial(
                            parent, tail, r.block_table[full_pages])
            for pid in r.block_table:
                self.allocator.release(pid)
            r.block_table = []
        if r.cow_page is not None:
            # Reserved fork page never used (cancel before the first
            # suffix write): back to the pool.
            self.allocator.release(r.cow_page)
            r.cow_page = None
        r.slot = -1

    # ------------------------------------------------------------------ step
    @property
    def mixed_dispatch_enabled(self) -> bool:
        """True when steps fuse prefill chunks into the decode dispatch
        (token budget > 0 and the executor has the fused entry point).
        A LoRA stack no longer disables this: the fused dispatch's
        decode half carries the per-slot ``lora_idx``, so slots holding
        DIFFERENT adapters decode together in one dispatch — only
        adapter-bound prefills stay legacy (plan selection skips them,
        and an all-adapter prefill queue falls back per step)."""
        return (self.prefill_token_budget > 0
                and getattr(self.executor, "supports_mixed_dispatch", False))

    @property
    def prefix_cache_hit_rate(self) -> float:
        """Fraction of cacheable prompt pages served from the prefix
        cache (hit pages / looked-up pages since engine start). A TRUE
        reuse rate: every hit page is mapped into the slot's table and
        its tokens are skipped by the suffix prefill."""
        lookups = self.metrics.get("prefix_lookup_pages", 0)
        return self.metrics["prefix_hit_pages"] / lookups if lookups else 0.0

    @property
    def prefill_suffix_frac(self) -> float:
        """Fraction of admitted prompt tokens actually prefilled (the
        cold suffix); 1.0 = no prefix reuse. TTFT scales with this."""
        total = self.metrics.get("prompt_tokens", 0)
        if not total:
            return 1.0
        return 1.0 - self.metrics["prefix_cached_tokens"] / total

    @property
    def speculation_enabled(self) -> bool:
        """True when decode ticks run draft + verify: a speculation
        config is set AND the executor has the verify entry point (off
        pp / LoRA — those paths decode plain, exactly as before)."""
        return (self.speculation is not None
                and self._drafter is not None
                and self.lora_manager is None
                and getattr(self.executor, "supports_speculation", False))

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens the target model accepted (0-1
        since engine start). The n-gram drafter's number is traffic-
        dependent: repetitive/multi-turn prompts accept high."""
        drafted = self.metrics.get("spec_drafted_tokens", 0)
        return (self.metrics["spec_accepted_tokens"] / drafted
                if drafted else 0.0)

    @property
    def spec_tokens_per_dispatch(self) -> float:
        """Tokens emitted per slot per verify dispatch — 1.0 is exactly
        what one plain decode forward yields per sequence, so > 1.0
        means speculation is amortizing target-model forwards. The
        accept-0 floor guarantees it never drops below 1.0."""
        n = self.metrics.get("spec_slot_rounds", 0)
        return self.metrics["spec_emitted_tokens"] / n if n else 0.0

    def step(self) -> list[dict]:
        """Advance the engine one tick: admit waiting requests while slots
        and pages allow, then dispatch.

        With mixed dispatch enabled (the default off the pp/LoRA paths),
        a step with both live decoders and pending prefill issues ONE
        fused dispatch: the full ``[max_slots]`` decode burst plus up to
        ``prefill_token_budget`` prompt tokens — prefill rides along with
        decode instead of preempting it, and just-finished prompts flush
        their batched first-token samples the same step.

        The legacy schedule (budget 0, pp meshes, LoRA stacks) runs ONE
        prefill chunk per step strictly ahead of decode, with the
        starvation guard forcing a decode burst after
        ``decode_starvation_limit`` consecutive stalled steps.

        Returns emission events ``{"request_id", "token", "done",
        "finish_reason"}``."""
        with self._lock:
            counts = (len(self._waiting), len(self._prefilling), len(self._active))
            busy = bool(any(counts) or self._pending_first)
        if not busy:
            return self._step()
        t0, sync0 = time.monotonic(), self.executor.sync_s
        with annotate("engine.step", waiting=counts[0], prefilling=counts[1],
                      active=counts[2]):
            events = self._step()
        sync_ms = (self.executor.sync_s - sync0) * 1e3
        self.metrics["steps"] += 1
        self.metrics["step_sync_ms_sum"] += sync_ms
        self.metrics["step_host_ms_sum"] += (time.monotonic() - t0) * 1e3 - sync_ms
        return events

    def _step(self) -> list[dict]:
        if self.has_work:
            # Belt-and-braces for a demote racing admission: no dispatch
            # ever runs against executor.params=None.
            self._ensure_weights_resident()
        # settled without a dispatch: deadlines that passed, and what
        # admission refused ("admission_failed")
        settled = self._expire_deadlines() + self._admit()
        events = self._step_scheduled()
        return settled + events if settled else events

    def _expire_deadlines(self) -> list[dict]:
        """Overload protection: sweep expired request deadlines at the
        tick boundary. A request that expires while still WAITING never
        touches the engine (no slot, no pages, no prefill) — counter
        ``deadline_expired_queued``; one that expires mid-prefill /
        mid-decode / awaiting its first sample is aborted and retired
        THIS tick, returning its slot, pages, and trie pins to the pool
        — counter ``deadline_expired_running``. Emits a terminal event
        per expiry so streams end promptly with finish_reason
        "deadline"."""
        events: list[dict] = []
        breached: list[Request] = []
        now = time.time()
        with self._lock:
            if self._waiting and any(
                    r.deadline is not None and now >= r.deadline
                    for r in self._waiting):
                keep: deque[Request] = deque()
                for r in self._waiting:
                    if r.deadline is not None and now >= r.deadline:
                        r.done, r.finish_reason = True, "deadline"
                        self.metrics["deadline_expired_queued"] += 1
                        r.timeline.add(loop_recorder.EV_DEADLINE, 0, now=now)
                        breached.append(r)
                        events.append({"request_id": r.request_id,
                                       "token": -1, "done": True,
                                       "finish_reason": "deadline"})
                    else:
                        keep.append(r)
                self._waiting = keep
            expired: list[Request] = []
            if any(r.deadline is not None and now >= r.deadline
                   for r in self._prefilling):
                keep = deque()
                for r in self._prefilling:
                    if r.deadline is not None and now >= r.deadline \
                            and not r.done:
                        expired.append(r)
                    else:
                        keep.append(r)
                self._prefilling = keep
            for r in list(self._active.values()):
                if r.deadline is not None and now >= r.deadline \
                        and not r.done:
                    expired.append(r)
            for r, _h in self._pending_first:
                if r.deadline is not None and now >= r.deadline \
                        and not r.done:
                    expired.append(r)  # the flush drops its handle
            for r in expired:
                r.done, r.finish_reason = True, "deadline"
                r.timeline.add(loop_recorder.EV_DEADLINE, 0, now=now)
                self._retire_locked(r)
                self.metrics["deadline_expired_running"] += 1
                breached.append(r)
                events.append({"request_id": r.request_id, "token": -1,
                               "done": True, "finish_reason": "deadline"})
        for r in expired:
            self._record_decode_span(r)
        for r in breached:
            self.dump_timeline(r, "deadline")
        return events

    def _step_scheduled(self) -> list[dict]:
        mix = self.metrics["engine_step_mix"]
        with self._lock:
            r = self._prefilling[0] if self._prefilling else None
            has_active = bool(self._active)
        if r is not None and has_active and self.mixed_dispatch_enabled:
            events = self._mixed_step()
            if events is not None:
                mix["mixed"] += 1
                self._starved_steps = 0
                if self._pending_first:
                    events = events + self._flush_first_samples()
                return events
            # no fusable prefill candidate (e.g. all adapter-bound):
            # fall through to the legacy schedule for this step
        if r is not None:
            if (has_active and self.decode_starvation_limit
                    and self._starved_steps >= self.decode_starvation_limit):
                self._starved_steps = 0
                mix["decode"] += 1
                return self._decode_all()
            if has_active:
                self._starved_steps += 1
                self.metrics["decode_stall_steps"] += 1
            events = self._prefill_chunk_one(r)
            mix["prefill"] += 1
            with self._lock:
                drained = not self._prefilling
            if drained and self._pending_first:
                events = events + self._flush_first_samples()
            return events
        self._starved_steps = 0
        if self._pending_first:
            mix["flush"] += 1
            return self._flush_first_samples()
        if self._active:
            mix["decode"] += 1
            return self._decode_all()
        return []

    def _admit(self) -> list[dict]:
        """Admit what fits; returns the terminal events of the requests
        that admission settled instead (``"admission_failed"``)."""
        with annotate("engine.admit") as span:
            admitted, failed = self._admit_waiting()
            span.set_metadata(admitted=len(admitted))
        now = time.monotonic()
        for r in admitted:
            r.admitted_at = now
            self.metrics["queue_wait_ms_sum"] += (now - r.arrived_at) * 1e3
            self.metrics["queue_wait_count"] += 1
        self.metrics["admission_failed"] += len(failed)
        return [{"request_id": r.request_id, "token": -1, "done": True,
                 "finish_reason": r.finish_reason} for r in failed]

    def _admit_waiting(self) -> tuple[list[Request], list[Request]]:
        """Move waiting requests into slots: ``(admitted, failed)``. One that
        cannot ever run is marked done and failed, its pages released."""
        admitted: list[Request] = []
        failed: list[Request] = []
        with self._lock:
            while self._waiting and self._free_slots:
                r = self._waiting[0]
                # Worst-case pages so a running request can never OOM the
                # pool mid-decode (admission control replaces page faults).
                total_tokens = len(r.prompt) + r.max_new_tokens
                n_pages = min(
                    (total_tokens + self.page_size - 1) // self.page_size,
                    self.max_pages_per_seq,
                )
                hits: list[int] = []
                partial: tuple[int, int] | None = None
                if self.enable_prefix_cache:
                    # Hit pages (and the partial) arrive PINNED: refcounts
                    # are bumped at match time, before any alloc can run —
                    # alloc's LRU eviction only skips refcount>0 pages, so
                    # an unpinned hit page could be evicted and handed
                    # back as "fresh" (the same physical page at two
                    # block-table positions: silent KV corruption), and
                    # the host-tier restore path allocs mid-match.
                    hits, partial = self._prefix_hits(r)
                # A partial hit does not shrink the reservation: the
                # fresh allocation keeps one spare page as the reserved
                # COW fork target, so the write-triggered fork can never
                # fail under pressure mid-stream.
                #
                # Admission watermark: the worst-case reservation taken
                # HERE is what guarantees a running slot never hits a
                # mid-decode allocation failure because of a newly
                # admitted one — refusing (and counting) below the
                # free-page watermark keeps the request IN the queue
                # (head-of-line wait), never bouncing it to the client.
                if self.allocator.available() < \
                        n_pages - len(hits) + self.admission_watermark_pages:
                    self._unpin_hits_locked(hits, partial)
                    self.metrics["admission_rejects"] += 1
                    break  # head-of-line: wait for pages to free
                self._waiting.popleft()
                fresh = self.allocator.alloc(n_pages - len(hits))
                if fresh is None:  # race-free under lock, but be safe
                    self._unpin_hits_locked(hits, partial)
                    r.done, r.finish_reason = True, "admission_failed"
                    failed.append(r)
                    continue
                if partial is not None:
                    # Shared partial tail block maps read-only at the
                    # suffix position; fresh[0] is the reserved fork.
                    r.cow_page = fresh[0]
                    r.partial_len = partial[1]
                    r.block_table = hits + [partial[0]] + fresh[1:]
                else:
                    r.block_table = hits + fresh
                r.shared_pages = len(hits) + (1 if partial is not None else 0)
                r.prefill_pos = len(hits) * self.page_size + (
                    partial[1] if partial is not None else 0)
                r.cached_prefix_tokens = r.prefill_pos
                self.metrics["prefix_hit_pages"] += len(hits)
                self.metrics["prefix_cached_tokens"] += r.prefill_pos
                self.metrics["prompt_tokens"] += len(r.prompt)
                if r.model and self.lora_manager is not None:
                    try:
                        # May read the adapter from storage + write the
                        # device stack; engine-loop blocking is the
                        # admission cost of a cold adapter (LRU-cached
                        # after).
                        r.lora_slot = self.lora_manager.acquire(r.model)
                    except Exception as e:
                        from .tenancy import AdapterCapacityError

                        self._release_admission_locked(r)
                        if isinstance(e, AdapterCapacityError):
                            # Every resident adapter is pinned by an
                            # in-flight request: a QUEUEING condition,
                            # not a client error — the request stays at
                            # the head of the queue until a finishing
                            # request unpins a slot. Back out the reuse
                            # accounting taken above so the retry does
                            # not double-count.
                            self.metrics["prefix_hit_pages"] -= len(hits)
                            self.metrics["prefix_cached_tokens"] -= \
                                r.cached_prefix_tokens
                            self.metrics["prompt_tokens"] -= len(r.prompt)
                            self._waiting.appendleft(r)
                            self.metrics["adapter_defers"] += 1
                            break
                        r.done, r.finish_reason = True, "admission_failed"
                        failed.append(r)
                        logger.warning("adapter %r load failed: %s", r.model, e)
                        continue
                elif r.model and self.lora_manager is None:
                    self._release_admission_locked(r)
                    r.done, r.finish_reason = True, "admission_failed"
                    failed.append(r)
                    continue
                r.slot = self._free_slots.pop()
                self._lora_idx[r.slot] = r.lora_slot
                self._block_tables[r.slot, :len(r.block_table)] = r.block_table
                self._prefilling.append(r)
                admitted.append(r)
        for r in admitted:
            if r.cached_prefix_tokens:
                r.timeline.add(loop_recorder.EV_PREFIX_HIT,
                               r.cached_prefix_tokens)
            self._record_prefix_match_span(r)
        return admitted, failed

    def _release_admission_locked(self, r: Request) -> None:
        """Undo a half-admitted request's page state (shared refs, fresh
        pages, the reserved COW fork)."""
        for pid in r.block_table:
            self.allocator.release(pid)
        r.block_table = []
        if r.cow_page is not None:
            self.allocator.release(r.cow_page)
            r.cow_page = None
        r.shared_pages = 0
        r.partial_len = 0
        r.prefill_pos = 0

    def _record_prefix_match_span(self, r: Request) -> None:
        """One span per admission: how much of the prompt the prefix
        trie served (full-block hits + partial tail rows) — the
        per-request view behind ``prefix_cache_hit_rate``."""
        if not r.trace:
            return
        from ..observability import tracing

        now = time.time()
        tracing.record_span(tracing.make_span(
            "llm.prefix_match", "llm", r.arrived_wall, now,
            r.trace.get("trace_id", ""), r.trace.get("span_id", ""),
            attrs={"request_id": r.request_id,
                   "prompt_tokens": len(r.prompt),
                   "cached_tokens": r.cached_prefix_tokens,
                   "hit_pages": r.shared_pages,
                   "partial_tokens": r.partial_len}))

    def _prefix_hits(self, r: Request) -> tuple[list[int],
                                                tuple[int, int] | None]:
        """Longest cached chain covering the prompt: full token-block
        pages from the trie, plus (with COW support) the best partial
        tail-block match at the boundary — capped so at least one prompt
        token is always computed (its hidden state seeds sampling — the
        reference caps identically). Returns ``(full_hit_pages,
        (partial_page, matched_rows) | None)``."""
        ps = self.page_size
        max_hit_pages = (len(r.prompt) - 1) // ps
        self.metrics["prefix_lookup_pages"] += max_hit_pages
        root, chain = self._chain_hashes(r.prompt, r.model)
        hashes = chain[:max_hit_pages]
        hits = self.allocator.match_prefix(hashes)
        for pid in hits:
            self.allocator.share(pid)  # pin before anything can alloc
        if self._host_kv and len(hits) < len(hashes):
            hits = self._restore_host_hits(root, hashes, hits)
        partial = None
        if self._cow_enabled:
            parent = hashes[len(hits) - 1] if hits else root
            remainder = r.prompt[len(hits) * ps:]
            # ≥1 computed token AND the matched rows must stay a strict
            # sub-page (a full page would be a full-block hit).
            cap = min(len(remainder) - 1, ps - 1)
            if cap > 0:
                partial = self.allocator.match_partial(
                    parent, tuple(int(t) for t in remainder), cap)
                if partial is not None:
                    self.allocator.share(partial[0])
        return hits, partial

    def _unpin_hits_locked(self, hits: list[int],
                           partial: tuple[int, int] | None) -> None:
        """Drop the pins ``_prefix_hits`` took when admission cannot use
        them (head-of-line wait, reservation failure) — the pages stay
        cached for the retry."""
        for pid in hits:
            self.allocator.release(pid)
        if partial is not None:
            self.allocator.release(partial[0])

    def _chain_hashes(self, tokens, model: str | None = None
                      ) -> tuple[bytes, list[bytes]]:
        """Adapter-scoped root hash plus the chain hash of every FULL
        token block of ``tokens`` — the trie's path identities. Shared by
        admission matching, page export, and import re-registration, so
        a page migrated between engines lands under byte-identical
        hashes on both sides."""
        ps = self.page_size
        h = hashlib.sha1()
        h.update((model or "").encode())  # adapter-scoped prefix space
        root = h.digest()
        hashes: list[bytes] = []
        for i in range(len(tokens) // ps):
            h.update(bytes(np.asarray(
                tokens[i * ps:(i + 1) * ps], np.int32).tobytes()))
            hashes.append(h.digest())
        return root, hashes

    def _spill_page_to_host(self, page_id: int, chain_hash: bytes) -> None:
        """Tiered-KV eviction hook (runs under the engine lock, inside
        ``PageAllocator._evict_one``): pull the doomed page's K/V to host
        RAM keyed by its chain hash, bounded LRU."""
        try:
            data = self.executor.export_pages([page_id])
        except Exception:
            return  # spill is best-effort; eviction proceeds regardless
        self._host_kv[chain_hash] = data
        self._host_kv.move_to_end(chain_hash)
        while len(self._host_kv) > self.host_kv_cache_pages:
            self._host_kv.popitem(last=False)
        self.metrics["host_kv_spilled_pages"] += 1

    def _restore_host_hits(self, root: bytes, hashes: list[bytes],
                           hits: list[int]) -> list[int]:
        """Extend a trie match with pages restored from the host-RAM
        spill tier: each restored page is scattered back into a fresh
        pool page and re-registered under its chain hash, so the suffix
        prefill skips it exactly like a device-resident hit. The caller
        pinned every prior hit, and each restored page keeps its alloc
        ref (= the pin), so the LRU eviction a restore's alloc may
        trigger can never recycle any page of this match."""
        while len(hits) < len(hashes):
            h = hashes[len(hits)]
            data = self._host_kv.get(h)
            if data is None:
                break
            got = self.allocator.alloc(1)
            if got is None:
                break
            (pid,) = got
            del self._host_kv[h]  # single copy: it lives on-device again
            try:
                self.executor.import_pages([pid], data)
            except Exception:
                self.allocator.release(pid)
                break
            parent = hashes[len(hits) - 1] if hits else root
            self.allocator.register_prefix(pid, h, parent)
            hits.append(pid)  # alloc ref doubles as the hit pin
            self.metrics["host_kv_restored_pages"] += 1
        return hits

    def _chunk_bucket(self, n: int) -> int:
        b = self.page_size
        while b < n and b < self.prefill_chunk_size:
            b *= 2
        return min(b, self.prefill_chunk_size)

    def _maybe_cow(self, r: Request) -> None:
        """Write-triggered copy-on-write: the next suffix chunk starts at
        ``prefill_pos``; when that position's page is still a SHARED
        partial tail block, fork it now — device-copy the one page into
        the fork reserved at admission, swap the slot's table entry, and
        drop our ref on the shared original (which stays immutable for
        its other readers). Never copies the pool, only the page."""
        if r.cow_page is None:
            return
        with self._lock:
            if r.done or not r.block_table:
                return
            idx = r.prefill_pos // self.page_size
            if idx >= r.shared_pages:
                # Pure full-block sharing after all (defensive): the
                # reserve is never written — return it to the pool.
                self.allocator.release(r.cow_page)
                r.cow_page = None
                return
            old, new = r.block_table[idx], r.cow_page
            # Copy before the swap is visible anywhere: the executor op
            # rides the ordered dispatch stream, so every shard forks the
            # rows before the chunk that writes past them.
            self.executor.copy_pages([old], [new])
            r.block_table[idx] = new
            self._block_tables[r.slot, idx] = new
            self.allocator.release(old)
            r.shared_pages = idx
            r.cow_page = None
            self.metrics["cow_forks"] += 1
            r.timeline.add(loop_recorder.EV_COW_FORK, new)

    def _prefill_chunk_one(self, r: Request) -> list[dict]:
        self._maybe_cow(r)
        remaining = len(r.prompt) - r.prefill_pos
        bt = np.full(self.max_pages_per_seq, r.slot, np.int32)  # trash-pad
        bt[:len(r.block_table)] = r.block_table
        # Chunk-pipelined prefill: an executor that can pipeline (pp
        # stages) takes up to `depth` consecutive FULL-size chunks of
        # this prompt in ONE dispatch — the single-chunk schedule leaves
        # (pp-1)/pp of prefill compute idle.
        depth = getattr(self.executor, "pipelined_prefill_depth", 1)
        full = self.prefill_chunk_size
        m = min(depth, remaining // full,
                (self.max_len - r.prefill_pos) // full)
        # power-of-two wavefront lengths: O(log depth) compiled variants
        while m & (m - 1):
            m &= m - 1
        if m >= 2 and not r.lora_slot:
            take = m * full
            tokens_m = np.asarray(
                r.prompt[r.prefill_pos:r.prefill_pos + take],
                np.int32).reshape(m, full)
            final = r.prefill_pos + take >= len(r.prompt)
            handle = (next(self._handle_counter)
                      if final and not r.prefill_only else None)
            self.executor.prefill_many(bt, tokens_m, r.prefill_pos, handle, full)
            self.metrics["prefill_chunks"] += m
            r.prefill_pos += take
            r.prefill_chunks += m
            r.timeline.add(loop_recorder.EV_PREFILL_CHUNK, take)
        else:
            # Bucket, clamped so the chunk's pages never run past the
            # table (both operands are page-aligned).
            chunk = min(self._chunk_bucket(remaining),
                        self.max_len - r.prefill_pos)
            tokens = np.zeros(chunk, np.int32)
            take = min(remaining, chunk)
            tokens[:take] = r.prompt[r.prefill_pos:r.prefill_pos + take]
            final = r.prefill_pos + take >= len(r.prompt)
            handle = (next(self._handle_counter)
                      if final and not r.prefill_only else None)
            self.executor.prefill(bt, tokens, r.prefill_pos, handle, take,
                                  lora_slot=r.lora_slot)
            self.metrics["prefill_chunks"] += 1
            r.prefill_pos += take
            r.prefill_chunks += 1
            r.timeline.add(loop_recorder.EV_PREFILL_CHUNK, take)
        if not final:
            return []  # more chunks to go
        # Prompt complete: queue the last real position's hidden state
        # (stashed device-side under `handle`) for BATCHED first-token
        # sampling — a burst of prefills costs one sampling sync total.
        with self._lock:
            if r.done:  # cancelled mid-prefill
                if handle is not None:
                    self.executor.drop_handle(handle)
                if self._prefilling and self._prefilling[0] is r:
                    self._prefilling.popleft()
                return []
            self._prefilling.popleft()
            if r.prefill_only:
                # Disaggregated prefill: the prompt's KV is in the pool
                # and (at retire) the prefix trie — nothing is sampled
                # here; a decode replica imports the pages and samples.
                r.done, r.finish_reason = True, "prefilled"
                self._retire_locked(r)
        if r.prefill_only:
            return [{"request_id": r.request_id, "token": -1, "done": True,
                     "finish_reason": "prefilled"}]
        self._pending_first.append((r, handle))
        return []

    def _flush_first_samples(self) -> list[dict]:
        """One dispatch + one sync samples the first token for every
        pending just-prefilled request."""
        pending, self._pending_first = self._pending_first, []
        live = [(r, h) for r, h in pending if not r.done]
        for r, h in pending:
            if r.done:  # cancelled mid-prefill: free the stashed hidden
                self.executor.drop_handle(h)
        if not live:
            return []
        m = len(live)
        temps = np.asarray([r.temperature for r, _ in live], np.float32)
        tokens = self.executor.sample_first([h for _, h in live], temps)
        events = []
        now = time.monotonic()
        now_wall = time.time()
        with annotate("engine.emit", first_tokens=m):
            for i, (r, _) in enumerate(live):
                with self._lock:
                    if r.done:  # cancelled while sampling
                        continue
                    self._active[r.slot] = r
                r.pos = len(r.prompt)
                r.first_token_at = now
                r.first_token_wall = now_wall
                r.timeline.add(loop_recorder.EV_FIRST_TOKEN, r.prefill_pos,
                               now=now_wall)
                self._record_prefill_span(r)
                events.append(self._emit(r, int(tokens[i])))
        return events

    def _record_prefill_span(self, r: Request) -> None:
        """Span from request arrival to its first sampled token: the
        engine-side TTFT (queue wait + chunked prefill + first sample)."""
        if not r.trace:
            return
        from ..observability import tracing

        tracing.record_span(tracing.make_span(
            "llm.prefill", "llm", r.arrived_wall, r.first_token_wall or time.time(),
            r.trace.get("trace_id", ""), r.trace.get("span_id", ""),
            attrs={"request_id": r.request_id,
                   "prompt_tokens": len(r.prompt),
                   "cached_prefix_tokens": r.cached_prefix_tokens,
                   "queue_wait_ms": round(
                       ((r.admitted_at or r.arrived_at) - r.arrived_at) * 1e3, 3),
                   "prefill_chunks": r.prefill_chunks}))

    # -------------------------------------------------------- flight recorder
    def dump_timeline(self, r: Request, reason: str) -> bool:
        """Dump one request's flight-recorder timeline as a single
        ``llm.request_timeline`` span (attrs carry the full event list:
        admission → prefix hits → prefill chunks → first token →
        per-token deltas → terminal event). Fires AT MOST ONCE per
        request — the first SLO breach (deadline expiry, shed, TTFT-SLO
        breach from the serving layer) wins; later triggers are no-ops.
        Returns True when a dump was recorded."""
        tl = r.timeline
        if tl is None or tl.dumped:
            return False
        tl.dumped = True
        from ..observability import tracing

        payload = tl.to_payload()
        trace = r.trace or {}
        now = time.time()
        tracing.record_span(tracing.make_span(
            "llm.request_timeline", "llm",
            payload["start"] or r.arrived_wall, now,
            trace.get("trace_id") or tracing.new_trace_id(),
            trace.get("span_id", ""),
            attrs={"request_id": r.request_id, "reason": reason,
                   "model": r.model or "", **payload}))
        self.metrics["timeline_dumps"] += 1
        self._breach_samples.append({
            "request_id": r.request_id, "reason": reason, "ts": now,
            "model": r.model or "", "n_events": payload["n_events"],
            "overflowed": payload["overflowed"],
            "events": payload["events"][-16:]})
        return True

    def breach_samples(self) -> list[dict]:
        """Most recent breach dumps (bounded), for serve.status() rows."""
        return list(self._breach_samples)

    def _record_decode_span(self, r: Request) -> None:
        if not r.trace:
            return
        from ..observability import tracing

        now = time.time()
        tracing.record_span(tracing.make_span(
            "llm.decode", "llm", r.first_token_wall or now, now,
            r.trace.get("trace_id", ""), r.trace.get("span_id", ""),
            attrs={"request_id": r.request_id,
                   "generated_tokens": len(r.generated),
                   "finish_reason": r.finish_reason}))
        if r.spec_drafted or r.spec_rollbacks:
            # One llm.speculate span per request that speculation
            # touched: how much the drafter proposed, how much the
            # target accepted, and how many rounds rolled back.
            tracing.record_span(tracing.make_span(
                "llm.speculate", "llm", r.first_token_wall or now, now,
                r.trace.get("trace_id", ""), r.trace.get("span_id", ""),
                attrs={"request_id": r.request_id,
                       "drafted_tokens": r.spec_drafted,
                       "accepted_tokens": r.spec_accepted,
                       "rollbacks": r.spec_rollbacks}))

    def _decode_batch_args(self, active: dict):
        """Fill the host mirrors for one decode burst over ``active`` and
        return the per-slot (temps, eos_ids, remaining) arrays."""
        temps = np.ones(self.max_slots, np.float32)
        eos_ids = np.full(self.max_slots, -1, np.int32)
        remaining = np.zeros(self.max_slots, np.int32)
        for slot, r in active.items():
            self._tokens[slot] = r.generated[-1]
            self._pos[slot] = r.pos
            temps[slot] = r.temperature
            eos_ids[slot] = -1 if r.eos_id is None else r.eos_id
            remaining[slot] = min(
                r.max_new_tokens - len(r.generated),
                len(r.block_table) * self.page_size - r.pos,
            )
        return temps, eos_ids, remaining

    def _emit_decode_events(self, active: dict, tokens, K: int) -> list[dict]:
        events = []
        with annotate("engine.emit", K=K, slots=len(active)) as span:
            for k in range(K):
                for slot, r in active.items():
                    if r.done:
                        continue
                    r.pos += 1
                    if r.first_token_at is None:
                        r.first_token_at = time.monotonic()
                        r.first_token_wall = time.time()
                    events.append(self._emit(r, int(tokens[k, slot])))
            span.set_metadata(tokens=len(events))
        return events

    def _decode_all(self) -> list[dict]:
        if self.speculation_enabled:
            events = self._speculative_decode()
            if events is not None:
                return events
            # no slot produced a draft this round: the plain fused burst
            # below is strictly better than an all-rejected verify
        with self._lock:
            active = dict(self._active)
        if not active:
            return []
        temps, eos_ids, remaining = self._decode_batch_args(active)
        # K fused decode+sample steps in ONE dispatch, ONE host sync
        # (on-device lax.scan). Finished slots redirect writes to trash;
        # their surplus tokens are discarded below.
        K = self.decode_steps_per_dispatch
        tokens = self.executor.decode(
            self._block_tables, self._tokens, self._pos, temps, eos_ids,
            remaining, K, lora_idx=self._lora_idx,
        )  # [K, slots]
        self.metrics["decode_steps"] += K
        # One dispatch == one staging-buffer commit on the paged path:
        # the pool is written decode_dispatches times, not decode_steps.
        self.metrics["decode_dispatches"] += 1
        self._note_loop_ticks()
        return self._emit_decode_events(active, tokens, K)

    def _speculative_decode(self) -> list[dict] | None:
        """One speculation round: draft K tokens per active slot on the
        host (n-gram lookup over each request's own token history — no
        model cost), then ONE verify dispatch scores all K+1 positions
        per slot and emits the accepted run plus one corrected/bonus
        token. Per-slot accept lengths vary freely inside the batch; a
        slot whose draft is fully rejected still advances one token, so
        a verify never emits less per slot than a single decode step.
        Returns None when no slot drafted anything — the caller falls
        back to the plain fused decode burst for this tick."""
        with self._lock:
            active = dict(self._active)
        if not active:
            return []
        K = self.speculation.num_draft_tokens
        temps, eos_ids, remaining = self._decode_batch_args(active)
        tok_mat = np.full((self.max_slots, K + 1), -1, np.int32)
        tok_mat[:, 0] = self._tokens
        drafted: dict[int, int] = {}
        for slot, r in active.items():
            d = self._drafter.draft(list(r.prompt) + list(r.generated), K)
            if d:
                d = d[:K]
                tok_mat[slot, 1:1 + len(d)] = d
                drafted[slot] = len(d)
        if not drafted:
            return None
        toks, live = self.executor.verify(
            self._block_tables, tok_mat, self._pos, temps, eos_ids,
            remaining)  # [K+1, slots] each
        self.metrics["spec_dispatches"] += 1
        self.metrics["decode_dispatches"] += 1
        self._note_loop_ticks()
        return self._emit_speculative_events(active, toks, live, drafted)

    def _emit_speculative_events(self, active: dict, toks, live,
                                 drafted: dict) -> list[dict]:
        """Emit each slot's verified run in step order (mirrors
        ``_emit_decode_events``): rows stop at the slot's first non-live
        step, and host-side terminators (stop_ids via ``_emit``) discard
        any surplus device rows exactly like the plain decode loop."""
        events: list[dict] = []
        S = toks.shape[0]
        for slot, r in active.items():
            emitted = 0
            for j in range(S):
                if r.done or not live[j, slot]:
                    break
                r.pos += 1
                if r.first_token_at is None:
                    r.first_token_at = time.monotonic()
                    r.first_token_wall = time.time()
                events.append(self._emit(r, int(toks[j, slot])))
                emitted += 1
            dr = drafted.get(slot, 0)
            accepted = min(max(0, emitted - 1), dr)
            if dr:
                r.timeline.add(loop_recorder.EV_SPEC_ROUND, accepted)
            r.spec_drafted += dr
            r.spec_accepted += accepted
            self.metrics["spec_drafted_tokens"] += dr
            self.metrics["spec_accepted_tokens"] += accepted
            self.metrics["spec_emitted_tokens"] += emitted
            self.metrics["spec_slot_rounds"] += 1
            if dr and accepted < dr:
                r.spec_rollbacks += 1
                self.metrics["spec_rollbacks"] += 1
        return events

    def _note_loop_ticks(self) -> None:
        """Mirror the executor's compiled-loop tick count (zero-RPC
        steady-state dispatch, dag/loop.py) into the engine metrics."""
        ticks = getattr(self.executor, "loop_ticks", None)
        if ticks is not None:
            self.metrics["dag_loop_ticks"] = ticks

    def _select_prefill_plans(self) -> list[dict]:
        """Chunks riding the next mixed dispatch: walk the prefill queue
        in admission order, taking one chunk per prompt until the token
        budget or ``max_prefill_seqs_per_step`` is spent. Chunk sizes are
        the SAME buckets as the standalone prefill path (a budget smaller
        than the natural bucket drops to the largest fitting bucket), so
        mixed dispatch adds no new prefill shapes — only combinations."""
        plans: list[dict] = []
        budget = self.prefill_token_budget
        with self._lock:
            queue = [r for r in self._prefilling if not r.done]
        for r in queue:
            if len(plans) >= self.max_prefill_seqs_per_step:
                break
            if budget < self.page_size:
                break
            if r.lora_slot:
                continue  # adapter prefill stays on the legacy path
            self._maybe_cow(r)  # fork a shared tail before writing it
            remaining = len(r.prompt) - r.prefill_pos
            chunk = self._chunk_bucket(remaining)
            if chunk > budget:
                b = self.page_size
                while b * 2 <= budget:
                    b *= 2
                chunk = b
            # Clamp so the chunk's pages never run past the table (same
            # clamp as the standalone path — both operands page-aligned).
            chunk = min(chunk, self.max_len - r.prefill_pos)
            take = min(remaining, chunk)
            if take <= 0:
                continue
            bt = np.full(self.max_pages_per_seq, r.slot, np.int32)
            bt[:len(r.block_table)] = r.block_table
            tokens = np.zeros(chunk, np.int32)
            tokens[:take] = r.prompt[r.prefill_pos:r.prefill_pos + take]
            final = r.prefill_pos + take >= len(r.prompt)
            plans.append({
                "request": r, "block_table": bt, "tokens": tokens,
                "start_pos": r.prefill_pos,
                "handle": (next(self._handle_counter)
                           if final and not r.prefill_only else None),
                "take": take, "final": final,
            })
            budget -= chunk
        return plans

    def _mixed_step(self) -> list[dict] | None:
        """ONE fused dispatch: the full decode burst plus the selected
        prefill chunks. Returns the decode emission events, or None when
        no prefill chunk was fusable (caller falls back to the legacy
        schedule for this step)."""
        plans = self._select_prefill_plans()
        if not plans:
            return None
        with self._lock:
            active = dict(self._active)
        if not active:
            return None  # decoders finished since the caller looked
        temps, eos_ids, remaining = self._decode_batch_args(active)
        K = self.decode_steps_per_dispatch
        wire = [{k: p[k] for k in ("block_table", "tokens", "start_pos",
                                   "handle", "take")} for p in plans]
        tokens = self.executor.mixed(
            wire, self._block_tables, self._tokens, self._pos, temps,
            eos_ids, remaining, K, lora_idx=self._lora_idx,
        )  # [K, slots]
        self.metrics["decode_steps"] += K
        self.metrics["decode_dispatches"] += 1
        # Prefill bookkeeping AFTER the dispatch (mirrors
        # _prefill_chunk_one): advance positions, move finished prompts to
        # the batched first-token queue, drop handles of cancelled ones.
        extra_events: list[dict] = []
        for p in plans:
            r = p["request"]
            self.metrics["prefill_chunks"] += 1
            r.prefill_pos = p["start_pos"] + p["take"]
            r.prefill_chunks += 1
            r.timeline.add(loop_recorder.EV_PREFILL_CHUNK, p["take"])
            if not p["final"]:
                continue
            with self._lock:
                try:
                    self._prefilling.remove(r)
                except ValueError:
                    pass  # cancel() already rebuilt the queue without it
                if r.done:  # cancelled mid-dispatch
                    if p["handle"] is not None:
                        self.executor.drop_handle(p["handle"])
                    continue
                if r.prefill_only:
                    r.done, r.finish_reason = True, "prefilled"
                    self._retire_locked(r)
                    extra_events.append(
                        {"request_id": r.request_id, "token": -1,
                         "done": True, "finish_reason": "prefilled"})
                    continue
            self._pending_first.append((r, p["handle"]))
        return self._emit_decode_events(active, tokens, K) + extra_events

    def _emit(self, r: Request, token: int) -> dict:
        r.generated.append(token)
        # Per-token ITL record: deltas between consecutive EV_TOKEN
        # timestamps are the inter-token latencies in the dump.
        r.timeline.add(loop_recorder.EV_TOKEN, len(r.generated))
        if (r.eos_id is not None and token == r.eos_id) or token in r.stop_ids:
            r.done, r.finish_reason = True, "stop"
        elif len(r.generated) >= r.max_new_tokens:
            r.done, r.finish_reason = True, "length"
        elif r.pos >= min(self.max_len, len(r.block_table) * self.page_size) - 1:
            r.done, r.finish_reason = True, "max_len"
        if r.done:
            with self._lock:
                self._retire_locked(r)  # idempotent if cancel() beat us
            self._record_decode_span(r)
        return {
            "request_id": r.request_id,
            "token": token,
            "done": r.done,
            "finish_reason": r.finish_reason,
        }

    def pool_stats(self) -> dict:
        """Page-pool accounting snapshot: free pages, cached (trie)
        pages, and pages still PINNED (refcount > 0, i.e. held by a live
        slot, an export pin, or a prefix-hit pin). After every request
        settles — including mid-decode deadline aborts — ``pinned`` must
        return to 0 and ``active_slots`` to 0: the chaos overload plan's
        refcounts-at-baseline invariant."""
        with self._lock:
            cached = len(self.allocator.page_hash) + \
                len(self.allocator._partial_pages)
            pinned = sum(1 for _p, c in self.allocator.refcount.items()
                         if c > 0)
            return {
                "num_pages": self.num_pages,
                "free": len(self.allocator.free),
                "cached": cached,
                "pinned": pinned,
                "active_slots": len(self._active),
                "prefilling": len(self._prefilling),
                "waiting": len(self._waiting),
            }

    # ------------------------------------------------------- weight residency
    @property
    def supports_weight_residency(self) -> bool:
        """Host-tier weight demotion (``llm/weights.py``): the executor
        must own a ``params`` pytree it lets us swap (single-device
        ``LocalEngineExecutor``; sharded/pp executors place their own)."""
        return bool(getattr(self.executor, "supports_weight_residency",
                            False)) and hasattr(self.executor, "params")

    def weights_resident(self) -> bool:
        """True while the weight pytree is on device (normal serving)."""
        return getattr(self.executor, "params", None) is not None

    def demote_weights_to_host(self) -> dict:
        """Standby demotion: copy the weight pytree to host RAM and drop
        the device reference, freeing HBM while the compile cache (and
        the whole engine — pool, trie, adapters) stays warm. Refused
        while any request is in flight — a demote mid-decode would pull
        the weights out from under a dispatch."""
        from . import weights as wlib

        with self._residency_lock:
            if not self.supports_weight_residency:
                return {"ok": False, "reason": "unsupported"}
            if not self.weights_resident():
                return {"ok": True, "already": True, "bytes": 0,
                        "seconds": 0.0}
            if self.has_work:
                return {"ok": False, "reason": "busy"}
            t0 = time.monotonic()
            host = wlib.tree_to_host(self.executor.params)
            self._host_params = host
            self.executor.params = None  # device buffers free on GC
            self.metrics["weights_demoted"] += 1
            # Scale-to-zero reclaims the adapter stack too: no request
            # is in flight, so every resident adapter is unpinned.
            adapters = (self.lora_manager.unload_idle()
                        if self.lora_manager is not None else 0)
            return {"ok": True, "bytes": wlib.tree_bytes(host),
                    "adapters_unloaded": adapters,
                    "seconds": round(time.monotonic() - t0, 6)}

    def promote_weights_from_host(self) -> dict:
        """Standby promotion: ``device_put`` the host copy back. The
        host copy is KEPT (weights are immutable under inference) so the
        next demotion is a pointer drop, not another device pull."""
        from . import weights as wlib

        with self._residency_lock:
            return self._promote_locked(wlib)

    def _promote_locked(self, wlib) -> dict:
        if self.weights_resident():
            return {"ok": True, "already": True, "seconds": 0.0}
        if self._host_params is None:
            return {"ok": False, "reason": "no_host_copy"}
        t0 = time.monotonic()
        params = wlib.host_to_device(self._host_params)
        try:
            import jax

            jax.block_until_ready(params)  # honest promote timing
        except Exception:
            pass
        self.executor.params = params
        dt = time.monotonic() - t0
        self.metrics["weights_promoted"] += 1
        self.metrics["weight_promote_ms"] = round(dt * 1000.0, 3)
        return {"ok": True, "seconds": round(dt, 6)}

    def install_weights(self, host_tree) -> dict:
        """Adopt a weight pytree delivered over the broadcast wire
        (``receive_weight_stream``): it becomes the host copy, then
        promotes if the engine is currently demoted. A resident engine
        only refreshes its host copy — live dispatches keep their
        device tree until the next demote/promote cycle."""
        from . import weights as wlib

        with self._residency_lock:
            if not self.supports_weight_residency:
                return {"ok": False, "reason": "unsupported"}
            self._host_params = wlib.tree_to_host(host_tree)
            if self.weights_resident():
                return {"ok": True, "resident": True, "seconds": 0.0}
            return self._promote_locked(wlib)

    def _ensure_weights_resident(self) -> None:
        """First-request promotion: admission and the step loop call
        this so a request that lands on a demoted (scale-to-zero'd)
        engine pays one device_put, never a crash."""
        if self.weights_resident() or self._host_params is None:
            return
        from . import weights as wlib

        with self._residency_lock:
            self._promote_locked(wlib)

    # ----------------------------------------------------------- KV migration
    @property
    def supports_kv_migration(self) -> bool:
        """Page export/import between engines: needs the prefix trie (the
        registration target) and an executor with the host gather/scatter
        path (off pp; see ``LocalEngineExecutor.supports_kv_migration``)."""
        return bool(self.enable_prefix_cache and
                    getattr(self.executor, "supports_kv_migration", False))

    def pin_prefix_for_export(self, prompt,
                              model: str | None = None) -> dict | None:
        """Match ``prompt``'s longest cached chain — full trie blocks
        plus the best partial tail — and PIN its pages for export: one
        extra refcount per page so pool pressure cannot recycle them
        mid-transfer. Returns the export plan ``{"page_ids", "tokens",
        "full_pages", "partial_len", "model"}`` (release with
        ``release_export_pages``), or None when nothing is cached (or
        migration is unsupported)."""
        if not self.supports_kv_migration or len(prompt) < 2:
            return None
        ps = self.page_size
        with self._lock:
            root, chain = self._chain_hashes(prompt, model)
            hashes = chain[:(len(prompt) - 1) // ps]
            hits = self.allocator.match_prefix(hashes)
            partial = None
            if self._cow_enabled:
                parent = hashes[len(hits) - 1] if hits else root
                remainder = prompt[len(hits) * ps:]
                cap = min(len(remainder) - 1, ps - 1)
                if cap > 0:
                    partial = self.allocator.match_partial(
                        parent, tuple(int(t) for t in remainder), cap)
            if not hits and partial is None:
                return None
            ids = list(hits) + ([partial[0]] if partial is not None else [])
            for pid in ids:
                self.allocator.share(pid)  # pinned until released
        plen = partial[1] if partial is not None else 0
        covered = len(hits) * ps + plen
        return {"page_ids": ids,
                "tokens": [int(t) for t in prompt[:covered]],
                "full_pages": len(hits), "partial_len": plen,
                "model": model or ""}

    def release_export_pages(self, page_ids: list[int]) -> None:
        """Drop the per-page export pins ``pin_prefix_for_export`` took;
        the pages become ordinary evictable cache entries again."""
        with self._lock:
            for pid in page_ids:
                self.allocator.release(pid)

    def export_prefix_kv(self, prompt, model: str | None = None) -> dict | None:
        """Export the cached KV covering ``prompt``'s longest prefix —
        full trie blocks plus the best partial tail — as a host payload
        an ``import_prefix_kv`` on another engine can adopt, in ONE
        blocking pull (the chunked alternative is a
        ``KVMigrationSource.for_cached_prefix`` stream). The pages are
        pinned across the device→host pull so pool pressure cannot
        recycle them mid-export. Returns None when nothing is cached (or
        migration is unsupported)."""
        plan = self.pin_prefix_for_export(prompt, model)
        if plan is None:
            return None
        ids = plan["page_ids"]
        try:
            data = self.executor.export_pages(ids)
        finally:
            self.release_export_pages(ids)
        self.metrics["kv_pages_exported"] += len(ids)
        self.metrics["kv_migrations_out"] += 1
        return {"page_size": self.page_size, "model": plan["model"],
                "tokens": plan["tokens"],
                "full_pages": plan["full_pages"],
                "partial_len": plan["partial_len"],
                "k": data["k"], "v": data["v"]}

    def import_prefix_kv(self, payload: dict | None) -> int:
        """Adopt a migrated KV payload: reserve pages, scatter the data
        in, and register the chain under the same hashes the source used
        — a following ``add_request`` for the same prompt then maps the
        pages as ordinary prefix hits and prefills only the cold suffix.
        Returns the number of prompt tokens now servable from cache; 0
        means clean fallback (pressure, geometry mismatch, unsupported)
        and the caller simply cold-prefills."""
        if not payload or not self.supports_kv_migration \
                or payload.get("page_size") != self.page_size:
            return 0
        full_pages = int(payload.get("full_pages") or 0)
        plen = int(payload.get("partial_len") or 0)
        if not self._cow_enabled:
            plen = 0  # partial tails need row-granular suffix starts
        want = full_pages + (1 if plen else 0)
        if want <= 0:
            return 0
        with self._lock:
            pages = (self.allocator.alloc(want)
                     if self.allocator.available() >= want else None)
        if pages is None:
            # Import under pressure: never evict live sequences' headroom
            # for a cache import — the request cold-prefills instead.
            self.metrics["kv_import_failures"] += 1
            return 0
        k = np.asarray(payload["k"])[:, :want]
        v = np.asarray(payload["v"])[:, :want]
        try:
            self.executor.import_pages(pages, {"k": k, "v": v})
        except Exception:
            with self._lock:
                for pid in pages:
                    self.allocator.release(pid)
            self.metrics["kv_import_failures"] += 1
            return 0
        return self.register_imported_chain(
            pages, payload["tokens"], full_pages, plen,
            model=payload.get("model") or None)

    def register_imported_chain(self, page_ids: list[int], tokens,
                                full_pages: int, partial_len: int,
                                model: str | None = None) -> int:
        """Register freshly imported pages in the prefix trie under the
        chain hashes recomputed from their token ids (self-validating:
        both engines derive identities from the data, not from trust in
        the wire). Callers hold one alloc ref per page; registration
        releases it, leaving the pages cached and immediately matchable.
        A chain link that is ALREADY resident keeps the local page and
        the duplicate import frees straight back to the pool. Returns
        the prompt tokens covered by the (existing + new) chain."""
        ps = self.page_size
        with self._lock:
            root, chain = self._chain_hashes(tokens, model)
            parent = root
            covered = 0
            kept = 0
            for i in range(min(full_pages, len(chain), len(page_ids))):
                h, pid = chain[i], page_ids[i]
                if self.allocator.lookup_prefix(h) is None:
                    self.allocator.register_prefix(pid, h, parent)
                    kept += 1
                self.allocator.release(pid)  # cached if registered, else freed
                parent = h
                covered = (i + 1) * ps
            if partial_len and len(page_ids) > full_pages:
                pid = page_ids[full_pages]
                tail = tuple(int(t) for t in
                             tokens[full_pages * ps:full_pages * ps + partial_len])
                if tail and self._cow_enabled:
                    self.allocator.register_partial(parent, tail, pid)
                self.allocator.release(pid)
                if tail and self.allocator._partials.get(parent, {}) \
                        .get(tail) is not None:
                    # Registered now, or an equivalent entry already
                    # resident — either way those rows are servable.
                    covered += len(tail)
                    if self.allocator._partials[parent][tail] == pid:
                        kept += 1
            self.metrics["kv_pages_imported"] += kept
            if kept or covered:
                self.metrics["kv_migrations_in"] += 1
        return covered

    def release_export_pins(self, r: Request) -> None:
        """The exporter is done with ``r``: drop the per-page refs
        ``pin_for_export`` took at retire (the pages become ordinary
        evictable cache entries), and take none if ``r`` has not retired
        yet — an exporter whose stream ends first (reader gone, channel
        dead, abort) would otherwise leave them pinned for good."""
        with self._lock:
            r.pin_for_export = False
            pins, r.export_pinned = r.export_pinned, []
            for pid in pins:
                self.allocator.release(pid)

    # ------------------------------------------------------------ conveniences
    def generate(self, prompt: list[int], max_new_tokens: int = 32,
                 temperature: float = 0.0, eos_id: int | None = None) -> list[int]:
        """Blocking single-prompt helper (tests / offline use)."""
        rid = f"gen-{next(self._counter)}"
        r = Request(rid, list(prompt), max_new_tokens, temperature, eos_id)
        self.add_request(r)
        while not r.done:
            self.step()
        return r.generated
