"""DataIterator: batch iteration and train-worker stream splitting.

Reference: ``python/ray/data/iterator.py:94`` (iter_batches) and
``dataset.py:1598`` streaming_split via a SplitCoordinator actor feeding
one iterator per train worker.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from ..core import api as ray
from ..observability.tracing import annotate
from .block import BlockAccessor, concat_blocks

_END = object()


def _batch_size_of(batch) -> tuple[int, int]:
    """(rows, bytes) of a batch, for a span's attributes; a numpy batch is
    a dict of arrays, other formats count rows only."""
    if isinstance(batch, dict):
        arrays = [np.asarray(v) for v in batch.values()]
        return (len(arrays[0]) if arrays else 0), sum(a.nbytes for a in arrays)
    return getattr(batch, "num_rows", len(batch)), 0


def batches_from_blocks(
    block_iter,
    *,
    batch_size: int | None,
    batch_format: str = "numpy",
    drop_last: bool = False,
) -> Iterator:
    """Re-slice a stream of blocks into fixed-size batches."""
    if batch_size is None:
        for block in block_iter:
            if block.num_rows:
                yield BlockAccessor.for_block(block).to_batch(batch_format)
        return
    carry = []
    carry_rows = 0
    for block in block_iter:
        carry.append(block)
        carry_rows += block.num_rows
        while carry_rows >= batch_size:
            merged = concat_blocks(carry)
            batch = merged.slice(0, batch_size)
            rest = merged.slice(batch_size, merged.num_rows - batch_size)
            carry = [rest] if rest.num_rows else []
            carry_rows = rest.num_rows
            yield BlockAccessor.for_block(batch).to_batch(batch_format)
    if carry_rows and not drop_last:
        merged = concat_blocks(carry)
        yield BlockAccessor.for_block(merged).to_batch(batch_format)


class SplitCoordinator:
    """Actor that owns a dataset's output stream and deals blocks to n
    consumers (reference: StreamSplitDataIterator's coordinator).

    Blocks are dealt round-robin to per-split queues so every consumer gets
    a fair share regardless of polling order. Scheduled with num_cpus=0
    (it only shuffles refs) so it never starves the cluster."""

    def __init__(self, dataset, n: int, equal: bool = False):
        self._iter = dataset.iter_internal_ref_bundles()
        self._n = n
        self._equal = equal
        self._queues: list[list] = [[] for _ in range(n)]
        self._delivered = [0] * n
        self._next_split = 0
        self._exhausted = False
        self._finished: set[int] = set()

    def _pull_until(self, split_idx: int) -> None:
        while not self._queues[split_idx] and not self._exhausted:
            try:
                ref = next(self._iter)
            except StopIteration:
                self._exhausted = True
                if self._equal:
                    # equal=True: trim trailing imbalance so every split
                    # sees the same number of blocks (reference: equal
                    # splits drop the remainder).
                    floor = min(self._delivered[i] + len(self._queues[i]) for i in range(self._n))
                    for i in range(self._n):
                        excess = self._delivered[i] + len(self._queues[i]) - floor
                        if excess > 0:
                            del self._queues[i][-excess:]
                return
            self._queues[self._next_split].append(ref)
            self._next_split = (self._next_split + 1) % self._n

    def next_block_ref(self, split_idx: int):
        """Returns the next block ref for this split, or None when its
        share of the stream is exhausted."""
        self._pull_until(split_idx)
        if self._queues[split_idx]:
            self._delivered[split_idx] += 1
            return self._queues[split_idx].pop(0)
        return None

    def mark_finished(self, split_idx: int) -> bool:
        """Consumer i is done; returns True when ALL consumers are done
        (the last one kills this actor to release its slot)."""
        self._finished.add(split_idx)
        return len(self._finished) >= self._n


class DataIterator:
    """Per-worker view of a split stream."""

    def __init__(self, coordinator, split_idx: int):
        self._coord = coordinator
        self._idx = split_idx
        self._exhausted = False

    def _blocks(self):
        if self._exhausted:
            return  # second epoch over a drained one-shot stream is empty
        from ..core.status import ActorDiedError

        while True:
            try:
                ref = ray.get(self._coord.next_block_ref.remote(self._idx), timeout=120)
            except ActorDiedError:
                # coordinator reclaimed by another consumer's final kill
                self._exhausted = True
                return
            if ref is None:
                self._exhausted = True
                # Last finished consumer reclaims the coordinator actor so a
                # leaked slot can't starve later scheduling (advisor round 1).
                try:
                    all_done = ray.get(self._coord.mark_finished.remote(self._idx), timeout=30)
                    if all_done:
                        ray.kill(self._coord)
                except Exception:
                    pass
                return
            yield ray.get(ref, timeout=120)

    def _sized_batches(self, *, batch_size, batch_format, drop_last):
        """(batch, rows, bytes): sized once, for this span and the next."""
        batches = batches_from_blocks(
            self._blocks(), batch_size=batch_size,
            batch_format=batch_format, drop_last=drop_last,
        )
        while True:
            # what the consumer waits for: its blocks (from the
            # coordinator and the object store) and their conversion
            with annotate("data.next_batch") as span:
                batch = next(batches, _END)
                if batch is not _END:
                    rows, nbytes = _batch_size_of(batch)
                    span.set_metadata(rows=rows, bytes=nbytes)
            if batch is _END:
                return
            yield batch, rows, nbytes

    def iter_batches(self, *, batch_size: int | None = 256,
                     batch_format: str = "numpy", drop_last: bool = False):
        for batch, _, _ in self._sized_batches(
                batch_size=batch_size, batch_format=batch_format,
                drop_last=drop_last):
            yield batch

    def iter_rows(self):
        for block in self._blocks():
            yield from BlockAccessor.for_block(block).iter_rows()

    def to_device_batches(self, *, batch_size: int, sharding=None,
                          batch_format: str = "numpy", drop_last: bool = True):
        """TPU idiom: host batch → ``jax.device_put`` (async HBM prefetch
        with one batch of lookahead double-buffering)."""
        import jax

        prev = None
        for batch, rows, nbytes in self._sized_batches(
                batch_size=batch_size, batch_format=batch_format,
                drop_last=drop_last):
            arrs = {k: np.asarray(v) for k, v in batch.items()}
            with annotate("data.device_put", rows=rows, bytes=nbytes):
                cur = jax.device_put(arrs, sharding) if sharding else jax.device_put(arrs)
            if prev is not None:
                yield prev
            prev = cur
        if prev is not None:
            yield prev
