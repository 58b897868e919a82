"""The one general traffic generator: a mix's data file plus a seed give
the inputs of a run. A mix is parameters only (``traffic/<mix>.json``); a
cell that needs a new KIND of traffic brings it with the ``benchmark`` PR
that adds the cell.

``train_steps``: ``rows_steps`` batches of ``seq``-token rows, every id
drawn from the seed inside the model's vocabulary. Every seed gives the
same amount of work (the shapes are the mix's), so runs differ only in
the ids.
"""

from __future__ import annotations

import numpy as np


def train_rows(mix: dict, vocab_size: int, batch: int, seed: int, *,
               seq: int | None = None) -> np.ndarray:
    """The seeded token rows of one run of a ``train_steps`` mix:
    ``[batch x rows_steps, seq]`` int32 (``seq`` overrides the mix's only
    in a CPU rehearsal). An id past the vocabulary would be an
    out-of-range gather, so none is drawn."""
    if mix["kind"] != "train_steps":
        raise ValueError(f"traffic kind {mix['kind']!r} has no generator here")
    rng = np.random.default_rng(seed)
    rows = batch * int(mix["rows_steps"])
    return rng.integers(0, vocab_size, size=(rows, seq or int(mix["seq"])),
                        dtype=np.int32)
