"""Which path each attention call took, recorded when it was TRACED.

The Pallas entry points pick native lowering or the interpreter from the
backend, and flash attention swaps in ``mha_reference`` for ragged shapes.
Those decisions are taken in Python while a jitted program is traced, so
the compiled program cannot be asked afterwards; this record can. A
worker reports it (train metrics, ``LLMDeployment.engine_metrics``) and
``chip_smoke.py`` fails when a chip run shows anything but ``pallas``.
"""

from __future__ import annotations

import collections
import logging
import threading

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_counts: collections.Counter = collections.Counter()


def note_kernel_trace(kernel: str, path: str) -> None:
    """Count one trace of ``kernel`` down ``path`` (``"pallas"``,
    ``"interpret"`` or ``"mha_reference"``); log the first of each."""
    key = f"{kernel}:{path}"
    with _lock:
        _counts[key] += 1
        first = _counts[key] == 1
    if first:
        logger.info("%s traced as %s", kernel, path)


def kernel_traces() -> dict[str, int]:
    """``{"<kernel>:<path>": times traced}`` for this process."""
    with _lock:
        return dict(_counts)
