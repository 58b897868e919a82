"""What a kind of layer part owns.

A decoder block is ``x += mixer(norm(x)); x += mlp(norm(x))``. The stack
(``models/llama.py``) knows that much and no more: which leaves a mixer or
an MLP has, how they shard, how they start, what they cost, what of their
forward pass a remat policy may save and what they count beside the loss
belong to the kind, so a new kind is a new ``LayerKind`` and no new branch
in the stack.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class LayerKind:
    # config -> {leaf: logical axes of ONE layer's leaf}
    axes: Callable[[Any], dict]
    # (config, keys, lead, normal) -> {leaf: array}, every leaf with ``lead``
    # prepended to its shape (the layers stacked for the scan); ``keys`` are
    # the kind's own PRNG keys and ``normal(key, shape, fan_in)`` the
    # stack's truncated-normal draw in the model's dtype
    init: Callable[[Any, Any, tuple, Callable], dict]
    # mixer: (h, layer, config=, positions=, mesh=) -> y
    # mlp:   (h, layer, config=, mesh=, ep_axis=) -> (y, aux)
    apply: Callable
    # config -> matmul parameters one token passes through in one layer
    matmul_params: Callable[[Any], float]
    # (config, seq) -> forward FLOPs a token of one layer that are no
    # parameter product (attention's scores, a scan's state); x3 trained
    mixing_flops: Callable[[Any, int], float] = lambda c, seq: 0.0
    # checkpoint names the ``attn`` remat policy saves for this kind
    save_names: tuple[str, ...] = ()
