"""Model zoo. One pre-norm decoder built TPU-first and described by layer
kinds (``llama.py``, ``kinds.py``): a scan over periods of the stack, bf16
params with f32 statistics, logical-axis shardings from ``ray_tpu.parallel``.
Token mixers: softmax attention through the Pallas flash kernels or ring
attention (with its variants: q/k norms, partial rope, an output gate), and
Gated DeltaNet (``gdn.py``: a chunked delta-rule scan as Pallas kernels),
latent attention (``mla.py``: low-rank q and kv; three choices of keys: a
learned indexer's top-k, a causal window, every causal key; a head-wise gate;
YaRN with its factor on the softmax scale), grouped-query attention by
spec (``gqa.py``: a head count, a rope, plain or YaRN with its factor on cos
and sin, or no rope at all, and a window of a kind's own, a head-wise gate),
lightning attention (``lightning.py``: linear attention whose state decays by
a constant of the head and the layer, two Pallas kernels over chunks, q/k
norms, rope, an output norm and an element-wise gate) and block-selected
attention (``block_sparse.py``: grouped queries over the 64 blocks of 64 keys
that the layer chooses from its own q and k with no parameters, one choice a
kv group, no rope, an element-wise gate) and Mamba-2 state-space layers
(``mamba2.py``: heads of 64 features over a 128-wide float32 state whose decay
is a function of the token and of a parameter, one B and one C for all heads,
a causal conv with a bias, the gate inside a norm over all features; two Pallas
kernels over chunks, ``ops/ssd.py``) and double-gated short convolutions
(``short_conv.py``: one product to two gates and a conv's input, a causal
depthwise conv of 3 taps between the gates, no activation, one product out;
the pass between the products a Pallas kernel pair on the chip,
``ops/sconv_elementwise.py``, and plain XLA elsewhere). A spec of grouped-query attention may
state its softmax scale as a constant, and ``tie_embeddings`` makes the head
contract the embedding's own rows (no ``lm_head`` leaf).
MLPs: dense SwiGLU, or with
``moe_experts > 0`` a routed expert layer (``moe.py``: dropless, the
(token, expert) rows sorted by expert over a Pallas grouped matmul, a softmax
or a sigmoid router with its selection bias, a routed scale, a shared expert,
a chip's share of the experts, SwiGLU or ReGLU experts); leading layers may
have an MLP kind of their own. A block hands its MLP kind the block's input
before the mixer runs, for a router that reads the residual stream there and
not the MLP's own normed input (``kinds.py``: ``early``).
Llama-3, InternLM2, Mistral, OLMoE-1B-7B, Qwen3-Next, dots3-note-prev,
Laguna-S-2.1, Kimi-K2, SmallThinker, MiniCPM-SALA, Granite-4.0-H (these two
under fixed multipliers on the embedding, the residual branches and the head's
input) and LFM2 are configurations."""

from .llama import (
    LlamaConfig,
    PRESETS,
    init_params,
    forward,
    loss_fn,
    param_axes,
    update_buffers,
)

__all__ = [
    "LlamaConfig",
    "PRESETS",
    "init_params",
    "forward",
    "loss_fn",
    "param_axes",
    "update_buffers",
]
