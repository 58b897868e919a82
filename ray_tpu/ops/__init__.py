"""TPU compute ops: Pallas kernels and the JAX ops the models are built on.

The hot paths (attention, the experts' grouped matmul and the sum of a held
range's rows into their tokens, the gated delta rule's, lightning attention's
and the Mamba-2 state-space layers' (``ssd.py``) scans over chunks) are Pallas
TPU kernels; what is elementwise is left to XLA fusion, but for the three
mixers' passes XLA ran at a sixth to a quarter of their bytes
(``gdn_elementwise.py``, ``mamba_elementwise.py``, ``sconv_elementwise.py``). Sequence/context parallelism (ring attention) is
green-field — the reference has none (SURVEY.md §5.7).
"""

from .attention import flash_attention, mha_reference
from .gated_delta import gated_delta_rule
from .grouped_matmul import grouped_matmul
from .ring_attention import ring_attention
from .ulysses import ulysses_attention
from .norms import rms_norm
from .rope import apply_rope, rope_frequencies

__all__ = [
    "flash_attention",
    "gated_delta_rule",
    "grouped_matmul",
    "mha_reference",
    "ring_attention",
    "ulysses_attention",
    "rms_norm",
    "apply_rope",
    "rope_frequencies",
]
