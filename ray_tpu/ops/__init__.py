"""ray_tpu.ops — TPU kernels (Pallas) and their XLA reference forms.

The reference framework has no tensor ops of its own (Ray core schedules
CPUs/GPUs and moves bytes; math lives in torch/tf — SURVEY.md §5
"Long-context / sequence parallelism: absent").  In a TPU-native framework
the hot ops are part of the framework: flash attention on the MXU, ring
attention over the ICI 'sp' axis, Ulysses all-to-all attention, MoE routing,
the state-space scan and the gated short convolution (``ops/ssm.py``) and
the gated delta rule (``ops/delta.py``) of the recurrent mixers.  The ops
know no model: the mixers and FFNs that call them, one module each, live
in ``ray_tpu/models/blocks/``.
Every op has a pure-XLA reference implementation used for numerics tests and
as the CPU fallback.
"""

from ray_tpu.ops.attention import flash_attention, mha_reference
from ray_tpu.ops.delta import delta_chunked, delta_reference
from ray_tpu.ops.paged_attention import (
    paged_attention, paged_attention_reference)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses import ulysses_attention
from ray_tpu.ops.layers import rms_norm, rope, apply_rope, swiglu

__all__ = [
    "flash_attention", "mha_reference", "delta_chunked", "delta_reference",
    "paged_attention",
    "paged_attention_reference", "ring_attention",
    "ulysses_attention", "rms_norm", "rope", "apply_rope", "swiglu",
]
