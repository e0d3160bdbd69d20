"""Pallas TPU kernels — the replacement for the reference's csrc/ CUDA tree.

| reference (csrc/)                       | here                     |
|-----------------------------------------|--------------------------|
| transformer attention + softmax kernels | flash_attention          |
| inference softmax_context (KV cache)    | decode_attention         |
| (no reference analog: paged serving)    | paged_attention          |
| (no reference analog: latent pages)     | latent_attention         |
| (no reference analog: a window's ring)  | ring_append.ring_append  |
| adam/multi_tensor_adam.cu               | fused_adam.fused_adamw   |
| lamb/fused_lamb_cuda.cpp (trust ratios) | fused_lamb.fused_lamb    |
| transformer/normalize_kernels.cu        | layernorm.fused_layer_norm |
| quantization/quantizer.cu               | quantizer.quantize/dequantize |

The attention kernels' matmuls run in the operand dtype (bf16 hot path)
with fp32 accumulation: flash_attention, latent_attention and, for a pool
or cache narrower than float32, paged_attention and decode_attention
(their shared ``_common.online_softmax_block``); a float32 pool's stay
float32.

Kernels run in interpreter mode automatically off-TPU so the whole suite
tests on the CPU mesh.
"""

from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .paged_attention import paged_attention
from .latent_attention import latent_attention
from .fused_adam import fused_adamw, FusedAdamState
from .fused_lamb import fused_lamb, FusedLambState
from .layernorm import fused_layer_norm
from .quantizer import quantize, dequantize
