"""Pallas TPU kernels for hot ops.

Reference parity: the role of hand-written CUDA kernels in
paddle/fluid/operators/fused/ (multihead_matmul_op.cu — BERT fused
attention) and operators/jit/ (runtime-codegen CPU kernels) — here as
Pallas kernels compiled through Mosaic for the TPU's MXU/VMEM.
"""
from .conv_bn_relu import conv_bn_relu  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .grouped_experts import grouped_experts  # noqa: F401
from .int8_matmul import int8_matmul  # noqa: F401
from .layernorm_residual import layernorm_residual  # noqa: F401
from .mla_decode import mla_decode  # noqa: F401
from .optimizer_update import fused_momentum_update  # noqa: F401
