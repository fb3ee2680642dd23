"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up reimplementation of the capabilities of the reference framework
(PaddlePaddle ≈2.0-beta, see /root/repo/SURVEY.md) designed for TPU:
eager + static graph execution lowered to XLA via JAX, mesh-based
distributed training over ICI/DCN collectives, bf16-first AMP, and pallas
kernels for hot ops.

Public API mirrors the paddle 2.0 namespace layout
(python/paddle/__init__.py in the reference).
"""
from __future__ import annotations

__version__ = "0.1.0"

from . import framework
from .framework import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Parameter,
    TPUPlace,
    Tensor,
    bfloat16,
    bool_,
    complex64,
    complex128,
    enable_grad,
    float16,
    float32,
    float64,
    get_device,
    grad,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_tpu,
    no_grad,
    seed,
    set_device,
    to_tensor,
    uint8,
)
from .framework.dtype import get_default_dtype, set_default_dtype  # noqa: F401

# Tensor/math API at top level (paddle.add, paddle.matmul, ...)
from .ops import (  # noqa: F401
    abs,
    accuracy,
    add,
    addmm,
    all,
    any,
    arange,
    argmax,
    argmin,
    argsort,
    asin,
    acos,
    atan,
    atan2,
    bernoulli,
    bitwise_and,
    bitwise_not,
    bitwise_or,
    bitwise_xor,
    bmm,
    broadcast_to,
    cast,
    ceil,
    chunk,
    clip,
    concat,
    cos,
    cosh,
    cross,
    cumsum,
    cumprod,
    diag,
    diag_embed,
    divide,
    dot,
    einsum,
    equal,
    erf,
    exp,
    expm1,
    expand,
    expand_as,
    eye,
    flatten,
    flip,
    floor,
    floor_divide,
    full,
    full_like,
    gather,
    gather_nd,
    greater_equal,
    greater_than,
    index_sample,
    index_select,
    inverse,
    isfinite,
    isinf,
    isnan,
    kthvalue,
    less_equal,
    less_than,
    linspace,
    log,
    log1p,
    log2,
    log10,
    logical_and,
    logical_not,
    logical_or,
    logical_xor,
    logsumexp,
    masked_select,
    matmul,
    max,
    maximum,
    mean,
    meshgrid,
    min,
    minimum,
    mod,
    multinomial,
    multiply,
    neg,
    normal,
    not_equal,
    numel,
    ones,
    ones_like,
    pow,
    prod,
    rand,
    randint,
    randn,
    randperm,
    reciprocal,
    remainder,
    repeat_interleave,
    reshape,
    roll,
    round,
    rsqrt,
    scale,
    scatter,
    scatter_nd_add,
    shard_index,
    sign,
    sin,
    sinh,
    slice,
    sort,
    split,
    sqrt,
    square,
    squeeze,
    stack,
    strided_slice,
    subtract,
    sum,
    t,
    take_along_axis,
    tan,
    tanh,
    tile,
    topk,
    transpose,
    tril,
    triu,
    trunc,
    unbind,
    uniform,
    unsqueeze,
    unstack,
    where,
    zeros,
    zeros_like,
)
from .ops import shape as shape  # noqa: F401
from .ops import sigmoid  # noqa: F401  (paddle.sigmoid, 2.0 top-level alias)

import paddle_tpu.ops as ops  # noqa: F401,E402
from . import amp  # noqa: E402
from . import io  # noqa: E402
from .framework.serialization import load, save  # noqa: E402
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import parallel  # noqa: E402
from . import distributed  # noqa: E402
from .distributed import DataParallel  # noqa: E402  (dygraph DP wrapper)
from . import models  # noqa: E402
from . import static  # noqa: E402
from . import metric  # noqa: E402
from . import inference  # noqa: E402
from . import jit_api as jit  # noqa: E402  (paddle.jit.to_static/save/load)
from .hapi import Model  # noqa: E402
from .hapi.model import summary  # noqa: E402  (hapi/model_summary.py)
from . import device  # noqa: E402  (memory facade: paddle.device surface)
from . import vision  # noqa: E402
from . import text  # noqa: E402  (text datasets: imdb/imikolov/wmt/conll05)
from . import profiler  # noqa: E402
from . import monitor  # noqa: E402  (metrics registry + training monitor)
from . import serving  # noqa: E402  (online inference: batcher/replicas/HTTP)
from . import distribution  # noqa: E402
from . import errors  # noqa: E402  (platform/enforce.h error taxonomy)
from . import incubate  # noqa: E402  (auto-checkpoint)
from . import slim  # noqa: E402  (quantization: QAT + PTQ)
from . import tensor  # noqa: E402  (2.0 tensor-API namespace split)
from . import crypto  # noqa: E402  (encrypted model io, framework/io/crypto)
from . import linalg  # noqa: E402  (2.0 linalg namespace)
from .ops import (  # noqa: E402,F401  (2.0 tail additions, flat aliases)
    clone,
    diagflat,
    dist,
    empty,
    empty_like,
    increment,
    inner,
    is_complex,
    is_integer,
    multiplex,
    mv,
    outer,
    poisson,
    put_along_axis,
    rank,
    standard_normal,
    stanh,
)
from . import utils  # noqa: E402  (run_check, gated download)
from . import reader  # noqa: E402  (reader decorator library, paddle.reader)
from . import nets  # noqa: E402  (composite helpers, fluid/nets.py)
from . import flags as _flags_mod  # noqa: E402
from .flags import get_flags, set_flags  # noqa: E402  (core.globals() API)

# one compile-cache location for every program that imports the package
# (runtime/compile_cache.py); nothing has compiled yet at this point
from .runtime import compile_cache as _compile_cache  # noqa: E402

_compile_cache.apply()
