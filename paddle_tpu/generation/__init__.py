"""Generative (autoregressive) inference subsystem.

Turns a causal LM into a compile-bound token stream:

- :mod:`generation.cache` — static-shape ring KV cache pytree + the
  causal/cache mask composition (O(1) memory per sequence, functional
  index-update writes so decode shapes never change).
- :mod:`generation.sampling` — greedy / temperature / top-k sampling,
  pure jnp (traced into the compiled steps), plus the shared eager
  ``decode_loop`` the seq2seq model delegates to.
- :mod:`generation.engine` — :class:`GenerationEngine`: prefill padded
  to a sequence-length bucket ladder, ONE jitted decode step for every
  slot, warmup + compile accounting (``generation::compile`` /
  ``extra_compiles() == 0`` in steady state).
- :mod:`generation.paging` — the paged KV layout
  (``FLAGS_kv_cache_layout=paged``): a fixed-size-page pool shared by
  every slot, per-slot page tables the attention gathers through, a
  refcounted free list with copy-on-write sharing, and a radix prefix
  index over page content hashes so requests sharing a templated
  prompt map its pages instead of re-prefilling them.

Continuous batching over the engine (slot turnover mid-batch, HTTP
``/generate``) lives in :mod:`paddle_tpu.serving.continuous` /
:class:`paddle_tpu.serving.GenerationServer`.

Quickstart::

    from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
    from paddle_tpu.generation import GenerationEngine

    engine = GenerationEngine(GPTForCausalLM(gpt_tiny_config()),
                              slots=4, cache_len=64).warmup()
    tokens = engine.generate([[5, 6, 7]], max_new_tokens=16)[0]
"""
from __future__ import annotations

from ..nn.transformer import (  # noqa: F401
    PagedStaticCache,
    QuantizedPagedCache,
    QuantizedStaticCache,
    StaticCache,
    causal_mask,
)
from .cache import (  # noqa: F401
    CacheLostError,
    cache_nbytes,
    decode_mask,
    init_cache,
    insert_slot_kv,
    kv_bytes_per_token,
    layer_caches,
    prefill_mask,
    unzip_layer_caches,
)
from .cache import pad_slot_arrays, verify_mask  # noqa: F401
from .engine import COMPILE_COUNTER, GenerationEngine  # noqa: F401
from .handoff import (  # noqa: F401
    HANDOFF_CONTENT_TYPE,
    HANDOFF_PAGED_CONTENT_TYPE,
    HandoffError,
    PageSlab,
    pack_kv_pages,
    pack_kv_slab,
    unpack_kv_pages,
    unpack_kv_slab,
)
from .paging import (  # noqa: F401
    PagePool,
    PagePoolExhaustedError,
    PrefixIndex,
    TRASH_PAGE,
    chain_hashes,
    init_paged_cache,
    page_nbytes,
    split_planes,
)
from .sampling import decode_loop, sample_logits, top_k_filter  # noqa: F401

__all__ = [
    "GenerationEngine", "COMPILE_COUNTER", "CacheLostError", "StaticCache",
    "QuantizedStaticCache", "PagedStaticCache", "QuantizedPagedCache",
    "causal_mask",
    "sample_logits", "top_k_filter", "decode_loop",
    "init_cache", "layer_caches", "unzip_layer_caches",
    "insert_slot_kv", "cache_nbytes", "kv_bytes_per_token",
    "decode_mask", "prefill_mask", "verify_mask", "pad_slot_arrays",
    "HandoffError", "pack_kv_slab", "unpack_kv_slab",
    "pack_kv_pages", "unpack_kv_pages", "PageSlab",
    "HANDOFF_CONTENT_TYPE", "HANDOFF_PAGED_CONTENT_TYPE",
    "PagePool", "PagePoolExhaustedError", "PrefixIndex", "TRASH_PAGE",
    "chain_hashes", "init_paged_cache", "page_nbytes", "split_planes",
]
