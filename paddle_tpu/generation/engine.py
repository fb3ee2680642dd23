"""Compile-once generation engine: bucketed prefill + O(1)-cache decode.

The serving batcher bounds the BATCH axis with a powers-of-two bucket
ladder; autoregressive decoding re-opens the same compile-explosion on
the SEQUENCE axis (every prompt length and every growing context is a
new XLA program if shapes are dynamic). The engine closes it with a
prefill/decode split:

- **Prefill** pads the prompt up to a sequence-length bucket ladder
  (``FLAGS_generation_prefill_buckets``) and runs ONE full forward over
  the bucket, writing K/V into the admitted slot of the static ring
  cache — one compile per ladder bucket, ever.
- **Decode** is a single jitted step over ALL decode slots: read last
  tokens ``[S]``, attend the static cache window, sample, write back —
  its shapes never depend on sequence length or slot turnover, so its
  steady-state compile count is exactly 1 (asserted in tests the same
  way ``serving/unexpected_compiles`` is).

Compile accounting mirrors the serving pool: every new signature is AOT
lowered/compiled through the cost model (so decode MFU lands in the
``/statz`` ledger) and bumps the ``generation::compile`` profiler
counter — warmup snapshots it, and ``extra_compiles()`` must stay 0
under any traffic mix.

**Speculative decoding** (pass ``draft_model``): decode is memory-bound
and serial — every token pays one full-model dispatch. A small draft
GPT proposes ``k`` greedy tokens per slot (one compiled "draft" program
running the whole chain), and the target model scores all ``k + 1``
positions in ONE batched forward (the "verify" program): the longest
proposal prefix matching the target's own sampled chain is accepted,
and the target sample one past it is emitted as the correction/bonus
token — so every round emits ``1..k+1`` tokens for two dispatches
instead of ``1`` per dispatch. Greedy output is token-identical to the
plain engine by construction (acceptance compares against the target
argmax chain itself); sampled output draws every emitted token from the
target's own distribution. Both programs compile once through the
CompiledStore and the ring cache commit is the same functional index
update discipline — the physical ring simply carries ``draft_k`` extra
scratch entries (see generation/cache.py "store vs window") so the
verify step's in-place span write can never clobber a live window
entry. Rejected-position writes are garbage but provably masked until
the next round overwrites them.

**Disaggregated prefill/decode** (``kind`` warmup + KV handoff): a
prefill-tier engine runs only :meth:`prefill_export` (bucket-ladder
forward into window-width fresh caches, returning the slot's KV slab +
first sampled token), a decode-tier engine admits that slab with
:meth:`admit_prefilled` (pad to the ring store + ``insert_slot_kv``)
and runs only the decode/speculative programs — prefill scales on
compute, decode on HBM, and each tier's warmup compiles exactly its
own program set (``expected_compiles(kind)``).

The engine is single-threaded by design (one decode stream per model
replica); :mod:`paddle_tpu.serving.continuous` drives it from a slot
scheduler for continuous batching, and :meth:`generate` runs the same
slot loop inline for offline use (tests, parity goldens).
"""
from __future__ import annotations

import itertools
import operator
import threading
import time
import weakref
from collections import OrderedDict, deque

import numpy as np

import jax
import jax.numpy as jnp

from ..errors import InvalidArgumentError, PreconditionNotMetError
from ..flags import flag
from ..framework.jit import functional_call
from ..monitor import flight_recorder as _flight
from ..monitor import tracing as _tracing
from ..profiler import RecordEvent, add_span as _add_span, timed_span
from ..profiler import bump_counter as _bump_counter
from ..profiler import counters as _counters
from ..profiler import enabled as _profiler_enabled
from ..profiler import record_counter as _record_counter
from ..runtime.compiled import any_deleted
from . import cache as _cache
from . import paging as _paging
from .sampling import sample_logits

__all__ = ["GenerationEngine", "DecodeStep", "Admission", "COMPILE_COUNTER",
           "CACHE_LOST_COUNTER", "STATE_REBUILT_COUNTER", "CHUNKS_COUNTER"]

COMPILE_COUNTER = "generation::compile"
# calls that failed after they had consumed the donated cache (_dispatch)
CACHE_LOST_COUNTER = "generation::cache_lost"
# times a kept state signature was derived again because somebody had
# rebound a parameter's or a buffer's array (_KeptState)
STATE_REBUILT_COUNTER = "generation::state_rebuilt"
# one SAMPLE (profiler.record_counter) a prefill program enqueued: [its
# number within its prompt, 1 if it is the prompt's last]; a prompt
# admitted whole leaves [1, 1]
CHUNKS_COUNTER = "generation::prefill_chunks"

# deterministic engine instance ids (cache-key stability; see __init__)
_engine_counter = itertools.count()


def _leaf_signature(tree):
    """Shape and dtype of every leaf of ``tree``, in leaf order: its
    part of a compiled-store key."""
    return tuple((tuple(x.shape), str(x.dtype))
                 for x in jax.tree_util.tree_leaves(tree))


class _KeptState:
    """One model's functional state ``{"params", "frozen", "buffers"}``
    as the programs take it, and the signature of its leaves, kept
    between calls. The module tree is walked once. Every call reads
    each tensor's live array (so parameter updates flow in) and
    compares it, by identity, with the one the signature was made
    from: ``set_value``, ``set_state_dict`` and a dtype cast all
    rebind ``_array``. Only when one differs is the signature derived
    again (``generation::state_rebuilt``). The arrays are remembered
    weakly: the tensors own them, and an engine must not keep weights
    alive that their model has let go."""

    _live = operator.attrgetter("_array")

    def __init__(self, model):
        trainable, frozen = [], []
        for n, p in model.named_parameters():
            (trainable if getattr(p, "trainable", True)
             else frozen).append((n, p))
        buffers = [(n, b) for n, b in model.named_buffers()
                   if b is not None]
        self._groups = [
            (group, [n for n, _ in pairs], [t for _, t in pairs])
            for group, pairs in (("params", trainable), ("frozen", frozen),
                                 ("buffers", buffers))]
        self._seen = self._signature = None
        self._tree = lambda: None

    def current(self):
        """The state pytree of the arrays the tensors hold now."""
        tree = {group: OrderedDict(zip(names, map(self._live, tensors)))
                for group, names, tensors in self._groups}
        arrays = [a for part in tree.values() for a in part.values()]
        if self._seen is None or not all(map(
                operator.is_, arrays, map(operator.call, self._seen))):
            if self._seen is not None:
                _bump_counter(STATE_REBUILT_COUNTER)
            self._signature = _leaf_signature(tree)
            self._seen = [weakref.ref(a) for a in arrays]
        self._tree = weakref.ref(tree["params"])
        return tree

    def signature_of(self, arg):
        """The kept signature, if ``arg`` is the pytree that
        :meth:`current` returned last; else None."""
        tree = self._tree()
        if tree is not None and type(arg) is dict \
                and arg.get("params") is tree:
            return self._signature
        return None


class DecodeStep:
    """One enqueued decode step (:meth:`GenerationEngine.enqueue_step`):
    its ``[S]`` tokens and routing statistics, still on the device, the
    ring rows it had to read and those it brought from HBM (and, where
    layers choose their blocks, :meth:`GenerationEngine.sparse_blocks`),
    counted on the host as it was enqueued (only while the profiler is
    on), and the program that computes it (its
    :class:`flight_recorder.PhaseRing`:
    the fetch is noted there)."""

    __slots__ = ("tokens", "stats", "rows_read", "rows_fetched", "program",
                 "sparse")

    def __init__(self, tokens, stats, rows_read, rows_fetched, program,
                 sparse=None):
        self.tokens, self.stats, self.rows_read = tokens, stats, rows_read
        self.rows_fetched, self.program = rows_fetched, program
        self.sparse = sparse


class Admission:
    """One prompt on its way into a slot a chunk at a time
    (:meth:`GenerationEngine.begin_admission`): the slot, the prompt and
    its temperature, how many of its tokens are in (``lo``), how many
    chunk programs that took (``chunks``), the decode steps enqueued
    since the last of them (``steps``), and what the chunks left on the
    device until the last one is fetched: the token each sampled
    (``tok``: the last chunk's is the prompt's first), their routing
    statistics, and the program that computes them."""

    __slots__ = ("slot", "prompt", "temp", "lo", "chunks", "steps", "tok",
                 "stats", "program")

    def __init__(self, slot, prompt, temp):
        self.slot, self.prompt, self.temp = int(slot), prompt, float(temp)
        self.lo = self.chunks = self.steps = 0
        self.tok = self.program = None
        self.stats = []

    @property
    def done(self) -> bool:
        return self.lo >= len(self.prompt)

    def next_len(self, chunk) -> int:
        """Real tokens of the next chunk of ``chunk`` tokens."""
        return min(chunk, len(self.prompt) - self.lo)


class GenerationEngine:
    """Slot-structured generation over a causal LM.

    ``model`` must expose ``forward(input_ids, position_ids,
    attention_mask, caches) -> (logits, caches)`` with per-layer
    :class:`nn.StaticCache` support plus ``cache_spec()`` (GPTForCausalLM
    is the reference implementation). The engine owns the ring cache
    for ``slots`` concurrent sequences and exposes the two scheduler
    primitives: :meth:`admit` (prefill a prompt into a vacant slot,
    returns the first sampled token) and :meth:`step` (decode one token
    for every slot).

    **The ring cache has one owner.** Its arrays are reachable from
    ``self._kv`` (and ``self._kv_draft``) alone; every ring program that
    takes the cache is given it donated and its result is assigned back,
    so the arrays are written where they lie. Nothing may hold a cache
    array across such a call: afterwards the old ones are deleted. A
    call that fails after it consumed the cache loses every slot
    (:meth:`_dispatch`).
    """

    def __init__(self, model, *, slots=None, cache_len=None,
                 prefill_buckets=None, eos_id=None, pad_id=None,
                 max_new_tokens=None, temperature=None, top_k=None,
                 kv_cache_dtype=None, kv_cache_layout=None,
                 kv_page_size=None, kv_pool_pages=None,
                 draft_model=None, draft_k=None, seed=0):
        # lazy: serving imports generation's scheduler, so module-level
        # imports the other way would cycle
        from ..serving.batcher import parse_buckets

        from ..runtime.compiled import CompiledStore, CompileWatch

        self.model = model
        model.eval()  # generation never wants dropout
        cfg = getattr(model, "config", None)
        self.slots = int(slots if slots is not None
                         else flag("generation_decode_slots"))
        self.cache_len = int(cache_len if cache_len is not None
                             else flag("generation_kv_cache_len"))
        self.prefill_buckets = parse_buckets(
            prefill_buckets if prefill_buckets is not None
            else flag("generation_prefill_buckets"))
        if self.slots <= 0:
            raise InvalidArgumentError(
                f"generation needs at least one decode slot, got {self.slots}")
        if self.prefill_buckets[-1] > self.cache_len:
            raise InvalidArgumentError(
                f"largest prefill bucket {self.prefill_buckets[-1]} exceeds "
                f"the KV cache window {self.cache_len}; prompts must fit "
                "the cache")
        self.eos_id = (eos_id if eos_id is not None
                       else getattr(cfg, "eos_token_id", None))
        self.pad_id = int(pad_id if pad_id is not None
                          else getattr(cfg, "pad_token_id", 0))
        self.max_positions = int(getattr(cfg, "max_position_embeddings",
                                         1 << 30))
        self.default_max_new_tokens = int(
            max_new_tokens if max_new_tokens is not None
            else flag("generation_max_new_tokens"))
        self.default_temperature = float(
            temperature if temperature is not None
            else flag("generation_temperature"))
        # static: a different top_k is a different program (lax.top_k k);
        # per-request temperature stays a traced array and is free
        self.top_k = int(top_k if top_k is not None
                         else flag("generation_top_k"))
        # KV storage dtype: int8 stores the ring cache as int8 + per-head
        # dynamic scales (~4x fewer cache bytes -> ~2x the slots per HBM;
        # quantize on ring write, dequantize in the attention read). The
        # int8 avals change the compiled signature, so each dtype mode
        # gets its own cache keys in the CompiledStore — never a silent
        # reuse of the other mode's program.
        self.kv_cache_dtype = str(
            kv_cache_dtype if kv_cache_dtype is not None
            else flag("generation_kv_cache_dtype"))
        if self.kv_cache_dtype not in _cache.KV_CACHE_DTYPES:
            raise InvalidArgumentError(
                f"generation_kv_cache_dtype must be one of "
                f"{_cache.KV_CACHE_DTYPES}, got {self.kv_cache_dtype!r}")
        spec = model.cache_spec()
        # a model whose layers keep different things per slot answers
        # with one storage kind a layer (generation/cache.py); a model
        # whose layers are alike with (layers, heads, head_dim), and
        # takes the code paths it always took
        self._kinds = tuple(spec) if _cache.is_layer_kinds(spec) else None
        if self._kinds is not None:
            self._num_layers, self._num_heads, self._head_dim = (
                len(self._kinds), None, None)
            if self.kv_cache_dtype == "int8":
                self._refuse_for_kinds('kv_cache_dtype="int8"')
            if draft_model is not None:
                self._refuse_for_kinds("a draft model")
        else:
            self._num_layers, self._num_heads, self._head_dim = (
                int(spec[0]), int(spec[1]), int(spec[2]))
        # speculative decoding: a draft model makes the engine run
        # draft/verify rounds instead of single-token decode steps. The
        # physical ring store widens by draft_k scratch entries so the
        # verify span's in-place writes stay window-exact (cache.py).
        self.draft_model = draft_model
        self.speculative = draft_model is not None
        self.draft_k = int(draft_k if draft_k is not None
                           else flag("speculative_draft_k"))
        if self.speculative:
            if self.draft_k < 1:
                raise InvalidArgumentError(
                    f"speculative draft_k must be >= 1, got {self.draft_k}")
            draft_model.eval()
            dspec = draft_model.cache_spec()
            self._draft_layers, self._draft_heads, self._draft_dim = (
                int(dspec[0]), int(dspec[1]), int(dspec[2]))
            dcfg = getattr(draft_model, "config", None)
            self._draft_max_positions = int(getattr(
                dcfg, "max_position_embeddings", 1 << 30))
            dvocab = getattr(dcfg, "vocab_size", None)
            tvocab = getattr(cfg, "vocab_size", None)
            if dvocab is not None and tvocab is not None \
                    and int(dvocab) != int(tvocab):
                raise InvalidArgumentError(
                    f"draft model vocab ({dvocab}) must match the target "
                    f"({tvocab}); proposals are target token ids")
            tmax = int(getattr(cfg, "max_position_embeddings", 1 << 30))
            if self._draft_max_positions < tmax:
                # the draft tracks the target's positions exactly; a
                # shorter draft context would silently gather clamped
                # position embeddings past its limit (garbage prompt
                # view, acceptance collapse) — refuse loudly instead
                raise InvalidArgumentError(
                    f"draft max_position_embeddings "
                    f"({self._draft_max_positions}) must cover the "
                    f"target's ({tmax}); the draft decodes the same "
                    "positions")
        self.store_len = self.cache_len + (
            self.draft_k if self.speculative else 0)
        # the unit of an admission: where every kind of the cache can
        # take a prompt up again from the rows its slot holds, a prompt
        # longer than the ladder's second bucket goes in that many
        # tokens at a time, through ONE chunk program (begin_admission);
        # else None, and a prompt is admitted whole through its bucket.
        # The second bucket and not the first: every chunk reads the
        # weights again, and on the chip the first (1,024 tokens in
        # k-exaone-236b) took a tenth of the server's tokens/s for it
        # where the second took none (PERF.md, PR 45). The chunk
        # divides the store, so that none straddles a full ring's end
        chunk = int(self.prefill_buckets[:2][-1])
        self.chunk_len = chunk if (
            self._kinds is not None and _cache.kinds_continue(self._kinds)
            and chunk < self.prefill_buckets[-1]
            and self.store_len % chunk == 0) else None
        self._admission = None
        # KV layout: "ring" is the historical per-slot contiguous store;
        # "paged" draws fixed-size pages from a shared pool through
        # per-slot page tables (generation/paging.py) — same logical
        # ring, so greedy output is token-identical, plus copy-on-write
        # prefix reuse across requests.
        self.kv_cache_layout = str(
            kv_cache_layout if kv_cache_layout is not None
            else flag("kv_cache_layout"))
        if self.kv_cache_layout not in ("ring", "paged"):
            raise InvalidArgumentError(
                f"kv_cache_layout must be ring | paged, got "
                f"{self.kv_cache_layout!r}")
        self.paged = self.kv_cache_layout == "paged"
        if self.paged:
            self._refuse_for_kinds('kv_cache_layout="paged"')
            if self.kv_cache_dtype == "bfloat16":
                raise InvalidArgumentError(
                    "kv_cache_dtype=bfloat16 is the ring layout's; the "
                    "page pool stores float32 or int8")
        if self.paged and self.speculative:
            raise InvalidArgumentError(
                "speculative decoding does not compose with "
                "kv_cache_layout=paged yet; run the draft engine on the "
                "ring layout")
        self.page_size = int(kv_page_size if kv_page_size is not None
                             else flag("generation_kv_page_size"))
        if self.paged:
            if self.page_size < 1 or self.cache_len % self.page_size:
                raise InvalidArgumentError(
                    f"generation_kv_page_size {self.page_size} must be "
                    f">= 1 and divide the cache window {self.cache_len}")
            self._pages_per_slot = self.cache_len // self.page_size
            self._pool_pages_cfg = int(
                kv_pool_pages if kv_pool_pages is not None
                else flag("generation_kv_pool_pages"))
            if self._pool_pages_cfg < 0:
                raise InvalidArgumentError(
                    f"generation_kv_pool_pages must be >= 0, got "
                    f"{self._pool_pages_cfg}")
            if self._pool_pages_cfg \
                    and self._pool_pages_cfg < self._pages_per_slot:
                raise InvalidArgumentError(
                    f"generation_kv_pool_pages {self._pool_pages_cfg} "
                    f"cannot hold even one slot's window "
                    f"({self._pages_per_slot} pages)")
        # static capacity admission (FLAGS_memory_budget_check): the
        # slots x cache-len x dtype geometry is budgeted against the
        # device HBM BEFORE the rings allocate — a fleet operator learns
        # "this geometry cannot fit; suggest_decode_slots says N" at
        # boot, not as an allocator OOM mid-warmup
        self.check_memory_budget()
        self._base_key = jax.random.PRNGKey(int(seed))
        self._key_step = 0
        # the sampling-key counter is bumped from every dispatch path and
        # those paths run on different threads (prefill from HTTP handler
        # threads, decode from the batcher loop): every bump goes through
        # _next_key_step, which locks AND returns the snapshot — a bare
        # `+= 1` followed by a re-read hands two threads the same ctr,
        # correlating two requests' samples. The same lock guards the
        # speculative acceptance counters /statz reads.
        self._key_lock = threading.Lock()
        # speculative acceptance accounting (spec_stats / statz)
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        # prefix sharing is suppressed during warmup (every ladder
        # bucket must compile its own program; a matched prefix would
        # collapse later buckets onto already-compiled suffix shapes)
        self._prefix_enabled = True
        self.reset()
        self._state_kept = _KeptState(model)
        self._draft_state_kept = (_KeptState(draft_model)
                                  if self.speculative else None)
        # the ring programs take their cache donated (kv, kv_draft)
        self._prefill_jit = jax.jit(self._prefill_pure, donate_argnums=(1,))
        self._spec_prefill_jit = jax.jit(self._spec_prefill_pure,
                                         donate_argnums=(2, 3))
        self._prefill_chunk_jit = jax.jit(self._prefill_chunk_pure,
                                          donate_argnums=(1,))
        self._decode_jit = jax.jit(self._decode_pure, donate_argnums=(1,))
        self._paged_prefill_jit = jax.jit(self._paged_prefill_pure)
        self._paged_decode_jit = jax.jit(self._paged_decode_pure)
        self._prefill_export_jit = jax.jit(self._prefill_export_pure)
        self._draft_jit = jax.jit(self._draft_chain_pure,
                                  donate_argnums=(1,))
        self._verify_jit = jax.jit(self._verify_pure, donate_argnums=(1,))
        self._draft_prefill_jit = jax.jit(self._draft_prefill_pure,
                                          donate_argnums=(1,))
        # compiled prefill/decode programs live in the SHARED compiled-
        # callable runtime: AOT compile + cost capture (decode MFU in the
        # /statz ledger) + the flag-governed LRU bound, with every new
        # signature counted through ``generation::compile`` — the
        # bounded-compile discipline the batch-bucket ladder established,
        # on the sequence axis
        self._stores = {
            label: CompiledStore(f"generation_{label}",
                                 miss_counter=COMPILE_COUNTER)
            for label in ("prefill", "prefill_chunk", "decode", "draft",
                          "verify")}
        # deterministic per-engine index for the cache signature (stable
        # cache_key across runs, distinct per engine in the CostRecord
        # registry — two engines may share avals but not weights)
        self._instance = next(_engine_counter)
        self.warmed = False
        # a driver that splits its loop thread's time by phase (the
        # scheduler's stall record) puts its own dict here: each call's
        # enqueue and fetch nanoseconds are added to it (_fetched). Not a
        # parameter of step()/admit(): callers and tests wrap those
        self.phase_split = None
        # always on, driver or none: what each program's calls took
        # lately (name -> PhaseRing; the ring is also the store entry's
        # ``meta``), the program of the call in hand, and the innermost
        # phases of the enqueue in hand, until its phase is noted
        self._programs = {}
        self._program = self._nested = None
        # the serving-wide warmup-snapshot discipline; the continuous
        # batcher notes growth through this same watch
        self.watch = CompileWatch(
            lambda: _counters().get(COMPILE_COUNTER, 0),
            metric="serving/gen_unexpected_compiles",
            event="generation_unexpected_compile")

    def _refuse_for_kinds(self, what):
        """What a model with per-layer storage kinds cannot use yet
        refuses by name: an int8 ring, the paged layout, a draft model,
        the prefill/decode handoff. Each would need a form of its own
        for the kinds it does not know (a state layer has no rows to
        quantise, page or ship)."""
        if self._kinds is None:
            return
        names = sorted({type(k).__name__ for k in self._kinds})
        raise InvalidArgumentError(
            f"{what} is not available for a model whose cache_spec() is "
            f"a per-layer list of kinds ({', '.join(names)}): the ring "
            "layout in float32 or bfloat16, without a draft model or a "
            "prefill/decode handoff, is what such a cache supports")

    # -- functional state -----------------------------------------------------

    def _state(self):
        return self._state_kept.current()

    def _draft_state(self):
        return self._draft_state_kept.current()

    def reset(self):
        """Zero every slot (all caches empty, positions 0). A paged
        engine additionally rebuilds the page pool, page tables, and
        prefix index from scratch."""
        from ..monitor import registry as _mon

        ring_slots = getattr(self, "_ring_slots", self.slots)
        # a prompt half in went with the rows it had
        self._admission = None
        if self.paged:
            usable = self._pool_usable(ring_slots)
            self._kv = _paging.init_paged_cache(
                self._num_layers, self._num_heads, self._head_dim,
                self.page_size, usable, ring_slots,
                self._pages_per_slot, dtype=self.kv_cache_dtype)
            self._pool = _paging.PagePool(usable, self.page_size)
            self._index = _paging.PrefixIndex(self._pool)
            self._table_host = np.full(
                (ring_slots, self._pages_per_slot), _paging.TRASH_PAGE,
                np.int32)
            self._pos_host = np.zeros(ring_slots, np.int64)
            self._slot_live = [False] * ring_slots
            self._slot_tenant = ["default"] * ring_slots
            # per-tenant prefix accounting (prompt vs shared tokens)
            self._prefix_tenants = {}
            self._pool_gauges()
        elif self._kinds is not None:
            self._kv = _cache.init_kinds_cache(
                self._kinds, ring_slots, self.store_len,
                dtype=self.kv_cache_dtype)
            # the host's copy of ``pos``: what generation::kv_rows_read
            # is counted from, without a fetch
            self._pos_host = np.zeros(ring_slots, np.int64)
            if _profiler_enabled():
                _record_counter("generation::cache_bytes",
                                list(self.cache_bytes_by_kind()))
        else:
            self._kv = _cache.init_cache(
                self._num_layers, ring_slots, self._num_heads,
                self.store_len, self._head_dim,
                dtype=self.kv_cache_dtype)
        self._kv_draft = None
        if self.speculative:
            # draft ring arrays only — the draft mirrors the target's
            # committed token history exactly, so ONE shared pos vector
            # (the target kv's) serves both caches
            self._kv_draft = _cache.init_cache(
                self._draft_layers, ring_slots, self._draft_heads,
                self.store_len, self._draft_dim,
                dtype=self.kv_cache_dtype)[:-1]
        # every program hands the cache on with the shapes and dtypes it
        # has here, so this is its part of every later call's signature
        self._kv_signature = _leaf_signature(self._kv)
        self._kv_draft_signature = _leaf_signature(self._kv_draft)
        # the decode-capacity denominators, as registry gauges: what the
        # KV cache costs in HBM lands in /metrics next to the hbm/*
        # gauges it competes with (int8 mode shows the ~4x cut directly)
        _mon.gauge("generation/kv_cache_bytes").set(
            _cache.cache_nbytes(self._kv))
        _mon.gauge("generation/kv_bytes_per_token").set(
            self.kv_bytes_per_token())
        return self

    def cache_nbytes(self) -> int:
        """Device bytes the whole decode cache occupies (all slots,
        values + scales + positions, plus the draft ring when
        speculative) — the measured side of the int8-vs-f32 HBM claim."""
        n = _cache.cache_nbytes(self._kv)
        if self.speculative:
            n += _cache.cache_nbytes(self._kv_draft)
        return n

    def device_memory_stats(self) -> dict:
        """Allocator state of the device that holds the cache, where the
        backend reports it (the CPU backend reports nothing)."""
        return _flight.allocator_stats(next(iter(self._kv[-1].devices())))

    def kv_bytes_per_token(self) -> int:
        """Cache bytes one decoded token occupies across all layers (a
        state layer's share is nothing: its slot costs a constant)."""
        if self._kinds is not None:
            return _cache.kinds_bytes_per_token(self._kinds,
                                                self.kv_cache_dtype)
        return _cache.kv_bytes_per_token(
            self._num_layers, self._num_heads, self._head_dim,
            self.kv_cache_dtype)

    # -- static HBM capacity planning -----------------------------------------

    @staticmethod
    def _module_nbytes(model) -> int:
        total = 0
        for _n, p in model.named_parameters():
            a = p._array
            total += int(np.prod(a.shape, dtype=np.int64)) \
                * np.dtype(a.dtype).itemsize
        for _n, b in model.named_buffers():
            if b is None:
                continue
            a = b._array
            total += int(np.prod(a.shape, dtype=np.int64)) \
                * np.dtype(a.dtype).itemsize
        return total

    def param_nbytes(self) -> int:
        """Device bytes the model weights occupy (target + draft when
        speculative) — the fixed term of the capacity plan."""
        total = self._module_nbytes(self.model)
        if self.speculative:
            total += self._module_nbytes(self.draft_model)
        return total

    def _pool_usable(self, slots=None) -> int:
        """Usable pages (excluding trash) the paged pool holds for
        ``slots`` decode slots: the configured override, or slots x
        pages-per-slot (the ring-equivalent no-overcommit default)."""
        n = int(slots if slots is not None else self.slots)
        return self._pool_pages_cfg or n * self._pages_per_slot

    def page_nbytes(self, kv_cache_dtype=None) -> int:
        """Pool bytes ONE page costs across all layers (values + scales
        at int8) — the per-page unit of the paged capacity plan."""
        dtype = str(kv_cache_dtype if kv_cache_dtype is not None
                    else self.kv_cache_dtype)
        return _paging.page_nbytes(
            self._num_layers, self._num_heads, self._head_dim,
            self.page_size, dtype)

    def slot_nbytes(self, kv_cache_dtype=None) -> int:
        """Cache bytes ONE decode slot costs at this engine's geometry.

        Ring: ``store_len x kv_bytes_per_token`` (values + scales at
        int8) plus the slot's position word, plus the draft ring's
        analog when speculative. Paged: the slot's worst-case
        pages-in-flight (``pages_per_slot``) x ``page_nbytes`` plus its
        page-table row and position word — NOT ``store_len x
        kv_bytes_per_token``, which double-counts the speculative
        margin a paged slot never allocates. The per-slot divisor of
        :meth:`suggest_decode_slots`."""
        dtype = str(kv_cache_dtype if kv_cache_dtype is not None
                    else self.kv_cache_dtype)
        if self.paged:
            return (self._pages_per_slot * self.page_nbytes(dtype)
                    + self._pages_per_slot * 4 + 4)
        if self._kinds is not None:
            # every K/V layer's ring at its own length (store_len rows,
            # or its window), a constant in every state layer, the
            # position word
            return _cache.kinds_slot_nbytes(self._kinds, self.store_len,
                                            dtype) + 4
        per = self.store_len * _cache.kv_bytes_per_token(
            self._num_layers, self._num_heads, self._head_dim, dtype) + 4
        if self.speculative:
            per += self.store_len * _cache.kv_bytes_per_token(
                self._draft_layers, self._draft_heads, self._draft_dim,
                dtype)
        return per

    def hbm_required_bytes(self, slots=None, kv_cache_dtype=None) -> int:
        """Predicted device bytes the engine's geometry holds resident:
        weights plus ``slots`` rings (ring layout), or weights plus the
        page pool + trash page + page tables (paged layout) — the
        static plan the capacity admission and
        :meth:`suggest_decode_slots` budget against. Matches
        :meth:`cache_nbytes` on the real arrays BYTE-EXACTLY in both
        layouts (asserted in tests/test_paged_kv.py)."""
        n = int(slots if slots is not None else self.slots)
        if self.paged:
            pnb = self.page_nbytes(kv_cache_dtype)
            pool = (self._pool_pages_cfg
                    or n * self._pages_per_slot)
            return (self.param_nbytes() + (pool + 1) * pnb
                    + n * (self._pages_per_slot * 4 + 4))
        return self.param_nbytes() + n * self.slot_nbytes(kv_cache_dtype)

    def suggest_decode_slots(self, hbm_budget_bytes=None,
                             kv_cache_dtype=None) -> int:
        """Decode slots this model fits in ``hbm_budget_bytes`` (default:
        the device HBM from the cost-model peaks table): ``(budget -
        weights) // slot_nbytes``, with the paged layout additionally
        reserving the trash page before dividing (its pool grows by
        ``pages_per_slot`` pages + one table row per slot).
        ``kv_cache_dtype`` asks the other cache mode's answer (int8
        roughly doubles the count) without rebuilding the engine — the
        serving-capacity recipe in README "Memory planning"."""
        if hbm_budget_bytes is None:
            from ..analysis.memory import hbm_budget_bytes as _budget

            hbm_budget_bytes = _budget()
        avail = int(hbm_budget_bytes) - self.param_nbytes()
        if self.paged:
            avail -= self.page_nbytes(kv_cache_dtype)  # the trash page
        if avail <= 0:
            return 0
        return int(avail // self.slot_nbytes(kv_cache_dtype))

    def check_memory_budget(self, level=None, budget_bytes=None):
        """Refuse (strict) or warn about a slots x cache-len x dtype
        geometry the static plan says cannot fit the device HBM.
        ``level`` defaults to ``FLAGS_memory_budget_check``; returns the
        required bytes when admitted."""
        from ..analysis.memory import (
            MemoryBudgetError,
            _fmt_bytes,
            hbm_budget_bytes as _budget,
        )

        lvl = str(level if level is not None
                  else flag("memory_budget_check")).strip().lower()
        if lvl in ("", "0", "off", "false", "no"):
            return None
        budget = int(budget_bytes if budget_bytes is not None
                     else _budget())
        required = self.hbm_required_bytes()
        if budget <= 0 or required <= budget:
            return required
        fits = self.suggest_decode_slots(budget)
        msg = (
            f"generation geometry cannot fit: {self.slots} slot(s) x "
            f"cache_len {self.cache_len} (store {self.store_len}) x "
            f"{self.kv_cache_dtype} KV needs "
            f"{_fmt_bytes(required)} (weights "
            f"{_fmt_bytes(self.param_nbytes())} + "
            f"{_fmt_bytes(self.slot_nbytes())}/slot) against "
            f"{_fmt_bytes(budget)} HBM; suggest_decode_slots("
            f"{budget}) = {fits}"
            + ("" if self.kv_cache_dtype == "int8"
               or self._kinds is not None else
               f" (int8 KV would fit "
               f"{self.suggest_decode_slots(budget, 'int8')})"))
        _flight.record_event(
            "memory_budget", scope="generation", verdict="over_budget",
            required_bytes=required, budget_bytes=budget,
            slots=self.slots, cache_len=self.cache_len,
            kv_cache_dtype=self.kv_cache_dtype, suggested_slots=fits)
        if lvl == "strict":
            raise MemoryBudgetError(msg, budget_bytes=budget)
        import warnings

        warnings.warn(f"memory_budget_check={lvl}: {msg}",
                      RuntimeWarning, stacklevel=3)
        return required

    # -- compile accounting ---------------------------------------------------

    def _dispatch(self, label, jitted, make_args):
        """Run one compiled step through the shared compiled-callable
        runtime: new signatures are AOT-compiled and cost-captured (MFU
        in ``/statz``) under the one policy every dispatch site shares,
        and every compile is COUNTED (``generation::compile``, the
        store's miss counter). ``make_args`` builds the argument tuple,
        so that the look at the kept state and the signature are one
        ``generation::args`` span inside the caller's. The call's own
        few arrays go to the executable as host arrays and its call
        places them (``runtime::launch``): on the v5e's host that took
        half a millisecond less a step than a ``jnp.asarray`` each
        (PERF.md, PR 28). The call's innermost phases (this span, the
        runtime's lookup, launch and a first dispatch's compile) are
        timed whatever the profiler's state, into the list that the
        caller's :meth:`_note_phase` files with the program's ring."""
        store = self._stores[label]
        if self._nested is None:
            self._nested = []
        nested = self._nested
        with timed_span("generation::args", nested):
            args = make_args()
            sig = self._signature(args)
        entry, disposition = store.get_or_build(
            sig, lambda: (jitted, self._program_ring(label, args)),
            nested=nested)
        self._program = entry.meta
        entry.meta.runs += 1
        # the slot-admission / dispatch span (if one is current) learns
        # whether this call compiled — the compile-vs-execute attribution
        # a /tracez reader needs (the runtime adds cache_key + flops)
        _tracing.annotate(program_cache=disposition)
        try:
            return store.dispatch(entry, *args, donated=self._cache_leaves,
                                  nested=nested)
        except Exception as e:
            # a donated cache that the failed call consumed is gone for
            # every slot, not only for the caller's
            if not any_deleted(self._cache_leaves()):
                raise
            self.reset()
            _bump_counter(CACHE_LOST_COUNTER)
            error = f"{type(e).__name__}: {e}"
            _flight.record_event("generation_cache_lost", program=label,
                                 slots=self.slots, error=error[:300])
            raise _cache.CacheLostError(
                f"the {label} program failed after it had consumed the "
                "donated KV cache: every slot's context is lost, the "
                f"ring was rebuilt empty ({error})") from e

    def _signature(self, args):
        """The compiled-store key of one call: this engine and the
        shape and dtype of every argument leaf, in leaf order. An
        argument that is a kept state or a cache brings the signature
        kept with it (:class:`_KeptState`, :meth:`reset`); only the
        call's own few arrays are looked at."""
        sig = (self._instance,)
        for arg in args:
            sig += self._kept_signature(arg) or _leaf_signature(arg)
        return sig

    def _program_ring(self, label, args):
        """The ring of a program about to be built, by name: the store
        label, and for a program that takes a prompt its bucket (every
        prefill twin is handed the padded prompt as the one host
        ``[1, bucket]`` array of its call): ``prefill/512``, ``decode``,
        ``draft``, ``verify``. Twins of one name (a decode tier's draft
        prefill beside an export) share a ring."""
        name = label
        for a in args:
            if isinstance(a, np.ndarray) and a.ndim == 2 and len(a) == 1:
                name = f"{label}/{a.shape[1]}"
        return self._programs.setdefault(name, _flight.PhaseRing(name))

    def program_rings(self):
        """What each program's calls took lately, for a driver's stall
        record (:func:`flight_recorder.held_among`)."""
        return list(self._programs.values())

    def _kept_signature(self, arg):
        if arg is self._kv:
            return self._kv_signature
        if arg is self._kv_draft:
            return self._kv_draft_signature
        kept = self._state_kept.signature_of(arg)
        if kept is None and self.speculative:
            kept = self._draft_state_kept.signature_of(arg)
        return kept

    def _cache_leaves(self):
        """Every array of the cache: what a ring program may consume."""
        return jax.tree_util.tree_leaves((self._kv, self._kv_draft))

    def _fetched(self, phase, t0_ns, value, to_host, program=None):
        """``value`` on the host, the wait for it timed as the span
        ``<phase>_fetch``: ``generation::prefill`` / ``::decode`` close
        when the program is enqueued, this one closes when its result
        has arrived, so a slow device reads slow here. ``t0_ns`` is when
        the enqueue phase began (``None`` where :meth:`enqueue_step` has
        accounted for it already); both phases' nanoseconds go to the
        driver's :attr:`phase_split`, if it gave one, and to the ring of
        ``program`` (default: the one dispatched last)."""
        t1 = time.perf_counter_ns()
        out = to_host(value)
        t2 = time.perf_counter_ns()
        _add_span(phase + "_fetch", t1, t2)
        if t0_ns is not None:
            self._note_phase(phase, t0_ns, t1 - t0_ns)
        self._note_phase(phase + "_fetch", t1, t2 - t1, program)
        return out

    def _note_phase(self, name, t0_ns, ns, program=None):
        """One sibling phase of the caller's thread, begun at ``t0_ns``:
        added to the driver's split, and filed as one instance in the
        program's ring. An enqueue phase takes the innermost phases that
        :meth:`_dispatch` timed inside it along."""
        split = self.phase_split
        if split is not None:
            split[name] = split.get(name, 0) + ns
        program = program or self._program
        if program is not None:
            nested = None
            if not name.endswith("_fetch"):
                nested, self._nested = self._nested, None
            program.note(name, t0_ns, ns, nested)

    def extra_compiles(self) -> int:
        """Compiles since warmup — steady state must keep this at 0."""
        return self.watch.extra()

    def expected_compiles(self, kind="generate") -> int:
        """Exact warmup program count for a backend ``kind``:

        - ``generate`` (unified): one prefill per ladder bucket, plus
          either the single decode program or the draft + verify pair;
          where prompts go in by chunks (:attr:`chunk_len`), the
          buckets a shorter prompt takes, the chunk program and the
          decode program: the longer buckets are never reached;
        - ``prefill`` (disaggregated prefill tier): one prefill-export
          per bucket, nothing else;
        - ``decode`` (disaggregated decode tier): the decode (or
          draft + verify) program(s); a speculative decode tier also
          compiles one draft-prefill per bucket (the handed-off slab is
          target-only — the draft's view of the prompt is built at
          admission).
        """
        buckets = len(self.prefill_buckets)
        decode = 2 if self.speculative else 1
        if kind == "generate":
            return len(self._reached_buckets()) + decode \
                + (self.chunk_len is not None)
        if kind == "prefill":
            return buckets
        if kind == "decode":
            return decode + (buckets if self.speculative else 0)
        raise InvalidArgumentError(
            f"unknown backend kind {kind!r}; expected generate | "
            "prefill | decode")

    def warmup(self, kind="generate"):
        """Compile exactly ``expected_compiles(kind)`` programs ahead
        of traffic, then snapshot the compile counter. Idempotent."""
        if self.warmed:
            return self
        self.expected_compiles(kind)  # validates the kind loudly
        if kind != "generate":
            self._refuse_for_kinds(f"backend kind {kind!r}")
        # warmup must compile EVERY ladder bucket: with the prefix index
        # live, bucket N's pad prompt would share bucket N-1's pages and
        # prefill only a suffix — a smaller, already-compiled shape —
        # leaving the big bucket to compile on the first live prompt
        self._prefix_enabled = False
        try:
            self._warmup_drive(kind)
        finally:
            self._prefix_enabled = True
        self.reset()  # warmup traffic must not look like live context
        with self._key_lock:
            self._spec_rounds = 0
            self._spec_proposed = 0
            self._spec_accepted = 0
        self.watch.arm()
        self.warmed = True
        _flight.record_event(
            "generation_warmup", backend_kind=kind,
            prefill_buckets=list(self.prefill_buckets),
            slots=self.slots, cache_len=self.cache_len,
            kv_cache_layout=self.kv_cache_layout,
            speculative=self.speculative,
            programs=self.expected_compiles(kind))
        return self

    def _warmup_drive(self, kind):
        """Every program of ``kind``, compiled as a pipeline and run
        once: each is traced and lowered here while the ones before it
        compile (or load from the persistent cache) on their worker
        threads, then each warm-up step runs through the normal entry
        point, whose first dispatch waits for its executable."""
        with RecordEvent("generation::warmup"):
            if kind == "prefill":
                # a prefill tier never decodes: shrink the untouched
                # decode (and draft) rings to one slot — this tier's
                # HBM belongs to prefill activations, not a ring
                # nobody writes (its selling point in disaggregation)
                self._ring_slots = 1
                self.reset()
            plan = self._warmup_plan(kind)
            if not self.paged:
                # not for the paged layout: its admission allocates
                # pages while it builds the arguments
                for calls, _ in plan:
                    for call in calls:
                        self._precompile(*call)
            for _, run in plan:
                run()

    def _precompile(self, label, jitted, make_args):
        """Start compiling one program ahead of its first dispatch
        (``CompiledStore.precompile``)."""
        args = make_args()
        self._stores[label].precompile(
            self._signature(args),
            lambda: (jitted, self._program_ring(label, args)), args)

    def _warmup_plan(self, kind):
        """``[(calls, run)]``: ``run()`` is one warm-up step through the
        normal entry point, ``calls`` the ``(label, jitted, make_args)``
        of the programs it dispatches; the chunk program first, where
        there is one (the slowest to load: 3.5-3.7 s), then the prefill
        buckets largest first, the decode program last. The programs
        are traced one after another on this thread while the ones
        before load on theirs, so what the warm-up waits for at its end
        is the LAST program's load, and the other programs' first runs
        pass behind it: the largest buckets' loads took 2.1-3.8 s
        there, the decode program's 1.6 (PERF.md, PR 47)."""
        temp = self.default_temperature
        zeros_i = np.zeros(self.slots, np.int32)
        zeros_f = np.zeros(self.slots, np.float32)
        plan = []
        if kind != "prefill" and self.speculative:
            toks = jnp.asarray(zeros_i)
            proposals = jnp.zeros((self.slots, self.draft_k), jnp.int32)
            plan.append((
                [self._draft_call(toks),
                 self._verify_call(toks, proposals, zeros_f, 0)],
                lambda: self.spec_step(zeros_i, zeros_f)))
        elif kind != "prefill":
            plan.append(([self._decode_call(zeros_i, zeros_f, 0)],
                         lambda: self.step(zeros_i, zeros_f)))
        for bucket in (self._reached_buckets() if kind == "generate"
                       else self.prefill_buckets):
            prompt = [self.pad_id] * int(bucket)
            padded, n = self._padded_prompt(prompt)
            if kind == "generate":
                plan.append((
                    [self._prefill_call(0, padded, n, temp, 0)],
                    lambda p=prompt: self.admit(0, p)))
            elif kind == "prefill":
                plan.append((
                    [self._export_call(padded, n, temp, 0)],
                    lambda p=prompt: self.prefill_export(p)))
            elif self.speculative:
                plan.append((
                    [self._draft_prefill_call(0, padded, n)],
                    lambda p=prompt: self._admit_draft(0, p)))
        if kind == "generate" and self.chunk_len is not None:
            # two chunks, the second of one token
            prompt = [self.pad_id] * (self.chunk_len + 1)
            plan.append((
                [self._chunk_call(Admission(0, prompt, temp), 0)],
                lambda p=prompt: self.admit(0, p)))
        if kind == "decode":
            # pre-drive the handoff admission: the eager pad/insert ops
            # pay their one-time op compiles NOW (per plane shape), not
            # on the first live slab, where that cold cost would be the
            # first request's TTFT
            plan.append(([], lambda: self.admit_prefilled(
                0, self._fresh_slot_planes(), 1, 0,
                prompt=[self.pad_id] if self.speculative else None)))
        return plan[::-1]

    def _reached_buckets(self):
        """The ladder buckets an admission can take: all of them, or,
        where longer prompts go in by chunks, those a chunk covers."""
        return [b for b in self.prefill_buckets
                if self.chunk_len is None or b <= self.chunk_len]

    def _fresh_slot_planes(self):
        """Zeroed window-width per-slot planes (a synthetic empty slab
        — warmup's stand-in for a real handoff)."""
        return tuple(
            jnp.stack([a[0] for a in plane]) for plane in _cache.init_cache(
                self._num_layers, 1, self._num_heads, self.cache_len,
                self._head_dim, dtype=self.kv_cache_dtype)[:-1])

    # -- pure steps (jitted) --------------------------------------------------

    def _prefill_forward(self, model, state, layers, heads, head_dim,
                         tokens, length, store):
        """One bucketed prefill forward into window-width fresh caches:
        returns (logits ``[1, P, V]``, the slot's planes, each a tuple
        over layers of ``[H, C, D]`` (scales ``[H, C]``), zero-padded up
        to the ring store). Shared by target prefill, draft prefill,
        and the prefill-export program (window == store there)."""
        p = tokens.shape[1]
        fresh = _cache.fresh_layer_caches(
            layers, 1, heads, self.cache_len, head_dim,
            dtype=self.kv_cache_dtype)
        mask = _cache.prefill_mask(p, self.cache_len, length)
        pos_ids = jnp.arange(p, dtype=jnp.int32)[None]
        (logits, new_caches), _ = functional_call(
            model, state, tokens,
            position_ids=pos_ids, attention_mask=mask, caches=fresh)
        return logits, tuple(
            _cache.pad_slot_arrays([a[0] for a in plane], store, axis=1)
            for plane in _cache.unzip_layer_caches(new_caches))

    def _sample_first(self, logits, length, temp, ctr):
        """Sample the first generated token from the last REAL prompt
        position of a prefill's logits (a model may hand back that one
        row alone: the head over a 16 k bucket is 1.3 GB of float32 of
        which one row is read)."""
        last = logits[0, 0] if logits.shape[1] == 1 else \
            jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, axis=0, keepdims=False)
        key = jax.random.fold_in(self._base_key, ctr)
        return sample_logits(last[None], key, temp[None], self.top_k)[0]

    def _prefill_pure(self, state, kv, slot, tokens, length, temp, ctr):
        """Bucketed prefill of ONE prompt into decode slot ``slot``.

        ``tokens [1, P]`` (P = a ladder bucket), ``length`` = true prompt
        length. Runs the full forward over the bucket with fresh
        per-layer caches, installs the K/V (and, at int8, the scale
        planes) into the slot (zero-padded from the window width up to
        the ring store), and samples the first generated token from the
        last REAL prompt position.
        """
        if self._kinds is not None:
            return self._kinds_prefill_pure(state, kv, slot, tokens,
                                            length, temp, ctr)
        logits, planes = self._prefill_forward(
            self.model, state, self._num_layers, self._num_heads,
            self._head_dim, tokens, length, self.store_len)
        kv = _cache.insert_slot_kv(kv, slot, planes, length)
        tok = self._sample_first(logits, length, temp, ctr)
        return kv, tok

    def _kinds_prefill_pure(self, state, kv, slot, tokens, length, temp,
                            ctr):
        """:meth:`_prefill_pure` for a per-layer list of kinds: the
        forward runs from position 0 into fresh one-row caches of every
        kind, and what each layer then holds - K/V rows (a window
        layer's fresh ring is its window long and ends up with the last
        ``min(length, window)`` rows at ``position mod window``), or the
        state after the last real token and the convolution's tail - is
        written into the slot. The model is given the additive
        key-padding mask ``[1, 1, 1, P]`` and applies causality itself,
        by blocks. Also returns the model's routing statistics, if it
        keeps any."""
        p = tokens.shape[1]
        fresh = _cache.kinds_layer_caches(
            self._kinds, _cache.init_kinds_cache(
                self._kinds, 1, self.store_len, self.kv_cache_dtype))
        mask = jnp.where(jnp.arange(p) < length, 0.0,
                         _cache.NEG_INF).astype(jnp.float32)[None, None, None]
        (logits, new_caches), _ = functional_call(
            self.model, state, tokens,
            position_ids=jnp.arange(p, dtype=jnp.int32)[None],
            attention_mask=mask, caches=fresh)
        rows = tuple(tuple(a[0] for a in arrays)
                     for arrays in _cache.unzip_kinds_caches(new_caches))
        kv = _cache.insert_slot_kv(kv, slot, rows, length)
        tok = self._sample_first(logits, length, temp, ctr)
        return kv, tok, self._model_stats()

    def _prefill_chunk_pure(self, state, kv, slot, tokens, lo, length, temp,
                            ctr):
        """One CHUNK of a prompt into decode slot ``slot``, whose rings
        hold the prompt's rows below ``lo``: ``tokens [1, C]`` stand at
        ``lo .. lo + C - 1`` and the first ``length`` of them are real.
        The forward runs on the slot's own rows (each layer attends what
        its ring holds and the chunk, and writes the chunk's rows in),
        the rows go back into the slot and its ``pos`` becomes ``lo +
        length`` whatever it was: a decode step that ran over the
        half-filled slot since the last chunk moved it on by one and
        left one row, which is overwritten here or was out of every
        later query's band (``nn/gqa.py``). ``lo`` is data: ONE program
        for every chunk of every prompt. The token is sampled from the
        chunk's last real row; only the prompt's last chunk's is read."""
        c = tokens.shape[1]
        caches = _cache.kinds_slot_caches(self._kinds, kv, slot, lo[None])
        mask = jnp.where(jnp.arange(c) < length, 0.0,
                         _cache.NEG_INF).astype(jnp.float32)[None, None, None]
        (logits, new_caches), _ = functional_call(
            self.model, state, tokens,
            position_ids=(lo + jnp.arange(c, dtype=jnp.int32))[None],
            attention_mask=mask, caches=caches)
        rows = tuple(tuple(a[0] for a in arrays)
                     for arrays in _cache.unzip_kinds_caches(new_caches))
        kv = _cache.insert_slot_kv(kv, slot, rows, lo + length)
        tok = self._sample_first(logits, length, temp, ctr)
        return kv, tok, self._model_stats()

    def _model_stats(self):
        """The routing statistics of the forward just traced (a pytree
        of small arrays the program returns beside its result; fetched
        only while the profiler is on), or ``None``."""
        stats = getattr(self.model, "routing_stats", None)
        return None if stats is None else stats()

    def _spec_prefill_pure(self, state, dstate, kv, kv_draft, slot,
                           tokens, length, temp, ctr):
        """Speculative twin of :meth:`_prefill_pure`: ONE program
        prefills the prompt through BOTH models — the draft ring must
        hold the same committed history as the target's before the
        first draft chain runs."""
        logits, planes = self._prefill_forward(
            self.model, state, self._num_layers, self._num_heads,
            self._head_dim, tokens, length, self.store_len)
        kv = _cache.insert_slot_kv(kv, slot, planes, length)
        _, dplanes = self._prefill_forward(
            self.draft_model, dstate, self._draft_layers,
            self._draft_heads, self._draft_dim, tokens, length,
            self.store_len)
        kv_draft = _cache.insert_slot_planes(kv_draft, slot, dplanes)
        tok = self._sample_first(logits, length, temp, ctr)
        return kv, kv_draft, tok

    def _prefill_export_pure(self, state, tokens, length, temp, ctr):
        """Prefill-tier program: the bucketed forward WITHOUT a decode
        ring — returns the window-width per-slot KV planes (the handoff
        slab) and the first sampled token. The decode tier lands the
        slab with :meth:`admit_prefilled`."""
        logits, planes = self._prefill_forward(
            self.model, state, self._num_layers, self._num_heads,
            self._head_dim, tokens, length, self.cache_len)
        tok = self._sample_first(logits, length, temp, ctr)
        return tuple(jnp.stack(plane) for plane in planes), tok

    def _draft_prefill_pure(self, dstate, kv_draft, slot, tokens,
                            length):
        """Draft-only prefill into draft slot ``slot`` — a decode-tier
        engine admitting a handed-off TARGET slab still needs the
        draft's view of the prompt before it can speculate on it."""
        _, dplanes = self._prefill_forward(
            self.draft_model, dstate, self._draft_layers,
            self._draft_heads, self._draft_dim, tokens, length,
            self.store_len)
        return _cache.insert_slot_planes(kv_draft, slot, dplanes)

    def _decode_pure(self, state, kv, tokens, temps, ctr):
        """One decode step for EVERY slot: ``tokens [S]`` (each slot's
        last token) -> next token per slot. Static shapes throughout —
        this is the program whose compile count is exactly 1."""
        kinds = self._kinds
        caches = (_cache.layer_caches(*kv) if kinds is None
                  else _cache.kinds_layer_caches(kinds, kv))
        pos = kv[-1]
        pos_ids = jnp.minimum(pos, self.max_positions - 1)[:, None]
        # one mask a distinct ring length: a layer that keeps a window
        # of rows is handed the mask of that ring, not the store's
        mask = (_cache.decode_mask(pos, self.store_len,
                                   window=self.cache_len) if kinds is None
                else _cache.kinds_decode_mask(kinds, pos, self.store_len,
                                              window=self.cache_len))
        (logits, new_caches), _ = functional_call(
            self.model, state, tokens[:, None],
            position_ids=pos_ids, attention_mask=mask, caches=caches)
        kv = (_cache.unzip_layer_caches(new_caches) if kinds is None
              else _cache.unzip_kinds_caches(new_caches)) + (pos + 1,)
        key = jax.random.fold_in(self._base_key, ctr)
        nxt = sample_logits(logits[:, 0], key, temps, self.top_k)
        if kinds is not None:
            return kv, nxt, self._model_stats()
        return kv, nxt

    def _paged_prefill_pure(self, state, kv, slot, tokens, shared_len,
                            suffix_len, total_len, temp, ctr):
        """Unified full/suffix prefill of ONE prompt straight into the
        page pool. ``tokens [1, P]`` are the prompt's SUFFIX (everything
        past the ``shared_len`` tokens whose pages the prefix index
        mapped; ``shared_len == 0`` is a plain full prefill — one
        program per ladder bucket serves both). The forward runs over
        the slot's paged cache view directly: reads gather the shared
        prefix pages through the admitted table row, suffix K/V scatters
        into the slot's newly allocated pages at logical positions
        ``shared_len + t``. Pages 0..m-1 are shared and never written
        (the suffix starts at a page boundary; the bucket cannot wrap —
        admission guarantees ``shared_len + bucket <= cache_len``).
        Samples the first generated token from the last REAL suffix
        position."""
        p = tokens.shape[1]
        table, pos = kv[-2], kv[-1]
        row = table[slot][None]                    # [1, NP]
        caches = _paging.paged_layer_caches(
            kv, table=row, pos=shared_len[None])
        mask = _paging.suffix_prefill_mask(
            p, self.cache_len, shared_len, suffix_len)
        pos_ids = jnp.minimum(
            shared_len + jnp.arange(p, dtype=jnp.int32),
            self.max_positions - 1)[None]
        (logits, new_caches), _ = functional_call(
            self.model, state, tokens,
            position_ids=pos_ids, attention_mask=mask, caches=caches)
        kv = _paging.stack_paged_planes(new_caches) + (
            table, pos.at[slot].set(total_len))
        tok = self._sample_first(logits, suffix_len, temp, ctr)
        return kv, tok

    def _paged_decode_pure(self, state, kv, tokens, temps, ctr):
        """Paged twin of :meth:`_decode_pure`: the identical one-step
        decode over every slot, with reads/writes routed through the
        page tables (store == window == ``cache_len``; the paged layout
        carries no speculative margin). Host-side page management
        (:meth:`_prepare_decode_writes`) already made every busy slot's
        write-target page private, so this program never recompiles and
        never aliases a shared page."""
        caches = _paging.paged_layer_caches(kv)
        table, pos = kv[-2], kv[-1]
        pos_ids = jnp.minimum(pos, self.max_positions - 1)[:, None]
        mask = _cache.decode_mask(pos, self.cache_len)
        (logits, new_caches), _ = functional_call(
            self.model, state, tokens[:, None],
            position_ids=pos_ids, attention_mask=mask, caches=caches)
        kv = _paging.stack_paged_planes(new_caches) + (table, pos + 1)
        key = jax.random.fold_in(self._base_key, ctr)
        nxt = sample_logits(logits[:, 0], key, temps, self.top_k)
        return kv, nxt

    def _draft_chain_pure(self, dstate, kv_draft, pos, tokens):
        """The draft program: ``k`` greedy proposals per slot from one
        dispatch. ``k + 1`` chained single-token draft decode steps —
        step ``j`` writes its input token's K/V at ``pos + j`` (so the
        draft ring ends the round holding the FULL proposed chain,
        including the last proposal: on full acceptance the draft's
        committed history still mirrors the target's) and feeds its
        argmax forward. Returns (draft arrays, proposals ``[S, k]``)."""
        caches = _cache.layer_caches(*(kv_draft + (pos,)))
        cur = tokens
        proposals = []
        for j in range(self.draft_k + 1):
            pj = pos + j
            pos_ids = jnp.minimum(pj, self._draft_max_positions - 1)[:, None]
            mask = _cache.decode_mask(pj, self.store_len,
                                      window=self.cache_len)
            (logits, caches), _ = functional_call(
                self.draft_model, dstate, cur[:, None],
                position_ids=pos_ids, attention_mask=mask, caches=caches)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            if j < self.draft_k:
                proposals.append(nxt)
            cur = nxt
        return (_cache.unzip_layer_caches(caches),
                jnp.stack(proposals, axis=1))

    def _verify_pure(self, state, kv, tokens, proposals, temps, ctr):
        """The verify program: ONE batched target forward over all
        ``k + 1`` in-flight positions of every slot.

        Inputs ``[S, k+1] = [last committed token | k proposals]`` write
        their K/V into the ring span ``pos .. pos+k`` (in place —
        window-exact by the store margin) and produce logits at every
        position; the target's own sampled chain ``ts`` decides
        acceptance: the longest proposal prefix with ``proposal[i] ==
        ts[i]`` is accepted and ``ts[m]`` (the sample one past it) is
        the correction/bonus token, so the round emits ``ts[:, :m+1]``
        — exactly the token sequence the plain engine would have
        produced one dispatch at a time (greedy: ``ts`` IS the argmax
        chain). ``pos`` advances by the emitted count; rejected-position
        ring writes are left as masked garbage for the next round's
        span to overwrite."""
        span = self.draft_k + 1
        seq = jnp.concatenate([tokens[:, None], proposals], axis=1)
        caches = _cache.layer_caches(*kv)
        pos = kv[-1]
        pos_ids = jnp.minimum(
            pos[:, None] + jnp.arange(span, dtype=jnp.int32)[None, :],
            self.max_positions - 1)
        mask = _cache.verify_mask(pos, self.store_len, span,
                                  window=self.cache_len)
        (logits, new_caches), _ = functional_call(
            self.model, state, seq,
            position_ids=pos_ids, attention_mask=mask, caches=caches)
        key = jax.random.fold_in(self._base_key, ctr)
        ts = jnp.stack(
            [sample_logits(logits[:, i], jax.random.fold_in(key, i),
                           temps, self.top_k) for i in range(span)],
            axis=1)
        match = (proposals == ts[:, :self.draft_k]).astype(jnp.int32)
        # cumprod/sum promote int32 -> int64 under x64 mode; the pos
        # vector's dtype is part of every program's signature, so pin
        # it or the second round re-compiles everything downstream
        accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        counts = (accepted + 1).astype(jnp.int32)
        kv = _cache.unzip_layer_caches(new_caches) + (
            (pos + counts).astype(jnp.int32),)
        return kv, ts, counts

    # -- scheduler primitives -------------------------------------------------

    def bucket_for(self, prompt_len) -> int:
        """Smallest prefill bucket covering ``prompt_len``."""
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return int(b)
        raise InvalidArgumentError(
            f"prompt of {prompt_len} tokens exceeds the largest prefill "
            f"bucket {self.prefill_buckets[-1]}; raise "
            "FLAGS_generation_prefill_buckets or truncate")

    def validate(self, prompt, max_new_tokens) -> int:
        """Admission checks shared by offline generate and the serving
        scheduler. Returns the prompt length."""
        n = len(prompt)
        if n < 1:
            raise InvalidArgumentError("generation needs a non-empty prompt")
        self.bucket_for(n)  # raises if no bucket covers it
        if max_new_tokens < 1:
            raise InvalidArgumentError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        total = n + int(max_new_tokens)
        if total > self.max_positions:
            raise InvalidArgumentError(
                f"prompt ({n}) + max_new_tokens ({max_new_tokens}) = "
                f"{total} exceeds the model's max_position_embeddings "
                f"{self.max_positions}")
        return n

    def _padded_prompt(self, prompt):
        n = len(prompt)
        bucket = self.bucket_for(n)
        padded = np.full(bucket, self.pad_id, np.int32)
        padded[:n] = np.asarray(prompt, np.int32)
        return padded, n

    def _next_key_step(self) -> int:
        """Bump the sampling-key counter under its lock and return the
        snapshot. Every dispatch site uses the RETURNED value — re-reading
        ``self._key_step`` after an unlocked ``+=`` is the race graphlint's
        ``unlocked-shared-mutation`` rule exists for (two threads sampling
        with the same key)."""
        with self._key_lock:
            self._key_step += 1
            return self._key_step

    def admit(self, slot, prompt, temperature=None, tenant=None) -> int:
        """Prefill ``prompt`` into ``slot`` and return the first sampled
        token. The slot's previous occupant is simply overwritten — a
        vacated slot needs no reset pass (ring), or its pages are
        reclaimed first (paged). Speculative engines prefill the draft
        ring in the same program. ``tenant`` labels the paged layout's
        prefix-reuse observability; the ring layout ignores it."""
        if self.paged:
            return self._admit_paged(slot, prompt, temperature, tenant)
        adm = self.begin_admission(slot, prompt, temperature)
        if adm is not None:
            # the same chunks back to back: nothing to put between them
            while not adm.done:
                self.enqueue_chunk(adm)
            return self.fetch_admission(adm)
        padded, n = self._padded_prompt(prompt)
        temp = (self.default_temperature if temperature is None
                else float(temperature))
        ctr = self._next_key_step()
        t0 = time.perf_counter_ns()
        with RecordEvent("generation::prefill"):
            out = self._dispatch(
                *self._prefill_call(slot, padded, n, temp, ctr))
            if self.speculative:
                self._kv, self._kv_draft, tok = out
            elif self._kinds is not None:
                self._kv, tok, stats = out
            else:
                self._kv, tok = out
        _record_counter(CHUNKS_COUNTER, [1, 1])
        tok = self._fetched("generation::prefill", t0, tok, int)
        if self._kinds is not None:
            with self._key_lock:  # a step's += on another thread
                self._pos_host[slot] = n
            self._sample_stats(stats)
        return tok

    def begin_admission(self, slot, prompt, temperature=None):
        """Begin admitting ``prompt`` into ``slot`` a chunk at a time:
        an :class:`Admission` to hand to :meth:`enqueue_chunk` until it
        is ``done`` and then to :meth:`fetch_admission`, or ``None``
        where this prompt goes in whole (:meth:`admit`): the cache has a
        kind that cannot take a prompt up again (:attr:`chunk_len` is
        None), or one chunk holds it. One prompt is in its chunks at a
        time, and between two of its chunks at most ONE decode step may
        run: the row that a step leaves in the half-filled slot is
        rewritten by the next chunk in a full ring and lies outside
        every later query's band in a window ring, but a second step's
        row would take the place of position ``lo - window + 1``, which
        the next chunk's first query attends. After the last chunk none
        may run until the token is fetched: the slot is live from there
        and a step would decode it from no token
        (:meth:`enqueue_step` refuses both)."""
        if self.chunk_len is None or len(prompt) <= self.chunk_len:
            return None
        if self._admission is not None:
            raise PreconditionNotMetError(
                f"slot {self._admission.slot} is in its chunks: one prompt "
                "at a time")
        self._admission = Admission(
            slot, prompt, self.default_temperature if temperature is None
            else temperature)
        return self._admission

    def _own(self, adm):
        if adm is not self._admission:
            raise PreconditionNotMetError(
                "this admission was abandoned, or lost its rows with the "
                "cache")

    def enqueue_chunk(self, adm):
        """Enqueue the next chunk of ``adm`` and return at once, the
        program left to run behind whatever is enqueued: the span
        ``generation::prefill``, and one sample of
        ``generation::prefill_chunks``, ``[the chunk's number in its
        prompt, 1 if it is the last]``. After the last one ``adm.done``
        is true and :meth:`fetch_admission` has the first token."""
        self._own(adm)
        n = adm.next_len(self.chunk_len)
        last = adm.lo + n == len(adm.prompt)
        ctr = self._next_key_step() if last else 0
        t0 = time.perf_counter_ns()
        with RecordEvent("generation::prefill"):
            self._kv, adm.tok, stats = self._dispatch(
                *self._chunk_call(adm, ctr))
        adm.program = self._program
        adm.lo += n
        adm.chunks += 1
        adm.steps = 0
        adm.stats.append(stats)
        _record_counter(CHUNKS_COUNTER, [adm.chunks, int(last)])
        with self._key_lock:  # a step's += on another thread
            self._pos_host[adm.slot] = adm.lo
        self._note_phase("generation::prefill", t0,
                         time.perf_counter_ns() - t0)

    def fetch_admission(self, adm) -> int:
        """The first sampled token of a prompt whose last chunk is
        enqueued, on the host: the wait is the span
        ``generation::prefill_fetch``, as :meth:`admit`'s is. A driver
        with a decode step in flight fetches that first: its tokens are
        ready a chunk earlier."""
        self._own(adm)
        if not adm.done:
            raise PreconditionNotMetError(
                f"{len(adm.prompt) - adm.lo} prompt tokens are not in yet")
        self._admission = None
        tok = self._fetched("generation::prefill", None, adm.tok, int,
                            adm.program)
        stats = adm.stats[-1]
        if _profiler_enabled() and stats is not None:
            # the prompt's load is its chunks' sum, one sample as ever
            loads = [s["load"] for s in jax.device_get(adm.stats)]
            stats = {"load": np.sum(loads, axis=0)}
        self._sample_stats(stats, program=adm.program)
        return tok

    def abandon_admission(self, adm):
        """Give up a prompt between two of its chunks: its slot keeps
        the rows it got, as a vacated slot does, until the next
        admission writes over them."""
        if adm is self._admission:
            self._admission = None

    def _sample_stats(self, stats, rows_read=None, rows_fetched=None,
                      program=None, sparse=None):
        """While the profiler is on, fetch the routing statistics a
        program returned and put them on its timeline as counter
        samples: ``moe::expert_load`` (per held expert, prompt and
        decode alike), and for a decode step ``moe::pairs_here`` and
        ``moe::experts_hit`` (one value an expert layer; where the model
        has zero-compute experts, ``moe::zero_pairs`` beside them, and
        where the experts' kernel ran, ``moe::tile_rows``) with
        ``generation::state_bytes`` (what the state layers' leaves
        hold, of :meth:`cache_nbytes`), ``generation::kv_rows_read`` and
        ``generation::kv_rows_fetched`` (``rows_read``, ``rows_fetched``:
        :meth:`kv_rows_read` and :meth:`kv_rows_fetched` as the step was
        enqueued; ``None`` for a prompt), and where layers choose their
        blocks ``sparse::blocks_read``, ``sparse::blocks_live`` and
        ``sparse::slots_dense`` (``sparse``: :meth:`sparse_blocks`). The
        whole of it is the span
        ``generation::stats_fetch``. Off, the arrays are dropped where
        they lie: no transfer, one boolean."""
        if not _profiler_enabled():
            return
        t0 = time.perf_counter_ns()
        prefill = rows_read is None
        if stats is not None:
            stats = jax.device_get(stats)
            _record_counter("moe::expert_load", stats["load"].tolist())
            if not prefill:
                _record_counter("moe::pairs_here", stats["pairs"].tolist())
                _record_counter("moe::experts_hit", stats["hit"].tolist())
                if "zero_pairs" in stats:
                    _record_counter("moe::zero_pairs",
                                    stats["zero_pairs"].tolist())
                if "tile_rows" in stats:
                    _record_counter("moe::tile_rows",
                                    stats["tile_rows"].tolist())
        if not prefill:
            _record_counter("generation::state_bytes", self.state_nbytes())
            _record_counter("generation::kv_rows_read", list(rows_read))
            _record_counter("generation::kv_rows_fetched",
                            list(rows_fetched))
            if sparse is not None:
                for name, value in zip(("blocks_read", "blocks_live",
                                        "slots_dense"), sparse):
                    _record_counter("sparse::" + name, value)
        # a sibling of the fetch spans: the transfer is the loop
        # thread's time (3-4 ms an iteration on the chip), and only
        # spent while the profiler is on
        t1 = time.perf_counter_ns()
        _add_span("generation::stats_fetch", t0, t1)
        self._note_phase("generation::stats_fetch", t0, t1 - t0, program)

    def state_nbytes(self) -> int:
        """Device bytes of the state layers' leaves (all slots): the
        part of :meth:`cache_nbytes` that does not grow with
        ``cache_len``."""
        return sum(_cache.cache_nbytes(arrays)
                   for kind, arrays in zip(self._kinds or (), self._kv)
                   if isinstance(kind, _cache.StateKind))

    @staticmethod
    def _kind_place(kind):
        """Where a kind counts in :meth:`cache_bytes_by_kind` (and, less
        the state's place, in :meth:`kv_rows_read`): 0 a full-length K/V
        ring, 1 a window ring, 2 a state, 3 a latent ring."""
        if isinstance(kind, _cache.StateKind):
            return 2
        if isinstance(kind, _cache.LatentKind):
            return 3
        return 0 if kind.window is None else 1

    def cache_bytes_by_kind(self):
        """``(full-length K/V rings, window rings, state, latent
        rings)``: the device bytes of :meth:`cache_nbytes` by what a
        layer keeps (``pos`` aside)."""
        out = [0, 0, 0, 0]
        for kind, arrays in zip(self._kinds or (), self._kv):
            out[self._kind_place(kind)] += _cache.cache_nbytes(arrays)
        return tuple(out)

    def kv_rows_read(self):
        """``(full-length K/V layers, window layers, latent layers)``:
        the ring rows a decode step at the host's copy of ``pos`` has to
        read, summed over slots and layers: what each layer's step names
        (``kind.rows_read``), which is ``min(pos + 1, ring)`` a slot and
        layer, the row the step writes included, and for a layer that
        chooses its blocks the chosen blocks' rows and the pooled rows
        it scores (first place). A vacant slot counts with the position
        it was left at: the step computes it too."""
        return self._ring_rows(lambda kind, live: kind.rows_read(live))

    def kv_rows_fetched(self):
        """:meth:`kv_rows_read`'s three places, holding the ring rows
        that step's attention brings from HBM
        (``kind.rows_fetched``): the whole ring a slot and layer where
        XLA reads it, the live rows rounded up to whole key blocks where
        the latent decode kernel runs, the blocks a sparse layer's
        gather takes and its pooled ring."""
        return self._ring_rows(lambda kind, live: kind.rows_fetched(
            live, self.store_len, self.kv_cache_dtype))

    def sparse_blocks(self):
        """``(blocks read, blocks live, slots under dense_len)`` of a
        decode step at the host's copy of ``pos``: the blocks the layers
        that choose theirs attend and the blocks that hold a live row,
        each summed over slots and such layers (the count read is a
        function of the length alone), and the slots that still attend
        everything. ``None`` where no layer chooses."""
        kinds = [k for k in self._kinds or ()
                 if isinstance(k, _cache.SparseKVKind)]
        if not kinds:
            return None
        with self._key_lock:
            pos = self._pos_host.copy()
        live = [np.minimum(pos + 1, k.ring(self.store_len)) for k in kinds]
        return (int(sum(k.blocks_read(n).sum() for k, n in zip(kinds, live))),
                int(sum(k.blocks_live(n).sum() for k, n in zip(kinds, live))),
                int((live[0] < kinds[0].sparse.dense_len).sum()))

    def _ring_rows(self, rows):
        """``rows(kind, live [S])`` summed over slots and ring layers by
        :meth:`_kind_place`, ``live`` the rows of a layer's ring that
        the host's copy of ``pos`` makes live a slot."""
        out = [0, 0, 0, 0]
        with self._key_lock:
            pos = self._pos_host.copy()
        for kind in self._kinds or ():
            ring = kind.ring(self.store_len)
            if ring is not None:
                out[self._kind_place(kind)] += int(
                    rows(kind, np.minimum(pos + 1, ring)).sum())
        return out[0], out[1], out[3]

    # Each ring program's (label, jitted, make_args): what its entry
    # point hands to _dispatch, and warm-up to _precompile. make_args
    # reads the cache when it is called, never before.

    @staticmethod
    def _prompt_args(padded, n, temp, ctr):
        return (padded[None], np.int32(n), np.float32(temp), np.int32(ctr))

    def _prefill_call(self, slot, padded, n, temp, ctr):
        if self.speculative:
            return "prefill", self._spec_prefill_jit, lambda: (
                self._state(), self._draft_state(), self._kv,
                self._kv_draft, np.int32(slot),
                *self._prompt_args(padded, n, temp, ctr))
        return "prefill", self._prefill_jit, lambda: (
            self._state(), self._kv, np.int32(slot),
            *self._prompt_args(padded, n, temp, ctr))

    def _chunk_call(self, adm, ctr):
        """The next chunk of ``adm``: its tokens right-padded to the
        chunk's length, where it begins and how many are real."""
        c, lo = self.chunk_len, adm.lo
        n = adm.next_len(c)
        padded = np.full(c, self.pad_id, np.int32)
        padded[:n] = np.asarray(adm.prompt[lo:lo + n], np.int32)
        return "prefill_chunk", self._prefill_chunk_jit, lambda: (
            self._state(), self._kv, np.int32(adm.slot), padded[None],
            np.int32(lo), np.int32(n), np.float32(adm.temp), np.int32(ctr))

    def _export_call(self, padded, n, temp, ctr):
        return "prefill", self._prefill_export_jit, lambda: (
            self._state(), *self._prompt_args(padded, n, temp, ctr))

    def _draft_prefill_call(self, slot, padded, n):
        return "prefill", self._draft_prefill_jit, lambda: (
            self._draft_state(), self._kv_draft,
            np.int32(slot), padded[None], np.int32(n))

    def _decode_call(self, tokens, temps, ctr):
        jitted = self._paged_decode_jit if self.paged else self._decode_jit
        tokens = (tokens.tokens if isinstance(tokens, DecodeStep)
                  else np.asarray(tokens, np.int32))
        return "decode", jitted, lambda: (
            self._state(), self._kv, tokens,
            np.asarray(temps, np.float32), np.int32(ctr))

    def _draft_call(self, toks):
        # the draft shares the target's position vector (reset())
        return "draft", self._draft_jit, lambda: (
            self._draft_state(), self._kv_draft, self._kv[-1], toks)

    def _verify_call(self, toks, proposals, temps, ctr):
        return "verify", self._verify_jit, lambda: (
            self._state(), self._kv, toks, proposals,
            np.asarray(temps, np.float32), np.int32(ctr))

    # -- paged layout: host-side page management ------------------------------
    #
    # All of this runs BETWEEN compiled steps on the engine's single
    # dispatch thread: page allocation, refcounts, CoW, and the prefix
    # index are plain host bookkeeping; the device pytree keeps its
    # fixed shapes, so no path here can add a compile.

    def _sync_table(self):
        """Push the host page-table mirror into the device pytree."""
        self._kv = self._kv[:-2] + (
            jnp.asarray(self._table_host), self._kv[-1])

    def _copy_page(self, src, dst):
        """Device-copy one pool page (all layers, values + scales) —
        the copy half of copy-on-write."""
        self._kv = tuple(
            a.at[:, dst].set(a[:, src]) for a in self._kv[:-2]
        ) + self._kv[-2:]

    def _alloc_pages(self, need):
        """``need`` private pages off the free list, evicting LRU
        index-only prefix pages when the list runs dry. Raises
        :class:`paging.PagePoolExhaustedError` (slots keep their pages;
        nothing was handed out) when the pool genuinely cannot supply."""
        need = int(need)
        if need > self._pool.free_pages():
            self._index.evict(need - self._pool.free_pages())
        if need > self._pool.free_pages():
            raise _paging.PagePoolExhaustedError(
                f"page pool exhausted: need {need} pages, "
                f"{self._pool.free_pages()} free and nothing evictable "
                f"(pool {self._pool.pages} pages x {self.page_size} "
                "tokens; raise FLAGS_generation_kv_pool_pages or lower "
                "concurrency)")
        return [self._pool.alloc() for _ in range(need)]

    def release_slot(self, slot):
        """Reclaim a vacated slot's pages: drop the slot's reference on
        every mapped page (pages the prefix index also holds survive as
        shared prefix cache; private ones return to the free list) and
        point the table row back at the trash page. No-op on the ring
        layout — ring slots are simply overwritten."""
        if not self.paged:
            return
        slot = int(slot)
        row = self._table_host[slot]
        if not self._slot_live[slot] and not row.any():
            return
        for pid in row:
            if int(pid) != _paging.TRASH_PAGE:
                self._pool.release(int(pid))
        self._table_host[slot] = _paging.TRASH_PAGE
        self._slot_live[slot] = False
        self._pos_host[slot] = 0
        self._sync_table()
        self._pool_gauges()

    def _cap_matched(self, n, m):
        """Cap a prefix match so the suffix's ladder bucket fits the
        window without wrapping into the shared pages (the suffix
        prefill writes ``bucket`` entries starting at ``m * ps``)."""
        while m:
            bucket = self.bucket_for(n - m * self.page_size)
            if m * self.page_size + bucket <= self.cache_len:
                break
            m -= 1
        return m

    def has_capacity(self, prompt_or_length) -> bool:
        """Would :meth:`admit` find pages for this prompt right now?
        Counts free + evictable pages against the pages the prompt
        needs beyond its indexed prefix — the admission gate
        ``serving/continuous.py`` consults INSTEAD of assuming a vacant
        slot implies capacity (pool free pages, not fixed slots)."""
        if not self.paged:
            return True
        ps = self.page_size
        if isinstance(prompt_or_length, int):
            n, m = int(prompt_or_length), 0
        else:
            prompt = list(prompt_or_length)
            n = len(prompt)
            m = self._cap_matched(n, len(self._index.known(
                _paging.chain_hashes(prompt, ps)[:(n - 1) // ps]))) \
                if self._prefix_enabled else 0
        need = -(-n // ps) - m
        return (self._pool.free_pages() + self._index.evictable()
                >= need)

    def _admit_paged(self, slot, prompt, temperature, tenant):
        """Paged admission: map the longest indexed prefix (full pages
        only, capped so the suffix keeps >= 1 real token and its bucket
        cannot wrap), allocate private pages for the rest, register the
        prompt's full pages in the index, and dispatch the unified
        full/suffix prefill program for the suffix's ladder bucket. The
        page bookkeeping is host work of the admission and lies inside
        its ``generation::prefill`` span, so that the loop thread's
        phases cover it (tests/test_host_timeline.py)."""
        with RecordEvent("generation::prefill"):
            slot, n, shared_len, suffix, padded = self._seat_paged(
                slot, prompt, tenant)
            temp = (self.default_temperature if temperature is None
                    else float(temperature))
            ctr = self._next_key_step()
            t0 = time.perf_counter_ns()
            out = self._dispatch(
                "prefill", self._paged_prefill_jit, lambda: (
                    self._state(), self._kv, np.int32(slot), padded[None],
                    np.int32(shared_len), np.int32(len(suffix)),
                    np.int32(n), np.float32(temp), np.int32(ctr)))
        self._kv, tok = out
        _record_counter(CHUNKS_COUNTER, [1, 1])
        return self._fetched("generation::prefill", t0, tok, int)

    def _seat_paged(self, slot, prompt, tenant):
        """The host side of a paged admission: ``(slot, prompt length,
        shared prefix length, suffix tokens, the suffix padded to its
        bucket)`` with the slot's page-table row written."""
        slot = int(slot)
        n = self.validate(prompt, 1)
        ps = self.page_size
        self.release_slot(slot)
        hashes = _paging.chain_hashes(prompt, ps)
        matched = []
        if self._prefix_enabled:
            # cap at floor((n-1)/ps): the suffix keeps >= 1 token, so
            # there is always a real logit position to sample from
            matched = self._index.match(hashes[:(n - 1) // ps])
            matched = matched[:self._cap_matched(n, len(matched))]
        m = len(matched)
        shared_len = m * ps
        suffix = list(prompt)[shared_len:]
        total_pages = -(-n // ps)
        # retain BEFORE allocating: _alloc_pages may evict ref==1 index
        # pages, and the matched pages are exactly that until retained
        for pid in matched:
            self._pool.retain(pid)
        try:
            new_pages = self._alloc_pages(total_pages - m)
        except _paging.PagePoolExhaustedError:
            for pid in matched:
                self._pool.release(pid)
            raise
        row = np.full(self._pages_per_slot, _paging.TRASH_PAGE, np.int32)
        row[:m] = matched
        row[m:total_pages] = new_pages
        self._table_host[slot] = row
        self._pos_host[slot] = n
        self._slot_live[slot] = True
        t = "default" if tenant is None else str(tenant)
        self._slot_tenant[slot] = t
        if self._prefix_enabled:
            self._index.insert(hashes[:n // ps],
                               [int(p) for p in row[:n // ps]])
        self._sync_table()
        self._note_prefix(t, n, shared_len, m)
        padded = np.full(self.bucket_for(len(suffix)), self.pad_id,
                         np.int32)
        padded[:len(suffix)] = np.asarray(suffix, np.int32)
        return slot, n, shared_len, suffix, padded

    def _note_prefix(self, tenant, prompt_tokens, shared_tokens,
                     matched_pages):
        """Per-tenant prefix-reuse accounting + the labeled gauges and
        the ``prefix_reuse`` flight event (PR 17 labeled families)."""
        from ..monitor import registry as _mon

        st = self._prefix_tenants.setdefault(
            tenant, {"lookups": 0, "hits": 0, "prompt_tokens": 0,
                     "shared_tokens": 0})
        st["lookups"] += 1
        st["prompt_tokens"] += int(prompt_tokens)
        if matched_pages:
            st["hits"] += 1
            st["shared_tokens"] += int(shared_tokens)
            _flight.record_event(
                "prefix_reuse", tenant=tenant,
                matched_tokens=int(shared_tokens),
                matched_pages=int(matched_pages),
                prompt_tokens=int(prompt_tokens))
        _mon.gauge("generation/prefix_hit_rate").labels(
            tenant=tenant).set(
            round(st["shared_tokens"] / st["prompt_tokens"], 4))
        tot_p = sum(s["prompt_tokens"]
                    for s in self._prefix_tenants.values())
        tot_s = sum(s["shared_tokens"]
                    for s in self._prefix_tenants.values())
        _mon.gauge("generation/prefix_hit_rate").set(
            round(tot_s / tot_p, 4) if tot_p else 0.0)
        self._pool_gauges()

    def _pool_gauges(self):
        """Pool occupancy gauges: global free/shared, plus per-tenant
        shared-page children (pages a tenant's live slots map at
        refcount > 1 — its CoW exposure)."""
        from ..monitor import registry as _mon

        _mon.gauge("generation/pages_free").set(self._pool.free_pages())
        _mon.gauge("generation/pages_shared").set(
            self._pool.shared_pages())
        per = {}
        for s, live in enumerate(self._slot_live):
            if not live:
                continue
            t = self._slot_tenant[s]
            per[t] = per.get(t, 0) + sum(
                1 for pid in self._table_host[s]
                if int(pid) != _paging.TRASH_PAGE
                and self._pool.ref[int(pid)] > 1)
        for t in self._prefix_tenants:
            _mon.gauge("generation/pages_shared").labels(
                tenant=t).set(per.get(t, 0))
        for t, n in per.items():
            if t not in self._prefix_tenants:
                _mon.gauge("generation/pages_shared").labels(
                    tenant=t).set(n)

    def _prepare_decode_writes(self):
        """Make every busy slot's next ring write safe BEFORE the
        compiled step runs: the write lands at logical page ``(pos %
        window) // ps`` — if that table entry is still the trash page
        (first visit), allocate; if the mapped page is shared (prefix
        pages after the ring wraps back into them, or pages the index
        retains), COPY it private first (copy-on-write) so the write
        cannot corrupt another slot's — or the index's — view."""
        changed = False
        for s, live in enumerate(self._slot_live):
            if not live:
                continue
            idx = int(self._pos_host[s]) % self.cache_len
            lp = idx // self.page_size
            pid = int(self._table_host[s, lp])
            if pid == _paging.TRASH_PAGE:
                (new,) = self._alloc_pages(1)
                self._table_host[s, lp] = new
                changed = True
            elif self._pool.ref[pid] > 1:
                try:
                    (new,) = self._alloc_pages(1)
                except _paging.PagePoolExhaustedError:
                    # pressure valve: stop caching this chain — forget
                    # the page's subtree so the index's pin drops. If
                    # the page is now private to this slot, write in
                    # place; if another live slot still shares it, the
                    # forget freed enough refs that a copy page exists.
                    self._index.forget_page(pid)
                    if self._pool.ref[pid] == 1:
                        continue
                    (new,) = self._alloc_pages(1)
                self._copy_page(pid, new)
                self._pool.release(pid)
                self._table_host[s, lp] = new
                self._pool.cow_copies += 1
                changed = True
        if changed:
            self._sync_table()
            self._pool_gauges()

    def paging_stats(self) -> dict:
        """The /statz paging block: layout + pool occupancy + prefix-
        index accounting (global and per tenant)."""
        if not self.paged:
            return {"layout": self.kv_cache_layout}
        per = {}
        for t, st in self._prefix_tenants.items():
            per[t] = dict(st, hit_rate=round(
                st["shared_tokens"] / st["prompt_tokens"], 4)
                if st["prompt_tokens"] else None)
        return {
            "layout": self.kv_cache_layout,
            "page_size": self.page_size,
            "pages_per_slot": self._pages_per_slot,
            "pages_total": self._pool.pages,
            "pages_free": self._pool.free_pages(),
            "pages_used": self._pool.used_pages(),
            "pages_shared": self._pool.shared_pages(),
            "peak_pages_used": self._pool.peak_used,
            "cow_copies": self._pool.cow_copies,
            "page_nbytes": self.page_nbytes(),
            "prefix_index": self._index.stats(),
            "per_tenant": per,
        }

    def known_page_hashes(self, hashes):
        """The prefix of ``hashes`` this engine's index already holds —
        a prefill tier (or router) asks before shipping a page-granular
        slab so the wire carries only pages this tier is missing."""
        if not self.paged:
            return set()
        return self._index.known(list(hashes))

    def prefill_export_pages(self, prompt, temperature=None,
                             known_hashes=()):
        """Page-granular :meth:`prefill_export`: runs the same bucketed
        forward, then splits the slab into pages with chain hashes.
        Returns ``(pages, length, first_token)`` where ``pages`` is a
        list of ``{"id", "hash", "planes"}`` dicts — full pages carry
        their chain hash (``hash=None`` for the partial tail), and a
        page whose hash is in ``known_hashes`` ships header-only
        (``planes=None``): the decode tier maps it from its own prefix
        index instead of the wire."""
        planes, n, tok = self.prefill_export(prompt, temperature)
        ps = self.page_size
        per_page = _paging.split_planes(planes, ps)
        hashes = _paging.chain_hashes(prompt, ps)
        known = set(known_hashes)
        pages = []
        for i in range(-(-n // ps)):
            h = hashes[i] if i < len(hashes) else None
            pages.append({
                "id": i, "hash": h,
                "planes": None if (h is not None and h in known)
                else per_page[i]})
        return pages, n, int(tok)

    def admit_prefilled_pages(self, slot, pages, length, first_token,
                              page_size=None, tenant=None) -> int:
        """Land a page-granular handoff in decode slot ``slot``: pages
        shipped on the wire are installed into freshly allocated pool
        pages; header-only pages (``planes is None``) must resolve
        through this engine's own prefix index (the sender asked
        :meth:`known_page_hashes` first) and are mapped copy-on-write —
        refcounted exactly like a local prefix hit. Full shipped pages
        with hashes register in the index, so this decode tier becomes
        a prefix-cache peer for the whole fleet."""
        from .handoff import HandoffError

        self._refuse_for_kinds("admit_prefilled_pages")
        if not self.paged:
            raise InvalidArgumentError(
                "page-granular handoff needs kv_cache_layout=paged on "
                "the decode tier (ring tiers speak the slab format)")
        slot = int(slot)
        length = int(length)
        ps = self.page_size
        if page_size is not None and int(page_size) != ps:
            raise HandoffError(
                f"page-granular slab page_size {page_size} does not "
                f"match this engine's {ps}")
        if not 1 <= length <= self.cache_len:
            raise InvalidArgumentError(
                f"handoff length {length} outside [1, {self.cache_len}]")
        npages = -(-length // ps)
        if len(pages) != npages:
            raise HandoffError(
                f"page-granular slab carries {len(pages)} pages; "
                f"length {length} at page size {ps} needs {npages}")
        arity = len(self._kv) - 2
        # resolve absent pages through the index FIRST — nothing is
        # allocated or mutated until the whole slab is provably landable
        hashes = [p.get("hash") for p in pages]
        full = length // ps
        chain = []  # the contiguous hashed prefix — chain hashes only
        for h in hashes[:full]:  # resolve through a prefix walk
            if h is None:
                break
            chain.append(h)
        plan = []
        for i, page in enumerate(pages):
            planes = page.get("planes")
            if planes is None:
                plan.append(("map", i))
            else:
                if len(planes) != arity:
                    raise HandoffError(
                        f"page {i} carries {len(planes)} planes, this "
                        f"engine's {self.kv_cache_dtype} cache needs "
                        f"{arity}")
                for p in planes:
                    if int(p.shape[2]) != ps:
                        raise HandoffError(
                            f"page {i} plane cache axis "
                            f"{tuple(p.shape)} does not match page "
                            f"size {ps}")
                plan.append(("ship", i))
        mapped = self._index.match(chain)
        for kind, i in plan:
            if kind == "map" and i >= len(mapped):
                raise HandoffError(
                    f"page {i} shipped header-only but this tier does "
                    "not hold its hash chain; the sender must ship the "
                    "payload")
        self.release_slot(slot)
        # retain mapped pages BEFORE allocating (allocation may evict
        # ref==1 index pages), then allocate the shipped set atomically
        map_ids = [mapped[i] for k, i in plan if k == "map"]
        for pid in map_ids:
            self._pool.retain(pid)
        try:
            fresh = self._alloc_pages(
                sum(1 for k, _ in plan if k == "ship"))
        except _paging.PagePoolExhaustedError:
            for pid in map_ids:
                self._pool.release(pid)
            raise
        row = np.full(self._pages_per_slot, _paging.TRASH_PAGE, np.int32)
        ship_ids, ship_planes = [], []
        it = iter(fresh)
        for kind, i in plan:
            if kind == "map":
                row[i] = mapped[i]
            else:
                pid = next(it)
                row[i] = pid
                ship_ids.append(pid)
                ship_planes.append(pages[i]["planes"])
        if ship_ids:
            ids = jnp.asarray(np.asarray(ship_ids, np.int32))
            for j in range(arity):
                stack = jnp.asarray(np.stack(
                    [np.asarray(pl[j]) for pl in ship_planes], axis=1))
                self._kv = self._kv[:j] + (
                    self._kv[j].at[:, ids].set(stack),
                ) + self._kv[j + 1:]
        self._table_host[slot] = row
        self._pos_host[slot] = length
        self._slot_live[slot] = True
        t = "default" if tenant is None else str(tenant)
        self._slot_tenant[slot] = t
        if self._prefix_enabled and full and all(
                h is not None for h in hashes[:full]):
            self._index.insert(hashes[:full],
                               [int(p) for p in row[:full]])
        self._sync_table()
        self._kv = self._kv[:-1] + (
            self._kv[-1].at[slot].set(length),)
        shared = sum(1 for kind, i in plan
                     if kind == "map" and i < len(mapped))
        self._note_prefix(t, length, shared * ps, shared)
        return int(first_token)

    def prefill_export(self, prompt, temperature=None):
        """Prefill-tier primitive: run the bucketed forward and return
        ``(planes, length, first_token)`` — the window-width per-slot
        KV planes (``[L, H, C, D]`` values, ``[L, H, C]`` scales at
        int8), the true prompt length, and the first sampled token.
        The slab ships to a decode tier (:mod:`generation.handoff`)
        whose :meth:`admit_prefilled` lands it in a free slot."""
        self._refuse_for_kinds("prefill_export")
        padded, n = self._padded_prompt(prompt)
        temp = (self.default_temperature if temperature is None
                else float(temperature))
        ctr = self._next_key_step()
        t0 = time.perf_counter_ns()
        with RecordEvent("generation::prefill_export"):
            planes, tok = self._dispatch(
                *self._export_call(padded, n, temp, ctr))
        self._note_phase("generation::prefill_export", t0,
                         time.perf_counter_ns() - t0)
        return planes, n, int(tok)

    def _admit_draft(self, slot, prompt):
        """Draft-only prefill of ``prompt`` into draft slot ``slot`` —
        the decode-tier half of a speculative handoff admission."""
        padded, n = self._padded_prompt(prompt)
        t0 = time.perf_counter_ns()
        with RecordEvent("generation::draft_prefill"):
            self._kv_draft = self._dispatch(
                *self._draft_prefill_call(slot, padded, n))
        self._note_phase("generation::draft_prefill", t0,
                         time.perf_counter_ns() - t0)

    def admit_prefilled(self, slot, planes, length, first_token,
                        prompt=None) -> int:
        """Land a handed-off KV slab in decode slot ``slot``: pad the
        window-width planes up to the ring store and commit them with
        the same functional indexed update admission always uses. The
        first token was already sampled by the prefill tier; it is
        returned unchanged for scheduler uniformity. A speculative
        engine additionally needs the PROMPT tokens (the slab is
        target-only) to build the draft's view via a draft prefill."""
        self._refuse_for_kinds("admit_prefilled")
        length = int(length)
        if not 1 <= length <= self.cache_len:
            raise InvalidArgumentError(
                f"handoff length {length} outside [1, {self.cache_len}]")
        if self.paged:
            # a v1 (contiguous) slab lands on a paged tier by splitting
            # into anonymous pages — no hashes, so no cross-request
            # sharing, but the decode path is uniform
            arity = len(self._kv) - 2
            if len(planes) != arity:
                raise InvalidArgumentError(
                    f"handoff slab has {len(planes)} planes, this "
                    f"engine's {self.kv_cache_dtype} cache needs "
                    f"{arity} (kv_cache_dtype mismatch between tiers?)")
            per_page = _paging.split_planes(
                tuple(jnp.asarray(p) for p in planes), self.page_size)
            npages = -(-length // self.page_size)
            pages = [{"id": i, "hash": None, "planes": per_page[i]}
                     for i in range(npages)]
            return self.admit_prefilled_pages(
                slot, pages, length, first_token)
        arity = len(self._kv) - 1
        if len(planes) != arity:
            raise InvalidArgumentError(
                f"handoff slab has {len(planes)} planes, this engine's "
                f"{self.kv_cache_dtype} cache needs {arity} "
                "(kv_cache_dtype mismatch between tiers?)")
        padded = _cache.pad_slot_arrays(
            tuple(jnp.asarray(p) for p in planes), self.store_len)
        for plane, p in zip(self._kv[:-1], padded):
            a = plane[0]
            if tuple(p.shape) != (len(plane),) + tuple(a.shape[1:]) \
                    or p.dtype != a.dtype:
                raise InvalidArgumentError(
                    f"handoff slab plane {tuple(p.shape)}/{p.dtype} does "
                    f"not fit this engine's cache "
                    f"{len(plane)} x {tuple(a.shape)}/{a.dtype}")
        if self.speculative:
            if prompt is None:
                raise InvalidArgumentError(
                    "a speculative decode tier needs the prompt tokens "
                    "with the KV slab (the draft ring must be prefilled)")
            self._admit_draft(slot, prompt)
        with RecordEvent("generation::admit_prefilled"):
            # eager and undonated: each layer's array is copied once
            self._kv = _cache.insert_slot_kv(
                self._kv, slot, padded, length)
        return int(first_token)

    def enqueue_step(self, tokens, temps) -> DecodeStep:
        """The first half of :meth:`step`: enqueue one decode step for
        every slot and return without waiting for it. ``tokens`` is the
        host ``[S]`` int32 array, or the :class:`DecodeStep` before this
        one, whose tokens are then taken where they lie on the device:
        the same compiled program either way (one shape, one dtype, one
        store key), no transfer, and no wait for the host to see them.
        What the host keeps of a step is counted here, because counts
        are not data: the sampling counter is taken, the host's copy of
        ``pos`` advances. The returned handle keeps the step's tokens
        (and its routing statistics) on the device until
        :meth:`fetch_step`; a handle that is dropped costs nothing."""
        adm = self._admission
        if adm is not None:
            if adm.done:
                raise PreconditionNotMetError(
                    f"slot {adm.slot}'s prompt is in and its first token "
                    "is not fetched: a step would decode it from none")
            if adm.steps:
                raise PreconditionNotMetError(
                    f"a second decode step since slot {adm.slot}'s last "
                    "chunk would overwrite a window ring's row that its "
                    "next chunk attends: run the next chunk first")
            adm.steps += 1
        ctr = self._next_key_step()
        if self.paged:
            # CoW/first-visit page turns happen on the host BEFORE the
            # compiled step, so the jitted scatter only ever writes
            # pages private to their slot (or the trash page)
            self._prepare_decode_writes()
        t0 = time.perf_counter_ns()
        with RecordEvent("generation::decode"):
            out = self._dispatch(*self._decode_call(tokens, temps, ctr))
        stats = rows_read = rows_fetched = sparse = None
        if self._kinds is not None:
            self._kv, nxt, stats = out
            if _profiler_enabled():
                rows_read = self.kv_rows_read()  # before pos moves on
                rows_fetched = self.kv_rows_fetched()
                sparse = self.sparse_blocks()
            with self._key_lock:
                self._pos_host += 1
        else:
            self._kv, nxt = out
        if self.paged:
            for s, live in enumerate(self._slot_live):
                if live:
                    self._pos_host[s] += 1
        self._note_phase("generation::decode", t0,
                         time.perf_counter_ns() - t0)
        return DecodeStep(nxt, stats, rows_read, rows_fetched,
                          self._program, sparse)

    def fetch_step(self, step) -> np.ndarray:
        """The second half of :meth:`step`: the tokens of an enqueued
        step on the host, ``[S]`` int32. The wait is the span
        ``generation::decode_fetch``; the step's statistics are read
        here too, while the profiler is on."""
        nxt = self._fetched("generation::decode", None, step.tokens,
                            np.asarray, step.program)
        if self._kinds is not None:
            self._sample_stats(step.stats, step.rows_read,
                               step.rows_fetched, step.program, step.sparse)
        return nxt

    def step(self, tokens, temps) -> np.ndarray:
        """Decode one token for every slot: :meth:`enqueue_step` and
        :meth:`fetch_step` back to back. ``tokens``/``temps`` are
        host ``[S]`` arrays (vacant slots: anything — their output is
        ignored and their cache entries are overwritten on admission)."""
        return self.fetch_step(self.enqueue_step(tokens, temps))

    @property
    def steps_ahead(self) -> int:
        """How many decode steps a driver may enqueue beyond the one it
        has not fetched yet: 1 where the next step's inputs are known
        before the last step's tokens reach the host (ring layout, no
        draft model: the tokens pass from step to step on the device),
        else 0. A speculative round's accepted counts are data and
        decide the next round's tokens; a paged step turns pages on the
        host for the slots that are live, so a slot that ended must be
        known before the next step is enqueued. 0 also for an engine
        whose ``step`` is not this class's own (a subclass, a wrapper
        that alters or times whole steps): such a ``step`` must go on
        seeing every step, so its driver may not take the halves
        apart."""
        whole = getattr(self.step, "__func__", None) is _WHOLE_STEP
        return int(whole and not self.paged and not self.speculative)

    def spec_step(self, tokens, temps, busy=None):
        """One speculative round for every slot: draft program (k
        proposals per slot) then verify program (one batched target
        forward over all k+1 positions). Returns ``(emitted [S, k+1],
        counts [S])`` — slot ``s`` produced ``emitted[s, :counts[s]]``
        new tokens this round (the caller truncates at EOS/budget).
        ``busy`` (slot indices, or None for all) scopes the acceptance
        accounting to slots actually generating."""
        if not self.speculative:
            raise InvalidArgumentError(
                "spec_step needs a draft model; construct the engine "
                "with draft_model= (FLAGS_speculative_enabled)")
        toks = jnp.asarray(np.asarray(tokens, np.int32))
        t0 = time.perf_counter_ns()
        with RecordEvent("generation::draft"):
            self._kv_draft, proposals = self._dispatch(
                *self._draft_call(toks))
        ctr = self._next_key_step()
        with RecordEvent("generation::verify"):
            out = self._dispatch(
                *self._verify_call(toks, proposals, temps, ctr))
        self._kv, ts, counts = out
        # the round's two enqueues count as the decode phase; the wait
        # for both programs is the one fetch
        ts, counts = self._fetched(
            "generation::decode", t0, (ts, counts), jax.device_get)
        n_busy = self.slots if busy is None else len(busy)
        if n_busy:
            accepted = int(counts.sum() - self.slots if busy is None
                           else sum(int(counts[s]) - 1 for s in busy))
            with self._key_lock:
                self._spec_rounds += 1
                self._spec_proposed += self.draft_k * n_busy
                self._spec_accepted += accepted
            from ..monitor import counter as _mcounter

            _mcounter("generation/spec_rounds_total").inc()
            _mcounter("generation/spec_proposed_total").inc(
                self.draft_k * n_busy)
            _mcounter("generation/spec_accepted_total").inc(accepted)
        return ts, counts

    def spec_stats(self) -> dict:
        """Speculative acceptance accounting since the last reset/
        warmup: rounds, proposed/accepted draft tokens, acceptance
        rate (the /statz block)."""
        with self._key_lock:  # consistent snapshot vs a concurrent round
            rounds, proposed, accepted = (
                self._spec_rounds, self._spec_proposed, self._spec_accepted)
        return {
            "enabled": self.speculative,
            "draft_k": self.draft_k if self.speculative else 0,
            "rounds": rounds,
            "proposed": proposed,
            "accepted": accepted,
            "acceptance_rate": round(accepted / proposed, 4)
            if proposed else None,
        }

    # -- offline API ----------------------------------------------------------

    def generate(self, prompts, max_new_tokens=None, temperature=None,
                 stop_at_eos=True, continuous=True):
        """Generate for a list of prompts, continuous-batched across the
        engine's slots: a finished sequence vacates its slot and the next
        prompt is admitted at the next step. ``continuous=False`` is the
        static baseline (a new group is admitted only when EVERY slot has
        drained — what tearing the batch down costs). Returns one token
        list per prompt (EOS included when hit)."""
        max_new = (self.default_max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        for prompt in prompts:
            self.validate(prompt, max_new)
        pending = deque(enumerate(prompts))
        results = [None] * len(prompts)
        active = {}  # slot -> (prompt_idx, tokens list)
        last = np.zeros(self.slots, np.int32)
        temps = np.zeros(self.slots, np.float32)
        temp = (self.default_temperature if temperature is None
                else float(temperature))

        def finished(tokens):
            return (len(tokens) >= max_new
                    or (stop_at_eos and self.eos_id is not None
                        and tokens[-1] == self.eos_id))

        while pending or active:
            admit_ok = bool(pending) and (continuous or not active)
            while admit_ok and pending and len(active) < self.slots:
                slot = next(s for s in range(self.slots) if s not in active)
                idx, prompt = pending.popleft()
                tok = self.admit(slot, prompt, temp)
                temps[slot] = temp
                if finished([tok]):
                    results[idx] = [tok]
                    self.release_slot(slot)
                else:
                    active[slot] = (idx, [tok])
                    last[slot] = tok
            if not active:
                continue
            if self.speculative:
                ts, counts = self.spec_step(last, temps,
                                            busy=list(active))
                for slot in list(active):
                    idx, tokens = active[slot]
                    for i in range(int(counts[slot])):
                        tokens.append(int(ts[slot, i]))
                        last[slot] = ts[slot, i]
                        if finished(tokens):
                            break
                    if finished(tokens):
                        results[idx] = tokens
                        del active[slot]
                        self.release_slot(slot)
            else:
                nxt = self.step(last, temps)
                for slot in list(active):
                    idx, tokens = active[slot]
                    tokens.append(int(nxt[slot]))
                    last[slot] = nxt[slot]
                    if finished(tokens):
                        results[idx] = tokens
                        del active[slot]
                        self.release_slot(slot)
        return results


# GenerationEngine.step as the class defines it (steps_ahead)
_WHOLE_STEP = GenerationEngine.step
