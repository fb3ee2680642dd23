"""Paged KV subsystem (ISSUE 20): block-paged cache pool, radix prefix
index with copy-on-write reuse, page-granular handoff.

Pins the PR's production contracts:
- pool/refcount/free-list invariants and the radix index's
  match/insert/evict/forget semantics (pure host bookkeeping);
- greedy parity goldens: the paged layout's gather-through-page-table
  attention is TOKEN-IDENTICAL to the ring engine, fp32 and int8,
  including page-boundary wraparound and CoW-after-share;
- byte-exact capacity accounting: ``hbm_required_bytes`` equals the
  real allocated arrays in BOTH layouts, and ``suggest_decode_slots``
  divides by paged slot bytes (pages-in-flight x page_nbytes), not the
  ring's ``store_len x kv_bytes_per_token``;
- the page-granular handoff corrupt-reject table (truncated page list,
  duplicate ids, refcount overflow, hash-mismatched payload) — always
  ``HandoffError``, never a half-inserted slot;
- scheduler integration: pool-aware admission, page reclamation on
  slot release, per-tenant prefix observability, and the compile-once
  discipline (``extra_compiles() == 0`` under reuse traffic).
"""
import json
import struct
import zlib
from urllib.request import Request, urlopen

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, profiler
from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.generation import (
    COMPILE_COUNTER,
    GenerationEngine,
    HandoffError,
    PagePool,
    PagePoolExhaustedError,
    PageSlab,
    PrefixIndex,
    TRASH_PAGE,
    chain_hashes,
    pack_kv_pages,
    split_planes,
    unpack_kv_pages,
)
from paddle_tpu.generation import paging as paging_mod
from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config
from paddle_tpu.serving import ContinuousBatcher, GenerationServer

CACHE = 16
BUCKETS = (4, 8)
PS = 4  # tokens per page in most tests: 4 pages per slot


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _ring(model, slots=2, **kw):
    return GenerationEngine(model, slots=slots, cache_len=CACHE,
                            prefill_buckets=BUCKETS, seed=7, **kw)


def _paged(model, slots=2, page_size=PS, **kw):
    return GenerationEngine(model, slots=slots, cache_len=CACHE,
                            prefill_buckets=BUCKETS, seed=7,
                            kv_cache_layout="paged",
                            kv_page_size=page_size, **kw)


def _prompts(n, rng_seed=0, lo=1, hi=9):
    rng = np.random.RandomState(rng_seed)
    return [list(rng.randint(3, 200, size=int(rng.randint(lo, hi))))
            for _ in range(n)]


# -- pool + index bookkeeping (pure host) ------------------------------------

def test_page_pool_refcount_invariants():
    pool = PagePool(4, page_size=2)
    assert pool.free_pages() == 4 and pool.used_pages() == 0
    a, b = pool.alloc(), pool.alloc()
    assert a != TRASH_PAGE and b != TRASH_PAGE and a != b
    assert pool.free_pages() == 2 and pool.peak_used == 2
    pool.retain(a)
    assert pool.shared_pages() == 1
    assert pool.release(a) is False      # ref 2 -> 1: still held
    assert pool.release(a) is True       # ref 1 -> 0: back on free list
    assert pool.free_pages() == 3
    with pytest.raises(InvalidArgumentError):
        pool.release(a)                  # double free
    with pytest.raises(InvalidArgumentError):
        pool.retain(a)                   # retain of a free page
    with pytest.raises(InvalidArgumentError):
        pool.retain(TRASH_PAGE)
    c, d, e = pool.alloc(), pool.alloc(), pool.alloc()
    assert pool.alloc() is None          # exhausted: caller decides
    assert TRASH_PAGE not in {b, c, d, e}


def test_chain_hashes_prefix_property():
    toks = list(range(40, 60))
    h = chain_hashes(toks, 4)
    assert len(h) == 5 and all(len(x) == 32 for x in h)
    # chained: divergence in page 2 changes hash 2 and everything
    # after, but never the pages before it
    other = toks[:11] + [999] + toks[12:]
    h2 = chain_hashes(other, 4)
    assert h2[:2] == h[:2] and h2[2] != h[2] and h2[3] != h[3]
    assert chain_hashes(toks[:7], 4) == h[:1]  # partial tail not hashed


def test_prefix_index_match_insert_evict_forget():
    pool = PagePool(8, page_size=2)
    idx = PrefixIndex(pool)
    toks = list(range(12))
    hashes = chain_hashes(toks, 2)      # 6 full pages, one chain
    pages = [pool.alloc() for _ in range(6)]
    idx.insert(hashes, pages)           # index retains each page
    assert pool.free_pages() == 2 and idx.pages == 6
    assert idx.match(hashes[:3]) == pages[:3]
    assert idx.match(chain_hashes([99] + toks[1:], 2)) == []
    assert idx.known(hashes) == set(hashes)
    # slot drops its refs; pages become index-only -> the chain's leaf
    # is evictable, and eviction cascades leaf by leaf
    for p in pages:
        pool.release(p)
    assert idx.evictable() == 1
    assert idx.evict(2) == 2
    assert pool.free_pages() == 4 and idx.pages == 4
    # forget the chain's root: the whole remaining subtree goes too
    assert idx.forget_page(pages[0]) == 4
    assert pool.free_pages() == 8 and idx.pages == 0
    assert idx.match(hashes[:1]) == []
    assert idx.forget_page(pages[0]) == 0   # already gone: no-op


def test_split_planes_and_page_nbytes():
    k = np.arange(2 * 3 * 8 * 5, dtype=np.float32).reshape(2, 3, 8, 5)
    v = k + 1
    per = split_planes((k, v), 4)
    assert len(per) == 2 and len(per[0]) == 2
    np.testing.assert_array_equal(np.asarray(per[0][0]), k[:, :, :4])
    np.testing.assert_array_equal(np.asarray(per[1][1]), v[:, :, 4:])
    with pytest.raises(InvalidArgumentError):
        split_planes((k, v), 3)          # 8 % 3 != 0
    # ps x kv_bytes_per_token, fp32 and int8 (values + f32 scales)
    assert paging_mod.page_nbytes(2, 3, 5, 4, "float32") == \
        4 * (2 * 2 * 3 * 5 * 4)
    assert paging_mod.page_nbytes(2, 3, 5, 4, "int8") == \
        4 * (2 * 2 * 3 * (5 + 4))


# -- greedy parity goldens ----------------------------------------------------

def test_paged_parity_greedy_fp32(model):
    prompts = _prompts(6, rng_seed=2)
    want = _ring(model).warmup().generate(
        prompts, max_new_tokens=6, temperature=0.0)
    before = profiler.counters().get(COMPILE_COUNTER, 0)
    eng = _paged(model).warmup()
    got = eng.generate(prompts, max_new_tokens=6, temperature=0.0)
    assert got == want
    # one program a bucket and one decode, however much prefix is shared
    assert profiler.counters().get(COMPILE_COUNTER, 0) - before \
        == len(BUCKETS) + 1
    assert eng.extra_compiles() == 0
    # every slot vacated -> every non-index page reclaimed
    st = eng.paging_stats()
    assert st["pages_free"] + st["prefix_index"]["pages"] == \
        st["pages_total"]


def test_mixed_burst_on_a_pool_smaller_than_the_rings(model):
    """Four slots of rings reserve 16 pages; the same short/long burst
    runs token-identically from 12 (1.33 x the slots a byte): a short
    request holds only the pages it touches and idle prefix-index pages
    are evicted under pressure."""
    rng = np.random.RandomState(4)
    prompts = [list(map(int, rng.randint(3, 200, size=8 if i % 2 else 2)))
               for i in range(8)]
    want = _ring(model, slots=4).warmup().generate(
        prompts, max_new_tokens=6, temperature=0.0)
    eng = _paged(model, slots=4, kv_pool_pages=12).warmup()
    assert eng.generate(prompts, max_new_tokens=6, temperature=0.0) == want
    st = eng.paging_stats()
    assert st["pages_total"] == 12 and st["peak_pages_used"] <= 12
    assert eng.extra_compiles() == 0


def test_paged_parity_greedy_int8(model):
    prompts = _prompts(4, rng_seed=3)
    want = _ring(model, kv_cache_dtype="int8").warmup().generate(
        prompts, max_new_tokens=6, temperature=0.0)
    eng = _paged(model, kv_cache_dtype="int8").warmup()
    got = eng.generate(prompts, max_new_tokens=6, temperature=0.0)
    assert got == want
    assert eng.extra_compiles() == 0


def test_paged_parity_page_boundary_wraparound():
    """Decode far past the window: the logical ring wraps across page
    boundaries (and back into index-retained prefix pages, forcing
    copy-on-write or the forget-and-write-in-place pressure valve) yet
    stays token-identical to the ring engine."""
    paddle.seed(5)
    cfg = gpt_tiny_config()
    cfg.attention_window = 6
    m = GPTForCausalLM(cfg)
    m.eval()
    prompts = [[5, 9, 4], [7], [11, 2], [3, 4, 5, 6]]
    ring = GenerationEngine(m, slots=2, cache_len=6,
                            prefill_buckets=(4,), seed=2).warmup()
    want = ring.generate(prompts, max_new_tokens=12, temperature=0.0)
    eng = GenerationEngine(m, slots=2, cache_len=6, prefill_buckets=(4,),
                           seed=2, kv_cache_layout="paged",
                           kv_page_size=2).warmup()
    got = eng.generate(prompts, max_new_tokens=12, temperature=0.0)
    assert got == want
    assert eng.extra_compiles() == 0


def test_prefix_reuse_parity_and_observability(model):
    """Requests sharing a templated prefix map its pages instead of
    re-prefilling, stay token-identical to the ring engine, and leave
    the per-tenant gauges + ``prefix_reuse`` flight event behind."""
    from paddle_tpu.monitor import flight_recorder

    rng = np.random.RandomState(9)
    shared = list(rng.randint(3, 200, size=4))   # 1 full page at PS=4
    reqs = [shared + [t, t + 1, t + 2, t + 3] for t in (7, 19, 31)]
    want = _ring(model).warmup().generate(
        reqs, max_new_tokens=5, temperature=0.0, stop_at_eos=False)
    eng = _paged(model).warmup()
    got = []
    for i, r in enumerate(reqs):
        seq = [eng.admit(0, r, 0.0, tenant=f"t{i % 2}")]
        last = np.zeros(2, np.int32)
        temps = np.zeros(2, np.float32)
        last[0] = seq[0]
        for _ in range(4):
            nxt = eng.step(last, temps)
            seq.append(int(nxt[0]))
            last[0] = nxt[0]
        eng.release_slot(0)
        got.append(seq)
    assert got == want
    st = eng.paging_stats()
    assert st["prefix_index"]["hits"] == 2       # admits 2 and 3 matched
    assert st["per_tenant"]["t0"]["shared_tokens"] == 4
    assert st["per_tenant"]["t1"]["shared_tokens"] == 4
    evs = [e for e in flight_recorder.events()
           if e.get("kind") == "prefix_reuse"]
    assert len(evs) == 2
    assert all(e["matched_tokens"] == 4 and e["matched_pages"] == 1
               for e in evs)
    assert {e["tenant"] for e in evs} == {"t0", "t1"}
    assert monitor.gauge("generation/prefix_hit_rate").labels(
        tenant="t1").value > 0
    assert monitor.gauge("generation/pages_free").value == \
        st["pages_free"]
    assert eng.extra_compiles() == 0


# -- capacity accounting ------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_hbm_required_byte_exact_both_layouts(model, dtype):
    for eng in (_ring(model, kv_cache_dtype=dtype),
                _paged(model, kv_cache_dtype=dtype)):
        predicted = eng.hbm_required_bytes() - eng.param_nbytes()
        real = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in jax.tree_util.tree_leaves(eng._kv))
        assert predicted == real == eng.cache_nbytes(), \
            (eng.kv_cache_layout, dtype)


def test_suggest_decode_slots_paged_geometry(model):
    """Paged slot bytes = pages-in-flight x page_nbytes (+ table row +
    position word), NOT store_len x kv_bytes_per_token with a
    speculative margin — the satellite's accounting fix."""
    eng = _paged(model)
    pnb = eng.page_nbytes()
    per_slot = (CACHE // PS) * pnb + (CACHE // PS) * 4 + 4
    assert eng.slot_nbytes() == per_slot
    # budget for exactly 5.5 slots after weights + the trash page
    budget = eng.param_nbytes() + pnb + 5 * per_slot + per_slot // 2
    assert eng.suggest_decode_slots(budget) == 5
    assert _ring(model).slot_nbytes() == \
        CACHE * eng.kv_bytes_per_token() + 4


def test_strict_memplan_rejects_over_budget_pool(model):
    """An over-budget page pool must be refused at ENGINE CONSTRUCTION
    (before traffic), while the same budget admits a smaller pool."""
    from paddle_tpu.analysis import MemoryBudgetError
    from paddle_tpu.flags import set_flags

    probe = _paged(model, slots=2)
    need = probe.hbm_required_bytes(slots=8)
    try:
        set_flags({"device_peaks": f"hbm_bytes={need - 1}",
                   "memory_budget_check": "strict"})
        with pytest.raises(MemoryBudgetError):
            _paged(model, slots=8)
        assert _paged(model, slots=2).paged
    finally:
        set_flags({"memory_budget_check": "warn", "device_peaks": ""})


def test_paged_speculative_refused(model):
    paddle.seed(11)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    draft = GPTForCausalLM(cfg)
    draft.eval()
    with pytest.raises(InvalidArgumentError):
        _paged(model, draft_model=draft)


def test_pool_exhaustion_and_has_capacity(model):
    """Admission against a full pool with nothing evictable raises
    PagePoolExhaustedError and hands out NOTHING; releasing slots makes
    the same prompt admissible again through index eviction."""
    a, b, c = (list(range(10, 18)), list(range(30, 38)),
               list(range(60, 68)))
    eng = _paged(model, slots=3, kv_pool_pages=4).warmup()
    eng.admit(0, a, 0.0)
    eng.admit(1, b, 0.0)                 # pool full: 4 pages, all live
    free_before = eng.paging_stats()["pages_free"]
    assert not eng.has_capacity(c)
    with pytest.raises(PagePoolExhaustedError):
        eng.admit(2, c, 0.0)
    st = eng.paging_stats()
    assert st["pages_free"] == free_before   # nothing half-allocated
    eng.release_slot(0)
    eng.release_slot(1)
    assert eng.has_capacity(c)           # index pages are now evictable
    eng.admit(2, c, 0.0)
    assert eng.extra_compiles() == 0


# -- page-granular handoff ----------------------------------------------------

def _page_blob(**over):
    """A small valid PTKP blob, with overrides for corruption."""
    k = np.arange(2 * 2 * 4 * 3, dtype=np.float32).reshape(2, 2, 4, 3)
    pages = [{"id": 0, "hash": "ab" * 16, "planes": (k, k + 1)},
             {"id": 1, "hash": None, "planes": (k + 2, k + 3)}]
    kw = {"length": 6, "first_token": 5, "page_size": 4}
    kw.update(over)
    return pack_kv_pages(pages, kw["length"], kw["first_token"],
                         kw["page_size"])


def _rewrite_header(blob, mutate):
    """Parse a PTKP blob, let ``mutate`` edit the header dict, and
    re-frame with a fresh CRC — corrupt-but-checksummed slabs."""
    head = struct.Struct(">4sHI")
    magic, version, hlen = head.unpack_from(blob, 0)
    header = json.loads(blob[head.size:head.size + hlen])
    payload = blob[head.size + hlen:-4]
    mutate(header)
    hb = json.dumps(header, separators=(",", ":")).encode()
    body = head.pack(magic, version, len(hb)) + hb + payload
    return body + struct.pack(">I", zlib.crc32(body) & 0xFFFFFFFF)


def test_page_slab_roundtrip():
    slab = unpack_kv_pages(_page_blob())
    assert isinstance(slab, PageSlab)
    assert (slab.length, slab.first_token, slab.page_size) == (6, 5, 4)
    assert [p["id"] for p in slab.pages] == [0, 1]
    assert slab.pages[0]["hash"] == "ab" * 16
    np.testing.assert_array_equal(
        np.asarray(slab.pages[1]["planes"][0]),
        np.asarray(slab.pages[0]["planes"][0]) + 2)
    # header-only page: planes stripped, hash kept
    k = np.zeros((2, 2, 4, 3), np.float32)
    slab2 = unpack_kv_pages(pack_kv_pages(
        [{"id": 0, "hash": "cd" * 16, "planes": None},
         {"id": 1, "hash": None, "planes": (k, k)}], 6, 5, 4))
    assert slab2.pages[0]["planes"] is None
    assert slab2.pages[0]["hash"] == "cd" * 16


def test_page_slab_corrupt_reject_table():
    """The satellite's reject table: every corruption lands
    HandoffError (-> HTTP 400), never a partial parse."""
    blob = _page_blob()
    # framing: truncation, wrong (v1) magic, CRC, trailing bytes
    for bad in (blob[:-3], b"PTKV" + blob[4:], b"", blob + b"x"):
        with pytest.raises(HandoffError):
            unpack_kv_pages(bad)
    # truncated page list: header claims fewer pages than length needs
    with pytest.raises(HandoffError, match="truncated"):
        unpack_kv_pages(_rewrite_header(
            blob, lambda h: h["pages"].pop()))
    # duplicate page ids
    with pytest.raises(HandoffError, match="duplicate"):
        unpack_kv_pages(_rewrite_header(
            blob, lambda h: h["pages"][1].update(id=0)))
    # refcount overflow (and negative), header forged with a valid CRC
    with pytest.raises(HandoffError, match="refcount"):
        unpack_kv_pages(_rewrite_header(
            blob, lambda h: h["pages"][0].update(refcount=1 << 31)))
    with pytest.raises(HandoffError, match="refcount"):
        unpack_kv_pages(_rewrite_header(
            blob, lambda h: h["pages"][0].update(refcount=-1)))
    # pack refuses the overflow too (range-checked on both ends)
    k = np.zeros((2, 2, 4, 3), np.float32)
    with pytest.raises(HandoffError, match="refcount"):
        pack_kv_pages([{"id": 0, "hash": None, "planes": (k, k),
                        "refcount": 1 << 31}], 4, 1, 4)
    # hash-mismatched page payload: flip one payload byte, re-CRC —
    # the per-page sha localizes the corruption and refuses the slab
    head = struct.Struct(">4sHI")
    _, _, hlen = head.unpack_from(blob, 0)
    body = bytearray(blob[:-4])
    body[head.size + hlen + 8] ^= 0x40
    bad = bytes(body) + struct.pack(
        ">I", zlib.crc32(bytes(body)) & 0xFFFFFFFF)
    with pytest.raises(HandoffError, match="hash mismatch"):
        unpack_kv_pages(bad)
    # absent page without a hash to resolve it by
    with pytest.raises(HandoffError, match="absent"):
        unpack_kv_pages(_rewrite_header(
            blob, lambda h: h["pages"][1].update(
                present=False, planes=None, hash=None)))


def test_page_handoff_end_to_end_and_prefix_peer(model):
    """prefill_export_pages -> wire -> admit_prefilled_pages equals the
    single-engine generation; a SECOND handoff of the same prompt ships
    header-only pages resolved out of the decode tier's own index (the
    fleet-prefix-cache contract)."""
    prompt = _prompts(1, rng_seed=6, lo=7, hi=9)[0]
    want = _ring(model, slots=1).warmup().generate(
        [prompt], max_new_tokens=6, temperature=0.0, stop_at_eos=False)[0]
    pre = _paged(model, slots=1).warmup(kind="prefill")
    dec = _paged(model, slots=2).warmup(kind="decode")

    def drive(slab, slot):
        got = [dec.admit_prefilled_pages(
            slot, slab.pages, slab.length, slab.first_token,
            page_size=slab.page_size, tenant="fleet")]
        last = np.zeros(2, np.int32)
        temps = np.zeros(2, np.float32)
        last[slot] = got[0]
        for _ in range(5):
            nxt = dec.step(last, temps)
            got.append(int(nxt[slot]))
            last[slot] = nxt[slot]
        return got

    pages, n, tok = pre.prefill_export_pages(prompt, temperature=0.0)
    slab = unpack_kv_pages(pack_kv_pages(pages, n, tok, PS))
    assert all(p["planes"] is not None for p in slab.pages)
    assert drive(slab, 0) == want

    # negotiate: the decode tier now knows the prompt's full pages
    hashes = chain_hashes(prompt, PS)
    known = dec.known_page_hashes(hashes)
    assert known == set(hashes)
    pages2, n2, tok2 = pre.prefill_export_pages(
        prompt, temperature=0.0, known_hashes=known)
    shipped = [p for p in pages2 if p["planes"] is not None]
    assert len(shipped) == len(pages2) - len(hashes)  # only the tail
    slab2 = unpack_kv_pages(pack_kv_pages(pages2, n2, tok2, PS))
    assert drive(slab2, 1) == want
    assert dec.paging_stats()["prefix_index"]["hits"] >= 1
    assert dec.extra_compiles() == 0
    # a header-only page the receiver does NOT hold is refused whole
    fresh = _paged(model, slots=1).warmup(kind="decode")
    before = fresh.paging_stats()["pages_free"]
    with pytest.raises(HandoffError, match="header-only"):
        fresh.admit_prefilled_pages(
            0, slab2.pages, slab2.length, slab2.first_token,
            page_size=slab2.page_size)
    assert fresh.paging_stats()["pages_free"] == before


def test_v1_slab_lands_on_paged_tier(model):
    """A ring prefill tier's contiguous PTKV slab still lands on a
    paged decode tier (split into anonymous pages) — mixed-layout
    fleets stay interoperable during a rollout."""
    prompt = [5, 6, 7, 8, 9]
    want = _ring(model, slots=1).warmup().generate(
        [prompt], max_new_tokens=6, temperature=0.0, stop_at_eos=False)[0]
    pre = _ring(model, slots=1).warmup(kind="prefill")
    dec = _paged(model, slots=2).warmup(kind="decode")
    planes, n, tok = pre.prefill_export(prompt, temperature=0.0)
    got = [dec.admit_prefilled(1, planes, n, tok)]
    last = np.zeros(2, np.int32)
    temps = np.zeros(2, np.float32)
    last[1] = got[0]
    for _ in range(5):
        nxt = dec.step(last, temps)
        got.append(int(nxt[1]))
        last[1] = nxt[1]
    assert got == want
    assert dec.extra_compiles() == 0


def test_page_size_mismatch_refused(model):
    dec = _paged(model, slots=1)
    k = np.zeros((2, 2, 8, 3), np.float32)
    with pytest.raises(HandoffError, match="page_size"):
        dec.admit_prefilled_pages(
            0, [{"id": 0, "hash": None, "planes": (k, k)}], 8, 1,
            page_size=8)


# -- scheduler + serving integration -----------------------------------------

def test_batcher_releases_pages_and_waits_for_pool(model):
    """Admission consults pool free pages: with a pool smaller than
    slots x pages_per_slot, more requests than the pool can hold at
    once still ALL complete (the queue waits for page reclamation),
    and a drained scheduler leaves every non-index page free."""
    eng = _paged(model, slots=2, kv_pool_pages=CACHE // PS + 2).warmup()
    total = eng.paging_stats()["pages_total"]
    sched = ContinuousBatcher(eng, queue_capacity=16).start()
    try:
        reqs = [sched.submit(p, max_new_tokens=4, temperature=0.0)
                for p in _prompts(5, rng_seed=4, lo=5, hi=9)]
        outs = [r.wait(timeout=120) for r in reqs]
        assert all(1 <= len(o) <= 4 for o in outs)
        assert sched.extra_compiles() == 0
    finally:
        sched.stop(drain=False)
    st = eng.paging_stats()
    assert st["pages_free"] + st["prefix_index"]["pages"] == total


def test_paged_statz_and_http_disagg(model):
    """/statz paging block + the PTKP wire over HTTP: the prefill tier
    answers page-granular when asked, /prefix_known negotiates, and
    the decode tier lands the slab and finishes the generation."""
    prompt = [5, 6, 7, 8]
    ref = _ring(model, slots=1).warmup().generate(
        [prompt], max_new_tokens=5, temperature=0.0)[0]
    pre = GenerationServer(_paged(model, slots=1), port=0,
                           kind="prefill")
    dec = GenerationServer(_paged(model, slots=2), port=0, kind="decode",
                           queue_capacity=8)
    try:
        pre.start()
        dec.start()
        known = json.loads(urlopen(
            Request(dec.url + "/prefix_known",
                    data=json.dumps({"hashes": chain_hashes(
                        prompt, PS)}).encode()),
            timeout=60).read())
        assert known == {"known": [], "layout": "paged"}
        body = json.dumps({"prompt": prompt, "max_new_tokens": 5,
                           "temperature": 0.0, "stream": False,
                           "page_format": True,
                           "known_hashes": known["known"],
                           "tenant": "acme"}).encode()
        r = urlopen(Request(pre.url + "/prefill", data=body),
                    timeout=120)
        blob = r.read()
        assert r.headers["Content-Type"].endswith("kv-pages")
        assert blob[:4] == b"PTKP"
        r2 = urlopen(Request(dec.url + "/generate_kv", data=blob),
                     timeout=120)
        assert json.loads(r2.read())["tokens"] == ref
        hz = json.loads(urlopen(dec.url + "/healthz", timeout=60).read())
        assert hz["kv_cache_layout"] == "paged"
        sz = json.loads(urlopen(dec.url + "/statz", timeout=60).read())
        assert sz["paging"]["layout"] == "paged"
        assert sz["paging"]["page_size"] == PS
        assert sz["paging"]["pages_total"] > 0
        assert "acme" in sz["paging"]["per_tenant"]
        prom = urlopen(dec.url + "/metrics", timeout=60).read().decode()
        assert "generation_pages_free" in prom
    finally:
        pre.stop(drain=False)
        dec.stop(drain=False)
