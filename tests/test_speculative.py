"""Speculative decoding + disaggregated prefill/decode handoff.

Pins the two cost-per-token levers this PR adds:

- **speculative decoding**: greedy output is TOKEN-IDENTICAL to the
  plain engine (including ring wraparound and co-batched slots, fp32
  and int8 KV caches), warmup compiles exactly the draft+verify program
  set with zero growth under traffic, a self-draft accepts everything,
  and the generalized store>window ring masks that make the in-place
  verify write exact are golden-tested;
- **KV-slab handoff**: prefill-export bytes round-trip through
  ``insert_slot_kv`` to a decode-parity continuation in BOTH cache
  modes, truncated/corrupt payloads are rejected loudly, and the
  serving plumbing (kind-scoped routes, router kind-aware pick +
  re-pick, per-kind autoscaler signals) behaves.
"""
import contextlib
import json
from concurrent.futures import ThreadPoolExecutor
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.generation import (
    COMPILE_COUNTER,
    GenerationEngine,
    HandoffError,
    decode_mask,
    pack_kv_slab,
    unpack_kv_slab,
    verify_mask,
)
from paddle_tpu.models import (
    GPTForCausalLM,
    gpt_tiny_config,
    load_gpt_model,
    save_gpt_model,
    truncated_draft,
)
from paddle_tpu.serving import GenerationServer, Router
from paddle_tpu.serving.scaler import (
    AutoScaler,
    FleetSignals,
    launch_process,
)

CACHE = 24
BUCKETS = (4, 8)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = gpt_tiny_config()
    cfg.attention_window = CACHE
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def draft(model):
    return truncated_draft(model, num_layers=1)


def _engine(model, slots=2, seed=7, **kw):
    return GenerationEngine(model, slots=slots, cache_len=CACHE,
                            prefill_buckets=BUCKETS, seed=seed, **kw)


def _prompts(n, rng_seed=0, lo=1, hi=9):
    rng = np.random.RandomState(rng_seed)
    return [list(map(int, rng.randint(3, 200,
                                      size=int(rng.randint(lo, hi)))))
            for _ in range(n)]


# -- generalized ring masks ---------------------------------------------------

def test_decode_mask_store_equals_window_unchanged():
    """The historical store==window behavior: entries < min(pos+1, C)
    kept, everything else masked."""
    pos = jnp.asarray([0, 2, 3, 7, 11], jnp.int32)
    m = np.asarray(decode_mask(pos, 4))[:, 0, 0]
    for b, p in enumerate([0, 2, 3, 7, 11]):
        expect = [0.0 if j < min(p + 1, 4) else -1e9 for j in range(4)]
        assert m[b].tolist() == expect, (p, m[b])


def test_decode_mask_store_wider_than_window():
    """store=C+k: entry j holds absolute position pos - ((pos-j) mod
    store); kept iff inside the window AND ever written."""
    store, window = 7, 4
    pos = jnp.asarray([2, 9], jnp.int32)
    m = np.asarray(decode_mask(pos, store, window=window))[:, 0, 0]
    for b, p in enumerate([2, 9]):
        for j in range(store):
            dd = (p - j) % store
            keep = dd < window and dd <= p
            assert (m[b, j] == 0.0) == keep, (p, j, dd)


def test_verify_mask_row0_is_decode_mask_and_causal_rows():
    """Row 0 of the verify span reduces to the decode mask; later rows
    additionally see their in-flight predecessors and NEVER the q > i
    future writes (ring distance >= window by the store margin)."""
    store, window, span = CACHE + 3, CACHE, 4
    pos = jnp.asarray([0, 5, CACHE + 2, 3 * CACHE + 1], jnp.int32)
    vm = np.asarray(verify_mask(pos, store, span, window=window))[:, 0]
    dm = np.asarray(decode_mask(pos, store, window=window))[:, 0, 0]
    assert (vm[:, 0] == dm).all()
    for b, p in enumerate(np.asarray(pos)):
        for i in range(span):
            for q in range(span):
                j = (int(p) + q) % store
                kept = vm[b, i, j] == 0.0
                assert kept == (q <= i), (p, i, q)


# -- speculative greedy parity ------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_spec_greedy_token_identical_incl_wraparound(model, draft, dtype):
    """The acceptance criterion: speculative greedy decode equals the
    plain engine token for token, on budgets that wrap the ring."""
    plain = _engine(model, kv_cache_dtype=dtype).warmup()
    spec = _engine(model, kv_cache_dtype=dtype, draft_model=draft,
                   draft_k=3).warmup()
    for p in _prompts(5, rng_seed=1):
        want = plain.generate([p], max_new_tokens=CACHE + 9,
                              temperature=0.0, stop_at_eos=False)[0]
        got = spec.generate([p], max_new_tokens=CACHE + 9,
                            temperature=0.0, stop_at_eos=False)[0]
        assert got == want, (p, got, want)
    assert spec.extra_compiles() == 0


def test_spec_cobatched_greedy_parity(model, draft):
    """Slot co-residency stays numerically inert under speculative
    rounds: continuous-batched == one-at-a-time."""
    # solo warms FIRST: the compile counter is process-global, so the
    # last-armed engine is the one whose extra_compiles() stays exact
    solo = _engine(model, slots=3, draft_model=draft, draft_k=4).warmup()
    spec = _engine(model, slots=3, draft_model=draft, draft_k=4).warmup()
    prompts = _prompts(7, rng_seed=2)
    together = spec.generate(prompts, max_new_tokens=12,
                             temperature=0.0, stop_at_eos=False)
    alone = [solo.generate([p], max_new_tokens=12, temperature=0.0,
                           stop_at_eos=False)[0] for p in prompts]
    assert together == alone
    assert spec.extra_compiles() == 0


def test_self_draft_acceptance_near_total(model):
    """Draft == target: proposals match the target's own chain except
    where the 1-row draft forward and the (k+1)-row verify forward
    round near-ties differently (the ulp deltas also land in the two
    rings' cached K/V and compound) — acceptance must sit near the
    ceiling, far above chance."""
    spec = _engine(model, draft_model=model, draft_k=3).warmup()
    spec.generate(_prompts(3, rng_seed=4), max_new_tokens=13,
                  temperature=0.0, stop_at_eos=False)
    stats = spec.spec_stats()
    assert stats["proposed"] > 0
    assert stats["acceptance_rate"] > 0.6, stats


def test_spec_warmup_compile_counts_exact(model, draft):
    """Warmup = len(buckets) prefills + draft + verify, and a mixed
    burst afterwards compiles NOTHING (the compile-bound contract on
    the speculative path)."""
    spec = _engine(model, draft_model=draft, draft_k=2)
    assert spec.expected_compiles() == len(BUCKETS) + 2
    c0 = profiler.counters().get(COMPILE_COUNTER, 0)
    spec.warmup()
    assert profiler.counters().get(COMPILE_COUNTER, 0) - c0 \
        == len(BUCKETS) + 2
    spec.generate(_prompts(6, rng_seed=5), max_new_tokens=9,
                  temperature=0.0, stop_at_eos=False)
    assert profiler.counters().get(COMPILE_COUNTER, 0) - c0 \
        == len(BUCKETS) + 2
    assert spec.extra_compiles() == 0


def test_spec_budget_truncation(model, draft):
    """A round emitting more than the remaining budget is truncated at
    the budget (finish_reason length), never over-delivered."""
    spec = _engine(model, draft_model=draft, draft_k=4).warmup()
    plain = _engine(model).warmup()
    for budget in (1, 2, 3):
        p = [5, 9, 3]
        want = plain.generate([p], max_new_tokens=budget,
                              temperature=0.0, stop_at_eos=False)[0]
        got = spec.generate([p], max_new_tokens=budget,
                            temperature=0.0, stop_at_eos=False)[0]
        assert got == want and len(got) == budget


def test_spec_validation(model, draft):
    with pytest.raises(InvalidArgumentError):
        _engine(model, draft_model=draft, draft_k=0)
    small = gpt_tiny_config()
    small.vocab_size = 7  # draft proposals are target token ids
    with pytest.raises(InvalidArgumentError):
        _engine(model, draft_model=GPTForCausalLM(small))
    short = gpt_tiny_config()
    short.max_position_embeddings = 16  # < target's: would silently
    with pytest.raises(InvalidArgumentError):  # gather clamped embeds
        _engine(model, draft_model=GPTForCausalLM(short))
    with pytest.raises(InvalidArgumentError):
        _engine(model).spec_step(np.zeros(2, np.int32),
                                 np.zeros(2, np.float32))


# -- KV-slab handoff ----------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_handoff_bytes_roundtrip_decode_parity(model, dtype):
    """The satellite contract: prefill-export -> bytes ->
    insert_slot_kv on a DIFFERENT engine -> decode continuation equals
    the single-process generation, fp32 and int8 (5-tuple arity)."""
    ref = _engine(model, slots=1, kv_cache_dtype=dtype).warmup()
    pre = _engine(model, slots=1, kv_cache_dtype=dtype).warmup(
        kind="prefill")
    dec = _engine(model, slots=2, kv_cache_dtype=dtype).warmup(
        kind="decode")
    for p in _prompts(3, rng_seed=6):
        want = ref.generate([p], max_new_tokens=CACHE + 6,
                            temperature=0.0, stop_at_eos=False)[0]
        planes, n, tok = pre.prefill_export(p, temperature=0.0)
        blob = pack_kv_slab(planes, n, tok, meta={"prompt": p})
        planes2, n2, tok2, meta = unpack_kv_slab(blob)
        assert (n2, tok2, meta["prompt"]) == (n, tok, p)
        slot = 1
        got = [dec.admit_prefilled(slot, planes2, n2, tok2)]
        last = np.zeros(2, np.int32)
        temps = np.zeros(2, np.float32)
        last[slot] = got[0]
        for _ in range(CACHE + 5):
            nxt = dec.step(last, temps)
            got.append(int(nxt[slot]))
            last[slot] = nxt[slot]
        assert got == want, (p, got, want)
    assert dec.extra_compiles() == 0


def test_handoff_rejects_truncated_and_corrupt():
    arr = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    blob = pack_kv_slab((arr, arr), 3, 17, meta={"x": 1})
    for bad in (blob[:-5],                      # truncated payload
                blob[:10],                      # truncated header
                blob[:40] + b"\x7f" + blob[41:],  # flipped byte
                b"nope" + blob[4:],             # bad magic
                blob + b"extra",                # trailing garbage
                b""):
        with pytest.raises(HandoffError):
            unpack_kv_slab(bad)


def test_handoff_rejects_hostile_plane_specs():
    """A CRC-VALID slab whose plane spec names a non-numeric dtype (or
    a negative dim) must 400 like any other corrupt payload — not
    crash frombuffer past the HandoffError mapping and drop the HTTP
    connection (which the router would read as a dead backend)."""
    import json as _json
    import struct as _struct
    import zlib as _zlib

    def forge(spec):
        header = _json.dumps({"planes": [spec], "length": 1,
                              "first_token": 0, "meta": {}},
                             separators=(",", ":")).encode()
        body = _struct.pack(">4sHI", b"PTKV", 1, len(header)) + header
        return body + _struct.pack(">I", _zlib.crc32(body) & 0xFFFFFFFF)

    for spec in ({"shape": [1], "dtype": "object"},
                 {"shape": [-1, 4], "dtype": "float32"},
                 {"shape": [2], "dtype": "str"},
                 {"shape": [2], "dtype": "complex128"}):
        with pytest.raises(HandoffError):
            unpack_kv_slab(forge(spec))


def test_handoff_arity_and_geometry_rejects(model):
    """A slab from the wrong cache mode (or geometry) must be refused
    BEFORE anything is inserted."""
    pre8 = _engine(model, slots=1, kv_cache_dtype="int8").warmup(
        kind="prefill")
    dec = _engine(model, slots=1).warmup(kind="decode")
    planes, n, tok = pre8.prefill_export([4, 5, 6])
    with pytest.raises(InvalidArgumentError):
        dec.admit_prefilled(0, planes, n, tok)  # 4 planes into fp32
    with pytest.raises(InvalidArgumentError):
        dec.admit_prefilled(0, dec._fresh_slot_planes(), 0, 0)  # len 0
    with pytest.raises(InvalidArgumentError):
        dec.admit_prefilled(0, dec._fresh_slot_planes(), CACHE + 1, 0)


def test_speculative_decode_tier_needs_prompt(model, draft):
    """A speculative decode tier cannot build the draft's ring from a
    target-only slab — admission without the prompt must error."""
    dec = _engine(model, slots=1, draft_model=draft,
                  draft_k=2).warmup(kind="decode")
    with pytest.raises(InvalidArgumentError):
        dec.admit_prefilled(0, dec._fresh_slot_planes(), 2, 0)
    # with the prompt it works (and decodes)
    dec.admit_prefilled(0, dec._fresh_slot_planes(), 2, 0,
                        prompt=[3, 4])
    assert dec.extra_compiles() == 0


# -- kind-scoped servers ------------------------------------------------------

def test_prefill_kind_server_routes_and_slab(model):
    srv = GenerationServer(_engine(model, slots=1), kind="prefill",
                           queue_capacity=4).start()
    try:
        body = json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 4,
                           "temperature": 0.0}).encode()
        r = urlopen(Request(srv.url + "/prefill", data=body), timeout=60)
        assert r.status == 200
        assert r.headers["Content-Type"].startswith(
            "application/x-ptpu-kv-slab")
        planes, n, tok, meta = unpack_kv_slab(r.read())
        assert n == 3 and meta["params"]["prompt"] == [5, 6, 7]
        assert meta["cache"]["cache_len"] == CACHE
        # the prefill tier does NOT serve /generate
        with pytest.raises(HTTPError) as e:
            urlopen(Request(srv.url + "/generate", data=body), timeout=60)
        assert e.value.code == 404
        lz = json.loads(urlopen(srv.url + "/loadz").read())
        assert lz["kind"] == "prefill"
        assert lz["compiles"]["expected"] == len(BUCKETS)
    finally:
        srv.stop(drain=False)


def test_decode_kind_server_generate_kv_parity(model):
    ref = _engine(model, slots=1).warmup()
    pre = GenerationServer(_engine(model, slots=1), kind="prefill",
                           queue_capacity=4).start()
    dec = GenerationServer(_engine(model, slots=2), kind="decode",
                           queue_capacity=4).start()
    try:
        prompt = [9, 2, 14, 6]
        want = ref.generate([prompt], max_new_tokens=7, temperature=0.0,
                            stop_at_eos=False)[0]
        body = json.dumps({"prompt": prompt, "max_new_tokens": 7,
                           "temperature": 0.0}).encode()
        slab = urlopen(Request(pre.url + "/prefill", data=body),
                       timeout=60).read()
        r = urlopen(Request(dec.url + "/generate_kv", data=slab),
                    timeout=60)
        out = json.loads(r.read())
        assert out["tokens"] == want
        assert out["prompt_tokens"] == len(prompt)
        # geometry mismatch -> 400 (slab re-labeled with a wrong window)
        planes, n, tok, meta = unpack_kv_slab(slab)
        meta["cache"]["cache_len"] = CACHE + 8
        bad = pack_kv_slab(planes, n, tok, meta=meta)
        with pytest.raises(HTTPError) as e:
            urlopen(Request(dec.url + "/generate_kv", data=bad),
                    timeout=60)
        assert e.value.code == 400
        # garbage body -> 400, not 500
        with pytest.raises(HTTPError) as e:
            urlopen(Request(dec.url + "/generate_kv", data=b"junk"),
                    timeout=60)
        assert e.value.code == 400
    finally:
        pre.stop(drain=False)
        dec.stop(drain=False)


@contextlib.contextmanager
def _two_tiers(model, how, tmp_path):
    """A prefill tier and a decode tier, as servers of this process or as
    two ``paddle_tpu.serving.backend`` PROCESSES over a saved model."""
    if how == "in_process":
        pre = GenerationServer(_engine(model, slots=1), kind="prefill",
                               queue_capacity=4).start()
        dec = GenerationServer(_engine(model, slots=2), kind="decode",
                               queue_capacity=4).start()
        try:
            yield pre.url, dec.url
        finally:
            pre.stop(drain=False)
            dec.stop(drain=False)
        return
    gpt_dir = str(tmp_path / "gpt")
    save_gpt_model(model, gpt_dir)
    common = ["--gpt-dir", gpt_dir, "--cache-len", str(CACHE),
              "--prefill-buckets", ",".join(map(str, BUCKETS))]
    with ThreadPoolExecutor(2) as pool:  # the two warm-ups side by side
        boots = [pool.submit(launch_process, "paddle_tpu.serving.backend",
                             ["--kind", kind, *common, "--slots", slots],
                             startup_timeout_s=120.0)
                 for kind, slots in (("prefill", "1"), ("decode", "2"))]
    try:
        yield tuple(b.result().url for b in boots)
    finally:
        for b in boots:
            if b.exception() is None:
                b.result().proc.kill()
                b.result().proc.wait(10)


@pytest.mark.parametrize("how", ["in_process", "two_processes"])
def test_router_disagg_generate_end_to_end(model, how, tmp_path):
    """Router-orchestrated prefill->decode /generate equals unified
    output, through servers of this process and through two backend
    processes handing the KV slab over HTTP; neither tier compiles
    anything its warm-up did not."""
    ref = _engine(model, slots=1).warmup()
    with _two_tiers(model, how, tmp_path) as (pre_url, dec_url):
        router = Router(backends=[pre_url, dec_url]).start()
        try:
            for url, kind in ((pre_url, "prefill"), (dec_url, "decode")):
                hz = json.loads(urlopen(url + "/healthz", timeout=10).read())
                assert hz["kind"] == kind
            prompt = [3, 7, 2]
            want = ref.generate([prompt], max_new_tokens=6,
                                temperature=0.0, stop_at_eos=False)[0]
            body = json.dumps({"prompt": prompt, "max_new_tokens": 6,
                               "temperature": 0.0}).encode()
            out = json.loads(urlopen(
                Request(router.url + "/generate", data=body),
                timeout=60).read())
            assert out["tokens"] == want
            assert out["prompt_tokens"] == len(prompt)
            # streaming survives both hops
            body = json.dumps({"prompt": prompt, "max_new_tokens": 6,
                               "temperature": 0.0,
                               "stream": True}).encode()
            lines = [json.loads(line) for line in urlopen(
                Request(router.url + "/generate", data=body),
                timeout=60).read().decode().splitlines()]
            toks = [ln["token"] for ln in lines if "token" in ln]
            assert toks == want and lines[-1].get("done")
            for url in (pre_url, dec_url):
                lz = json.loads(urlopen(url + "/loadz", timeout=10).read())
                assert lz["compiles"]["unexpected"] == 0
        finally:
            router.stop(drain=False)


def test_disagg_needs_both_tiers_else_unified(model):
    """A live prefill tier WITHOUT a decode tier must not capture
    /generate into a doomed handoff — unified generate backends keep
    serving."""
    pre = GenerationServer(_engine(model, slots=1), kind="prefill",
                           queue_capacity=4).start()
    gen = GenerationServer(_engine(model, slots=1), kind="generate",
                           queue_capacity=4).start()
    router = Router(backends=[pre.url, gen.url]).start()
    try:
        body = json.dumps({"prompt": [5, 6], "max_new_tokens": 4,
                           "temperature": 0.0}).encode()
        out = json.loads(urlopen(
            Request(router.url + "/generate", data=body),
            timeout=60).read())
        assert len(out["tokens"]) == 4  # served by the generate tier
    finally:
        router.stop(drain=False)
        pre.stop(drain=False)
        gen.stop(drain=False)


def test_spec_decode_tier_ladder_mismatch_400(model, draft):
    """A speculative decode tier whose ladder cannot cover the
    handed-off prompt must 400 at /generate_kv (its draft re-prefill
    needs a covering bucket) — not 500 out of the decode loop after
    the prefill-tier forward was already spent."""
    pre = GenerationServer(_engine(model, slots=1), kind="prefill",
                           queue_capacity=4).start()
    dec = GenerationServer(
        GenerationEngine(model, slots=1, cache_len=CACHE,
                         prefill_buckets=(4,), seed=7,
                         draft_model=draft, draft_k=2),
        kind="decode", queue_capacity=4).start()
    try:
        body = json.dumps({"prompt": [1 + i for i in range(6)],
                           "max_new_tokens": 3,
                           "temperature": 0.0}).encode()
        slab = urlopen(Request(pre.url + "/prefill", data=body),
                       timeout=60).read()
        with pytest.raises(HTTPError) as e:
            urlopen(Request(dec.url + "/generate_kv", data=slab),
                    timeout=60)
        assert e.value.code == 400
    finally:
        pre.stop(drain=False)
        dec.stop(drain=False)


def test_backend_cli_speculative_needs_draft_dir():
    from paddle_tpu.serving.backend import _parse_args

    with pytest.raises(SystemExit):
        _parse_args(["--kind", "generate", "--gpt-dir", "/x",
                     "--speculative"])


def test_prefill_tier_releases_decode_ring(model):
    """A prefill-tier engine's warmup shrinks the never-written decode
    ring to one slot — the tier's HBM goes to prefill activations."""
    eng = _engine(model, slots=8)
    full = eng.cache_nbytes()
    eng.warmup(kind="prefill")
    assert all(a.shape[0] == 1 for plane in eng._kv[:-1] for a in plane)
    assert eng.cache_nbytes() * 4 < full
    # exports still work after the shrink
    planes, n, tok = eng.prefill_export([3, 4, 5])
    assert n == 3 and planes[0].shape[2] == CACHE


# -- router kind-aware pick ---------------------------------------------------

def test_pick_prefers_kind_confirmed_backends(model):
    """A kind-unknown backend must not win a pick for a kind a
    CONFIRMED backend serves; unknowns are only the no-confirmed
    fallback."""
    router = Router()
    try:
        a = router.add_backend("http://127.0.0.1:1", probe=False)
        b = router.add_backend("http://127.0.0.1:2", probe=False)
        a.in_rotation = True
        a.kind = "generate"
        a.queue_depth = 50  # heavily loaded — still must win on kind
        b.in_rotation = True
        b.kind = None
        for _ in range(8):
            assert router._pick("generate", set()) is a
        # no confirmed backend for the kind -> unknown is eligible
        a.kind = "decode"
        assert router._pick("generate", set()) is b
        # nothing at all -> None
        b.in_rotation = False
        assert router._pick("generate", set()) is None
    finally:
        router.stop(drain=False)


def test_kind_mismatch_404_repicks_not_fails(model):
    """A kind-unknown backend answering 404 is re-picked around (its
    kind learned from the probe), and the request still succeeds."""
    dec = GenerationServer(_engine(model, slots=1), kind="decode",
                           queue_capacity=4).start()
    gen = GenerationServer(_engine(model, slots=1), kind="generate",
                           queue_capacity=4).start()
    # probe interval parked at 60s: the prober must NOT be the one to
    # learn the kinds — the 404 re-pick path has to
    router = Router(probe_interval_s=60.0).start()
    try:
        bd = router.add_backend(dec.url, probe=False)
        bg = router.add_backend(gen.url, probe=False)
        for s in (bd, bg):
            s.in_rotation = True
            s.kind = None  # unprobed: the router has no kind map yet
        bg.queue_depth = 5  # stack the pick toward the WRONG backend
        body = json.dumps({"prompt": [4, 5], "max_new_tokens": 3,
                           "temperature": 0.0}).encode()
        out = json.loads(urlopen(
            Request(router.url + "/generate", data=body),
            timeout=60).read())
        assert len(out["tokens"]) == 3
        assert bd.kind == "decode"  # learned by the mismatch probe
    finally:
        router.stop(drain=False)
        dec.stop(drain=False)
        gen.stop(drain=False)


# -- per-kind autoscaler signals ---------------------------------------------

class _StubState:
    def __init__(self, url, kind, depth, inflight=0, rotation=True):
        self.url = url
        self.kind = kind
        self.queue_depth = depth
        self.inflight = inflight
        self.in_rotation = rotation

    def score(self):
        return self.inflight + self.queue_depth


class _StubRouter:
    def __init__(self, states):
        self.states = states

    def backend_states(self):
        return list(self.states)

    def add_backend(self, url):
        pass

    def remove_backend(self, url):
        pass


def test_scaler_kind_split_unmasks_saturated_tier():
    """The satellite: fleet-wide mean queue depth averages a saturated
    decode tier against idle prefill backends below the threshold; a
    kind-bound scaler sees its tier's true pressure and scales."""
    states = [
        _StubState("http://p1", "prefill", 0),
        _StubState("http://p2", "prefill", 0),
        _StubState("http://p3", "prefill", 0),
        _StubState("http://d1", "decode", 8, inflight=2),
    ]
    router = _StubRouter(states)
    clock = [0.0]
    mk = lambda kind: AutoScaler(  # noqa: E731
        router, launcher=None, kind=kind, min_backends=1, max_backends=8,
        up_queue_depth=4.0, down_queue_depth=0.25, window=2,
        cooldown_s=0.0, interval_s=1.0, clock=lambda: clock[0])
    fleet, decode_tier = mk(None), mk("decode")
    sig = fleet.signals()
    assert sig.mean_queue_depth == pytest.approx(2.0)  # masked!
    assert sig.kinds["decode"]["mean_queue_depth"] == pytest.approx(8.0)
    assert sig.kinds["prefill"]["mean_queue_depth"] == 0.0
    tier_sig = decode_tier.signals()
    assert tier_sig.kind == "decode"
    assert tier_sig.backends_total == 1
    assert tier_sig.mean_queue_depth == pytest.approx(8.0)
    # hysteresis: the decode-bound scaler fires after its window while
    # the fleet-wide one never accumulates an up streak
    for _ in range(2):
        clock[0] += 1.0
        fleet_action = fleet.decide(fleet.signals())
        tier_action = decode_tier.decide(decode_tier.signals())
    assert fleet_action is None
    assert tier_action == "up"


def test_scaler_kind_counts_owned_unprobed_backend():
    """A just-launched owned backend (kind not yet probed) still counts
    toward ITS tier's totals — the max_backends bound must see it."""
    states = [_StubState("http://d1", "decode", 0),
              _StubState("http://new", None, 0, rotation=False)]
    sc = AutoScaler(_StubRouter(states), launcher=None, kind="decode",
                    min_backends=1, max_backends=2, window=1,
                    cooldown_s=0.0, clock=lambda: 0.0)
    sc.owned["http://new"] = object()
    assert sc.signals().backends_total == 2
