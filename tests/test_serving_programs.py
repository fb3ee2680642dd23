"""The decode and prefill programs of the five served families the
benchmark had before PR 49 (`minicpm-sala-9b` added a cache kind, a state
kind without a tail and host-side counters to code they share), pinned
to the text they lowered to at PR 48's tree: see `_programs`."""
import hashlib
import json
import os

import numpy as np

from paddle_tpu.generation import GenerationEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PINNED = os.path.join(ROOT, "tests", "data",
                       "serving_programs.pr48.sha256.json")


def _programs():
    """{family.program: sha256 of its StableHLO text} for the decode and
    every prefill program of the five served families at their tests'
    toy sizes (the engines of tests/test_hybrid_moe.py, test_exaone_moe.py,
    test_longcat_flash.py, test_nemotron_h.py and `gpt_tiny_config`): what
    jax hands to XLA, which names no path and no line. The pinned file
    was written from PR 48's tree (`SERVING_HLO_RECORD=<path> pytest
    tests/test_serving_programs.py` with this file copied there);
    a PR that means to change one of these programs records it again and
    says so."""
    import test_exaone_moe
    import test_hybrid_moe
    import test_longcat_flash
    import test_nemotron_h
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny_config

    def dump(tag, eng, buckets):
        calls = {"decode": eng._decode_call(
            np.zeros(eng.slots, np.int32), np.zeros(eng.slots, np.float32),
            0)}
        for p in buckets:
            calls[f"prefill{p}"] = eng._prefill_call(
                0, np.zeros(p, np.int32), p - 1, 0.0, 0)
        return {f"{tag}.{name}": hashlib.sha256(
            fn.lower(*make()).as_text().encode()).hexdigest()
            for name, (_, fn, make) in calls.items()}

    out = {}
    for tag, mod in (("solar", test_hybrid_moe), ("exaone", test_exaone_moe),
                     ("longcat", test_longcat_flash),
                     ("nemotron", test_nemotron_h)):
        out.update(dump(tag, mod._engine(mod._model()[0]), (8, 16, 32)))
    g = GPTForCausalLM(gpt_tiny_config())
    g.eval()
    out.update(dump("gpt", GenerationEngine(
        g, slots=2, cache_len=32, prefill_buckets=(8, 16), temperature=0.0,
        top_k=0), (8, 16)))
    return out


def test_the_served_families_programs_lower_to_the_parents_text():
    got = _programs()
    record = os.environ.get("SERVING_HLO_RECORD")
    if record:
        with open(record, "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
    with open(record or _PINNED) as f:
        want = json.load(f)
    assert len(want) == 4 * 4 + 3
    assert {k: v for k, v in got.items() if want[k] != v} == {}
