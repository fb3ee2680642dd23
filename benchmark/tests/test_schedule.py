"""The open-loop generator: its schedule is a function of (mix, seed,
seconds) only, every seed gets the same sizes and gaps in another order,
and latencies are timed from the due instant."""
import os

from benchmark.lib import common
from benchmark.tests import tiny

kind = common.load_module(os.path.join(
    tiny.BENCH, "traffic", "kinds", "open_loop_http.py"))
MIX = common.load_json(os.path.join(tiny.BENCH, "traffic",
                                    "chat-overload.json"))


def test_schedule_is_a_function_of_the_seed():
    a = kind.schedule(MIX, 2 ** 31 + 11, 20.0, 50257)
    b = kind.schedule(MIX, 2 ** 31 + 11, 20.0, 50257)
    c = kind.schedule(MIX, 2 ** 31 + 12, 20.0, 50257)
    assert a == b and a != c
    assert len(a) == round(MIX["rate_per_s"] * 20.0)
    assert a[0]["due_s"] == 0.0 and all(
        x["due_s"] <= y["due_s"] for x, y in zip(a, a[1:]))
    assert a[-1]["due_s"] < 20.0


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = kind.schedule(MIX, 1, 30.0, 50257)
    c = kind.schedule(MIX, 2, 30.0, 50257)
    size = lambda rs: sorted((len(r["prompt"]), r["max_new_tokens"])
                             for r in rs)
    assert size(a) == size(c)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    for r in a:
        p, o = len(r["prompt"]), r["max_new_tokens"]
        assert MIX["prompt_tokens"]["min"] <= p <= MIX["prompt_tokens"]["max"]
        assert 1 <= o <= MIX["output_tokens"]["max"]
        assert p + o <= MIX["context_limit"]
        assert min(r["prompt"]) >= 3 and max(r["prompt"]) < 50257


def test_latency_counts_from_the_due_instant():
    reqs = [{"due_s": 1.0, "prompt": [5] * 4, "max_new_tokens": 3}]
    rec = [{"i": 0, "due_s": 1.0, "sent_s": 1.4, "status": 200,
            "error": None, "done": True, "end_s": 2.0,
            "tokens": [7, 8, 9], "token_s": [1.5, 1.7, 2.0],
            "final_tokens": [7, 8, 9]}]
    r = kind.reduce(rec, reqs, 10.0)
    assert abs(r["ttft_p95_ms"] - 500.0) < 1e-6   # 1.5 - due 1.0, not - sent
    assert abs(r["generator_lag_p95_ms"] - 400.0) < 1e-6
    assert abs(r["itl_p95_ms"] - 295.0) < 1e-6    # gaps 200, 300 ms
    assert r["serve_tokens_per_s"] == 0.3 and r["failed"] == 0
    # no token from 2.0 s to the window's end: the longest silence
    assert (r["longest_silence_s"], r["longest_silence_at_s"]) == (8.0, 2.0)
    late = [dict(rec[0], status=429, done=False, tokens=[], token_s=[],
                 error="queue full")]
    assert kind.reduce(late, reqs, 10.0)["failed"] == 1


def test_backlog_at_start_is_due_at_the_first_instant():
    mix = dict(MIX, backlog_at_start=5)
    a = kind.schedule(mix, 3, 20.0, 50257)
    assert [r["due_s"] for r in a[:5]] == [0.0] * 5 and a[5]["due_s"] > 0.0
    assert len(a) == len(kind.schedule(dict(MIX, backlog_at_start=0), 3,
                                       20.0, 50257))
