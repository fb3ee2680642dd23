"""The served-token check (configs/gpt2-large/check.py) at a tiny size on
the CPU: a near-tie decided the other way passes, a token from another
request fails, the bfloat16 control reads a larger mean gap than a sound
program, and no token is compared for equality."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import common
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def setup():
    cfg = tiny.config("gpt2-large")
    d = os.path.join(tiny.BENCH, "configs", "gpt2-large")
    check = common.load_module(os.path.join(d, "check.py"))
    ref = common.load_module(os.path.join(d, "reference.py"))
    seed = 21
    w = jax.jit(lambda k: ref.weights(cfg, k))(common.seed_key(seed))
    rng = common.host_rng(seed, 3)
    fwd = jax.jit(lambda w, t: ref.forward(w, t, cfg))
    reqs = []
    for _ in range(256):
        prompt = rng.integers(3, cfg["vocab_size"], int(rng.integers(4, 40)))
        logits = np.asarray(fwd(w, jnp.asarray(prompt[None])))[0, -1]
        order = np.argsort(-logits)
        reqs.append({"prompt": prompt.tolist(), "tokens": [int(order[0])],
                     "second": int(order[1]),
                     "top2_gap": float(logits[order[0]] - logits[order[1]]),
                     "logits": logits,
                     "max_new_tokens": 4, "done": True})
    return cfg, check, seed, reqs


def test_sound_tokens_have_no_gap(setup):
    cfg, check, seed, reqs = setup
    got = check.gaps(cfg, seed, reqs)
    assert got["tokens"] == len(reqs)
    assert got["gap_max"] == 0.0 and got["exact_share"] == 1.0


def test_swapped_near_tie_passes_and_foreign_token_fails(setup):
    cfg, check, seed, reqs = setup
    limit = cfg["check"]["gap_max"]
    near = min(reqs, key=lambda r: r["top2_gap"])
    assert near["top2_gap"] < limit
    swapped = [dict(r, tokens=[r["second"]]) if r is near else r
               for r in reqs]
    got = check.gaps(cfg, seed, swapped)
    assert 0.0 < got["gap_max"] <= limit  # a near-tie the other way: fine
    assert got["exact_share"] < 1.0       # and yet not the exact argmax
    # a token that another request was served, put in this one's stream
    # (the one of the others whose gap here is the median of them all)
    others = sorted((r for r in reqs if r is not near),
                    key=lambda r: near["logits"][r["tokens"][0]])
    other = others[len(others) // 2]
    foreign = [dict(r, tokens=other["tokens"]) if r is near else r
               for r in reqs]
    got = check.gaps(cfg, seed, foreign)
    assert got["gap_max"] > 3 * limit


def test_bfloat16_control_reads_above_a_sound_program(setup):
    """The control put in the program's place: at every position of the
    same prompts and tokens, the token bfloat16 puts first. Over a
    thousand positions some near-ties flip: its mean gap is above the 0
    of a sound program here, and its widest gap is still a near-tie."""
    cfg, check, seed, reqs = setup
    sound = check.gaps(cfg, seed, reqs)
    assert sound["gap_mean"] == 0.0 and sound["err_scale"] <= 1e-5
    rng = common.host_rng(seed, 9)
    long = [{"prompt": [5], "tokens": rng.integers(
        3, cfg["vocab_size"], 120).tolist()} for _ in range(10)]
    _, control = check.gaps(cfg, seed, long, control=True)
    assert control["tokens"] == 1200
    assert control["gap_mean"] > 0.0 and control["exact_share"] < 1.0
    assert control["err_scale"] > cfg["check"]["err_scale"]
    assert control["gap_max"] < cfg["check"]["gap_max"]


def test_sample_is_seeded_and_holds_the_longest(setup):
    cfg, check, seed, reqs = setup
    a, b = check.sample(reqs, 5, 8), check.sample(reqs, 5, 8)
    assert a == b and len(a) == 8
    longest = max(reqs, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    assert a[0] is longest
    assert check.sample(reqs, 6, 8) != a


def test_err_scale_recovers_a_known_error():
    """Flips drawn from the model itself: spacings as a 50k-row head has
    them (exponential, mean 0.155), errors normal with scale 0.004 and
    with 0.0085; the fit tells the two apart with room."""
    import numpy as np

    cfg, check, seed, _ = (None, common.load_module(os.path.join(
        tiny.BENCH, "configs", "gpt2-large", "check.py")), 0, None)
    rng = np.random.default_rng(5)
    got = {}
    for s in (0.004, 0.0085):
        fits = []
        for _ in range(8):
            d = rng.exponential(0.155, 5000)
            fits.append(check.err_scale(d, rng.normal(0, s, 5000) > d))
        got[s] = fits
        assert 0.8 * s < min(fits) and max(fits) < 1.25 * s
    assert min(got[0.0085]) > 1.5 * max(got[0.004])
