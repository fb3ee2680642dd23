"""Decides `correct` for the served `gpt2-large` cells, after the window
has closed and the engine's cache is freed.

What is compared is what the timed path produced: tokens that the window
served over HTTP, greedy. A seeded sample of the finished requests, the
longest among them, goes through the reference once each (prompt +
served tokens, teacher-forced), and at every served position the gap
  reference's largest logit - reference's logit of the served token
is read. A token that is the reference's own argmax has gap 0; a near-tie
that the program's rounding decided the other way has a gap of the size
of the program's logit error; a token from another slot, position or
ring offset sits several standard deviations of the logits (0.7) down.
Two numbers are compared, each under its own limit (config.json,
"check"): the widest gap (`gap_max`: structure) and `err_scale`
(precision): the scale s of the program's logit error, in logit units,
that best explains WHICH near-ties were decided the other way. At each
served position the reference knows the spacing d between its two best
logits; a program whose difference of those two logits is off by a
normal error of scale s serves the other token with probability
Q(d / s). s is fitted by maximum likelihood over all served positions,
so the hundreds of near-ties that did NOT flip count as well as the
dozens that did. The mean gap (`gap_mean`, printed) carries the same
signal but only through the flipped tokens: it swings by a quarter from
seed to seed where `err_scale` swings by a tenth (PERF.md, Findings).

No token is compared for equality. The share of served tokens that are
the reference's exact argmax is printed as information."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import erfc

from benchmark.lib import common

_HERE = os.path.dirname(os.path.abspath(__file__))
reference = common.load_module(os.path.join(_HERE, "reference.py"))


def sample(finished, seed, n):
    """n of the finished requests, drawn from the seed, the longest (by
    prompt + served tokens) always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["prompt"])
                                   + len(finished[i]["tokens"])))
    rest = order[1:]
    rng = common.host_rng(seed, 7)
    pick = [order[0]] + [rest[i] for i in
                         rng.permutation(len(rest))[:max(n - 1, 0)]]
    return [finished[i] for i in pick]


_SCORERS = {}


def _scorer(cfg, length, dtype):
    """jit: (weights, tokens[1, T], targets[T]) -> per position the
    readings of one forward pass; one program per (sizes, precision)."""
    key = (length, jnp.dtype(dtype).name, cfg["n_layer"], cfg["n_embd"],
           cfg["n_head"], cfg["vocab_size"])
    if key not in _SCORERS:
        _SCORERS[key] = _make_scorer(cfg, dtype)
    return _SCORERS[key]


def _make_scorer(cfg, dtype):
    def score(w, tokens, targets):
        logits = reference.forward(w, tokens, cfg, dtype=dtype)[0]
        best, arg = jax.lax.top_k(logits, 2)
        own = jnp.take_along_axis(logits, targets[:, None], 1)[:, 0]
        return best[:, 0], own, arg[:, 0], logits.std(-1), \
            best[:, 0] - best[:, 1]

    return jax.jit(score)


SCALES = np.geomspace(1e-5, 1.0, 401)


def err_scale(spacing, flipped):
    """The error scale s (logit units) under which the observed flips
    are likeliest: position t flips with probability Q(spacing_t / s)."""
    d = np.asarray(spacing, np.float64)[None, :]
    f = np.asarray(flipped, bool)[None, :]
    p = np.clip(0.5 * erfc(d / SCALES[:, None] / np.sqrt(2.0)),
                1e-300, 1.0 - 1e-16)
    ll = np.where(f, np.log(p), np.log1p(-p)).sum(1)
    return float(SCALES[int(np.argmax(ll))])


def _stats(gap, exact, sigma, spacing):
    g = np.concatenate(gap) if gap else np.zeros(0)
    if not g.size:
        return {"gap_max": float("nan"), "gap_mean": float("nan"),
                "err_scale": float("nan"), "tokens": 0, "exact_share": 0.0,
                "logit_std": 0.0}
    exact = np.concatenate(exact)
    return {
        "gap_max": float(g.max()), "gap_mean": float(g.mean()),
        "err_scale": err_scale(np.concatenate(spacing), ~exact),
        "tokens": int(g.size), "exact_share": float(exact.mean()),
        "logit_std": float(np.concatenate(sigma).mean()),
    }


def gaps(cfg, seed, requests, control=False):
    """Per served token of ``requests`` the reference's gap, reduced to
    {gap_max, gap_mean, err_scale, tokens, exact_share, logit_std}. With ``control``
    returns (served, control): the control judges, at the same positions
    of the same prompts and tokens, not the served token but the one the
    bfloat16 reference puts first - the control put in the program's
    place."""
    length = cfg["n_positions"]
    w = jax.jit(lambda k: reference.weights(cfg, k))(common.seed_key(seed))
    ref = _scorer(cfg, length, jnp.float32)
    low = _scorer(cfg, length, jnp.bfloat16) if control else None
    acc = {False: ([], [], [], []), True: ([], [], [], [])}
    for r in requests:
        seq = list(r["prompt"]) + list(r["tokens"])
        m, n = len(r["prompt"]), len(r["tokens"])
        tok = np.zeros((1, length), np.int32)
        tok[0, :len(seq)] = seq
        served = np.zeros(length, np.int32)
        served[:len(seq) - 1] = seq[1:]
        pos = slice(m - 1, m - 1 + n)
        targets = {False: served}
        if control:
            targets[True] = np.asarray(low(w, tok, served)[2])
        for which, tgt in targets.items():
            top, own, arg, std, spacing = (
                np.asarray(a) for a in ref(w, tok, tgt))
            acc[which][0].append((top - own)[pos])
            acc[which][1].append((arg == tgt)[pos])
            acc[which][2].append(std[pos])
            acc[which][3].append(spacing[pos])
    if control:
        return _stats(*acc[False]), _stats(*acc[True])
    return _stats(*acc[False])


def invariants(requests):
    """Counts that must hold for every request the window finished."""
    bad = 0
    for r in requests:
        n = len(r["tokens"])
        if not r.get("done") or not 1 <= n <= r["max_new_tokens"]:
            bad += 1
    return bad


def decide(cfg, seed, finished, counters, mix):
    """(correct, lines): every number compared beside its limit."""
    lim = cfg["check"]
    n = int(mix.get("check_requests", lim.get("requests", 32)))
    got = gaps(cfg, seed, sample(finished, seed, n))
    rows = [
        ("gap_max", got["gap_max"], "<=", lim["gap_max"]),
        ("err_scale", got["err_scale"], "<=", lim["err_scale"]),
        ("checked_tokens", got["tokens"], ">=", lim["min_tokens"]),
        ("bad_streams", invariants(finished), "<=", 0),
        ("compiles_in_window", counters["compiles_in_window"], "<=", 0),
        ("undrained", counters["undrained"], "<=", 0),
    ]
    info = (f"check: exact-argmax share of served tokens "
            f"{got['exact_share']:.4f} and their mean gap "
            f"{got['gap_mean']:.3e} (information only), logit std "
            f"{got['logit_std']:.3f}")
    return rows, info
