"""Decides `correct` for the served `solar-open2-250b` cells, after the
window has closed and the engine's cache and weights are freed.

As `gpt2-large`'s check: what is compared is what the timed path
produced, tokens that the window served over HTTP, greedy. A seeded
sample of the finished requests, the longest among them, goes through
the reference once each (prompt + served tokens, teacher-forced: the
reference's FULL forward pass, no cache, the recurrence token by token),
and at every served position the gap
  reference's largest logit - reference's logit of the served token
is read. A served token came through a chunked prefill into a slot's
state and K/V rows and then through the cached decode, so a state handed
over wrongly, a padded position that advanced it, a ring row out of
place or an expert's share mis-weighted shows as a gap of logit size.
Two numbers are compared, each under its own limit (config.json,
"check"): the widest gap (`gap_max`: structure) and `err_scale`
(precision): the scale of the program's logit error that best explains
which near-ties were decided the other way (maximum likelihood over all
served positions; `gpt2-large`'s check.py has the derivation). No token
is compared for equality.

The reference runs one sequence at a time, padded on the right to one of
`check.score_lengths` (causal: padding changes no real position), one
compiled program a length and precision."""
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


def _scorer(cfg, length, control):
    """jit: (weights, tokens[T], targets[T]) -> per position the
    readings of one forward pass; one program per (length, precision)."""
    key = (length, bool(control), cfg["hidden_size"], cfg["vocab_size"],
           cfg["num_hidden_layers"])
    if key not in _SCORERS:
        def score(w, tokens, targets):
            logits = reference.forward(w, tokens, cfg, control=control)
            best, arg = jax.lax.top_k(logits, 2)
            own = jnp.take_along_axis(logits, targets[:, None], 1)[:, 0]
            return best[:, 0], own, arg[:, 0], logits.std(-1), \
                best[:, 0] - best[:, 1]

        _SCORERS[key] = jax.jit(score)
    return _SCORERS[key]


SCALES = np.geomspace(1e-5, 10.0, 481)


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
    {gap_max, gap_mean, err_scale, tokens, exact_share, logit_std}. With
    ``control`` returns (served, control): the control judges, at the
    same positions of the same prompts and tokens, not the served token
    but the one the float8 reference puts first - the control put in the
    program's place."""
    lengths = sorted(cfg["check"]["score_lengths"])
    w = reference.weights(cfg, common.seed_key(seed))
    acc = {False: ([], [], [], []), True: ([], [], [], [])}
    for r in requests:
        seq = list(r["prompt"]) + list(r["tokens"])
        m, n = len(r["prompt"]), len(r["tokens"])
        length = next(b for b in lengths if b >= len(seq))
        tok = np.zeros(length, np.int32)
        tok[:len(seq)] = seq
        served = np.zeros(length, np.int32)
        served[:len(seq) - 1] = seq[1:]
        pos = slice(m - 1, m - 1 + n)
        ref = _scorer(cfg, length, False)
        targets = {False: served}
        if control:
            targets[True] = np.asarray(
                _scorer(cfg, length, True)(w, tok, served)[2])
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
    """(rows, info): every number compared beside its limit."""
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
