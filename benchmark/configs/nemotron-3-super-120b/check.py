"""Decides `correct` for the served `nemotron-3-super-120b` cells, after
the window has closed and the engine's cache and weights are freed.

The method is `solar-open2-250b`'s check.py's, and its sampling, its
maximum-likelihood `err_scale`, its reduction and its invariants are
taken from that file: a seeded sample of the finished requests, the
longest among them, goes through the reference once each (prompt +
served tokens, teacher-forced: the reference's FULL forward pass, no
cache, no chunks, the recurrence token by token), and at every served
position the gap
  reference's largest logit - reference's logit of the served token
is read. A served token came through a chunked scan into a slot's five
states and convolution tails and its K/V rows, and then through the
one-token step of every slot at once, with the held experts' part
computed in the latent and projected up: a state handed to the wrong
slot, a padded position that advanced it, a tail off by a step, a chunk
border's decay left out, an expert's share mis-weighted or the latent
projected at the wrong place shows as a gap of logit size. Two numbers
are compared, each under its own limit (config.json, "check"): the
widest gap (`gap_max`: structure) and `err_scale` (precision): the
scale of the program's logit error that best explains which near-ties
were decided the other way. No token is compared for equality.

The reference runs one sequence at a time, padded on the right to one of
`check.score_lengths` (causal: padding changes no real position), and
gives the logits of `check.score_rows` positions from the last prompt
token on (a request's served tokens are fewer); one compiled program a
length and precision."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import common

_HERE = os.path.dirname(os.path.abspath(__file__))
reference = common.load_module(os.path.join(_HERE, "reference.py"))
_shared = common.load_module(os.path.join(
    os.path.dirname(_HERE), "solar-open2-250b", "check.py"))
sample, invariants, err_scale = (_shared.sample, _shared.invariants,
                                 _shared.err_scale)

_SCORERS = {}


def _scorer(cfg, length, control):
    """jit: (weights, tokens[T], start, targets[R]) -> per position of
    rows start .. start+R-1 the readings of one forward pass; one
    program per (length, precision)."""
    key = (length, bool(control), cfg["hidden_size"], cfg["vocab_size"],
           cfg["num_hidden_layers"])
    if key not in _SCORERS:
        rows = min(cfg["check"]["score_rows"], length)

        def score(w, tokens, start, targets):
            logits = reference.forward(w, tokens, cfg, control=control,
                                       rows=(start, rows))
            best, arg = jax.lax.top_k(logits, 2)
            own = jnp.take_along_axis(logits, targets[:, None], 1)[:, 0]
            return best[:, 0], own, arg[:, 0], logits.std(-1), \
                best[:, 0] - best[:, 1]

        _SCORERS[key] = jax.jit(score)
    return _SCORERS[key]


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
        rows = min(cfg["check"]["score_rows"], length)
        if n > rows:
            raise RuntimeError(f"{n} served tokens, check.score_rows {rows}")
        tok = np.zeros(length, np.int32)
        tok[:len(seq)] = seq
        start = min(m - 1, length - rows)
        served = np.zeros(rows, np.int32)
        served[m - 1 - start:m - 1 - start + n] = seq[m:]
        pos = slice(m - 1 - start, m - 1 - start + n)
        ref = _scorer(cfg, length, False)
        targets = {False: served}
        if control:
            targets[True] = np.asarray(
                _scorer(cfg, length, True)(w, tok, start, served)[2])
        for which, tgt in targets.items():
            top, own, arg, std, spacing = (
                np.asarray(a) for a in ref(w, tok, start, tgt))
            acc[which][0].append((top - own)[pos])
            acc[which][1].append((arg == tgt)[pos])
            acc[which][2].append(std[pos])
            acc[which][3].append(spacing[pos])
    if control:
        return _shared._stats(*acc[False]), _shared._stats(*acc[True])
    return _shared._stats(*acc[False])


def decide(cfg, seed, finished, counters, mix):
    """(rows, info): every number compared beside its limit."""
    lim = cfg["check"]
    n = int(mix.get("check_requests", lim.get("requests", 12)))
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
