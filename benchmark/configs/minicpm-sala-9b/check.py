"""Decides `correct` for the served `minicpm-sala-9b` cells, after the
window has closed and the engine's cache and weights are freed.

The method is `solar-open2-250b`'s check.py's, and its sampling, its
maximum-likelihood `err_scale`, its reduction and its invariants are
taken from that file: a seeded sample of the finished requests, the
longest among them, goes through the reference once each (prompt +
served tokens, teacher-forced: the reference's FULL forward pass, no
cache, no chunks, the recurrence token by token, every query's blocks
chosen as the six steps are written), and at every served position the
gap
  reference's largest logit - reference's logit of the served token
is read. A served token came through a blocked prefill into two layers'
K/V and pooled rings and six float32 states, and then through the
one-token step of every slot at once, slots under `dense_len` beside
slots over it: a state handed to the wrong slot, a padded position that
advanced it, a decay applied twice, the rotation at the wrong position,
a query that attended a block it had not chosen shows as a gap of logit
size. Two numbers are compared, each under its own limit (config.json,
"check"): the widest gap (`gap_max`: structure) and `err_scale`
(precision): the scale of the program's logit error that best explains
which near-ties were decided the other way. No token is compared for
equality.

One thing is this configuration's own: a top-k over float scores can
keep another block where two blocks' scores nearly tie, and with seeded
weights the softmax over thousands of keys is nearly flat, so the logits
hardly move with the set of blocks: the two rows above cannot see a
selection that forgets its forced blocks: so it was expected, and on
the chip they DO see it (dense attention in place of the selection, the
forced blocks left out and top-32 for top-64 read four to five times
the sound program's `err_scale` and `gap_max`, over both limits:
config.json has the readings). Those two rows hold what was SERVED.
The third row, `chosen_set_diff_share`, does not: it holds the
library's FUNCTION to the reference. The program's
`nn.sparse_attention.select_blocks` and `pool_keys`, called here on the
reference's normalised queries and keys rounded to the program's dtype,
choose every scored position's blocks, and the row is the share of
(sparse layer, K/V head, scored position of `dense_len` or more) whose
set differs from the reference's in any block. It never sees what the
engine's programs chose (no stream carries that), so an engine that
calls another function, or this one wrongly, passes it; what it catches
is a change to the function itself, and how often bfloat16 operands
flip the last place of a top-64 (one position in twelve for the sound
function: config.json).

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
_SELECT_ROWS = 512


def program_choice(cfg, saw, start):
    """The blocks the program's selection keeps for the positions ``start
    ..`` whose normalised queries ``saw["q"] [R, hkv, g, d]`` and the
    sequence's keys ``saw["k"] [T, hkv, d]`` the reference computed:
    ``[R, hkv, blocks]`` bool, in blocks of queries."""
    from paddle_tpu.nn import sparse_attention as sa

    sc = cfg["sparse_config"]
    sparse = sa.SparseConfig(sc["kernel_size"], sc["kernel_stride"],
                             sc["block_size"], sc["init_blocks"],
                             sc["window_size"], sc["topk"], sc["dense_len"])
    dtype = cfg.get("program_dtype", "bfloat16")
    q = saw["q"].astype(dtype).transpose(1, 2, 0, 3)      # [hkv, g, R, d]
    pooled = sa.pool_keys(saw["k"].astype(dtype).transpose(1, 0, 2), sparse)
    hkv, g, rows, d = q.shape
    step = min(_SELECT_ROWS, rows)
    at = (start + jnp.arange(rows, dtype=jnp.int32)).reshape(-1, step)
    qs = jnp.moveaxis(q.reshape(hkv, g, -1, step, d), 2, 0)

    def block(args):
        qi, ti = args
        return sa.select_blocks(qi, pooled, jnp.broadcast_to(
            ti, (hkv, step)), sparse, d ** -0.5)          # [hkv, step, nb]

    got = jax.lax.map(block, (qs, at))
    return jnp.moveaxis(got, 1, 2).reshape(rows, hkv, -1)


def _scorer(cfg, length, control):
    """jit: (weights, tokens[T], start, targets[R]) -> per position of
    rows start .. start+R-1 the readings of one forward pass, the blocks
    each sparse layer's K/V heads chose there [L, R, hkv, blocks], and
    (float32 reference only) whether the program's selection chose
    otherwise [L, R, hkv]; one program per (length, precision)."""
    key = (length, bool(control), cfg["hidden_size"], cfg["vocab_size"],
           cfg["num_hidden_layers"])
    if key not in _SCORERS:
        rows = min(cfg["check"]["score_rows"], length)

        def score(w, tokens, start, targets):
            logits, seen = reference.forward(
                w, tokens, cfg, control=control, rows=(start, rows),
                detail=True)
            best, arg = jax.lax.top_k(logits, 2)
            own = jnp.take_along_axis(logits, targets[:, None], 1)[:, 0]
            chosen = jnp.stack([s["chosen"] for s in seen])
            other = None if control else jnp.stack([
                (program_choice(cfg, s, start) != s["chosen"]).any(-1)
                for s in seen])
            return (best[:, 0], own, arg[:, 0], logits.std(-1),
                    best[:, 0] - best[:, 1]), chosen, other

        _SCORERS[key] = jax.jit(score)
    return _SCORERS[key]


def _share(flags):
    """Share of True among the flags gathered, 0 where there are none."""
    flags = np.concatenate([f.ravel() for f in flags]) if flags \
        else np.zeros(0, bool)
    return float(flags.mean()) if flags.size else 0.0


def gaps(cfg, seed, requests, control=False):
    """Per served token of ``requests`` the reference's gap, reduced to
    {gap_max, gap_mean, err_scale, tokens, exact_share, logit_std,
    chosen_set_diff_share}. With ``control`` returns (served, control):
    the control judges, at the same positions of the same prompts and
    tokens, not the served token but the one the float8 reference puts
    first, and not the program's selection but the float8 reference's -
    the control put in the program's place."""
    lengths = sorted(cfg["check"]["score_lengths"])
    dense_len = cfg["sparse_config"]["dense_len"]
    w = reference.weights(cfg, common.seed_key(seed))
    acc = {False: ([], [], [], []), True: ([], [], [], [])}
    differ = {False: [], True: []}
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
        # scored positions at which the selection runs
        sparse = (start + np.arange(rows) + 1 >= dense_len)[pos]
        ref = _scorer(cfg, length, False)
        targets = {False: served}
        if control:
            fp8, fp8_chosen, _ = _scorer(cfg, length, True)(
                w, tok, start, served)
            targets[True] = np.asarray(fp8[2])
        for which, tgt in targets.items():
            got, chosen, other = ref(w, tok, start, tgt)
            top, own, arg, std, spacing = (np.asarray(a) for a in got)
            acc[which][0].append((top - own)[pos])
            acc[which][1].append((arg == tgt)[pos])
            acc[which][2].append(std[pos])
            acc[which][3].append(spacing[pos])
            if which:
                other = (np.asarray(fp8_chosen) != np.asarray(chosen)).any(-1)
            differ[which].append(np.asarray(other)[:, pos][:, sparse])
    out = {}
    for which in targets:
        out[which] = dict(_shared._stats(*acc[which]),
                          chosen_set_diff_share=_share(differ[which]),
                          chosen_sets=int(sum(f.size for f in differ[which])))
    return (out[False], out[True]) if control else out[False]


def decide(cfg, seed, finished, counters, mix):
    """(rows, info): every number compared beside its limit."""
    lim = cfg["check"]
    n = int(mix.get("check_requests", lim.get("requests", 4)))
    got = gaps(cfg, seed, sample(finished, seed, n))
    rows = [
        ("gap_max", got["gap_max"], "<=", lim["gap_max"]),
        ("err_scale", got["err_scale"], "<=", lim["err_scale"]),
        ("chosen_set_diff_share", got["chosen_set_diff_share"], "<=",
         lim["chosen_set_diff_share"]),
        ("checked_tokens", got["tokens"], ">=", lim["min_tokens"]),
        ("bad_streams", invariants(finished), "<=", 0),
        ("compiles_in_window", counters["compiles_in_window"], "<=", 0),
        ("undrained", counters["undrained"], "<=", 0),
    ]
    info = (f"check: exact-argmax share of served tokens "
            f"{got['exact_share']:.4f} and their mean gap "
            f"{got['gap_mean']:.3e} (information only), logit std "
            f"{got['logit_std']:.3f}; {got['chosen_sets']} chosen sets "
            f"compared")
    return rows, info
