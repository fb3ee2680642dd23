"""`correct` for a training cell: the compiled step that the window
drives is followed through its first three steps by the plain reference.

Program side (taken at set-up, from the very object the window then
drives): the loss of each of three steps on three different seeded
batches, the per-leaf norm of the first gradient as the optimizer got it
(read from the optimizer's state after one step: Adam's first moment is
(1 - beta1) g, momentum's velocity is g), and the per-leaf norm of the
parameters' change over the three steps.

Reference side (after the window, when the program's state is freed):
the same three steps from the same benchmark-made weights, float32 at
`highest`, with the optimizer written out here.

Numbers compared (each under a limit of its own, in the config's
"check"): `loss_gap` (largest |loss - reference| over the three steps),
`grad_norm_gap` and `delta_norm_gap` (worst leaf: the gap between the
program's norm and the reference's, against the reference's norm of that
leaf or of the median leaf, whichever is larger), and `grad_diff` (the norm of the
difference between the program's first gradient and the reference's,
over all leaves together, against the reference's norm). The norm gaps are there for the
faults they catch (a step that leaves its state unchanged, a part of the
batch left out): rounding errors all but cancel in a norm, so a lower
precision hardly moves them (PERF.md, Findings, PR 23). `grad_diff` is
the one that tells precisions apart; taken by the worst leaf it swings
fourfold from seed to seed (the two NSP-head leaves see 128 rows), over
all leaves it is steady."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3


def sq_norms_by_name(named):
    return {k: float(v) for k, v in
            jax.jit(lambda t: {n: jnp.sum(jnp.square(a.astype(jnp.float32)))
                               for n, a in t.items()})(named).items()}


def program_readings(trainer, batches):
    """Drive ``trainer.step`` (the object the window will drive) through
    STEPS batches. Returns losses, squared norms of the first gradient
    and of the parameters' change, by parameter name."""
    step, names = trainer.step, trainer.accum_names
    p0 = jax.tree_util.tree_map(jnp.copy, dict(step.state["params"]))
    losses, grad_sq = [], None
    for i, batch in enumerate(batches[:STEPS]):
        losses.append(float(np.asarray(step(*batch)["loss"])))
        if i == 0:
            acc = step.state["opt"]["accums"][trainer.first_moment]
            scale = trainer.first_moment_scale
            grad_sq = {n: v / scale ** 2 for n, v in sq_norms_by_name(
                dict(zip(names, acc))).items()}
            # the first gradient itself, on the host until the window
            # has closed and the reference can be held beside it
            grad = {n: np.asarray(a, np.float32) / np.float32(scale)
                    for n, a in zip(names, acc)}
    diff = jax.jit(lambda a, b: {n: jnp.sum(jnp.square(
        a[n].astype(jnp.float32) - b[n].astype(jnp.float32))) for n in b})
    delta_sq = {k: float(v) for k, v in
                diff(dict(step.state["params"]), p0).items()}
    del p0
    return {"losses": losses, "grad_sq": grad_sq, "delta_sq": delta_sq,
            "grad": grad}


def _opt_step(opt, t):
    """jit: (params, grads, m, v) -> (params, m, v), the optimizer as the
    config states it."""
    if opt["kind"] == "adamw":
        b1, b2, eps, lr, wd = (opt["beta1"], opt["beta2"], opt["eps"],
                               opt["lr"], opt["weight_decay"])

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            new = p - lr * (m / (1 - b1 ** t)) / (
                jnp.sqrt(v / (1 - b2 ** t)) + eps) - lr * wd * p
            return new, m, v
    elif opt["kind"] == "momentum":
        mu, lr = opt["momentum"], opt["lr"]

        def upd(p, g, m, v):
            m = mu * m + g
            return p - lr * m, m, v
    else:
        raise ValueError(opt["kind"])

    def run(params, grads, ms, vs):
        out = jax.tree_util.tree_map(upd, params, grads, ms, vs)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    return jax.jit(run, donate_argnums=(0, 2, 3))


def reference_readings(ref, cfg, weights, batches, control=False, rng=None):
    """The same three steps by the reference. ``ref`` has
    value_and_grad(w, batch, cfg, control) and leaf_sq_norms(tree), and
    where the trainer names an ``rng`` (its step draws dropout masks),
    step_keys(rng, n) and a ``key`` argument to value_and_grad."""
    keys = ref.step_keys(rng, STEPS) if rng else [None] * STEPS
    params = weights
    keep0 = jax.tree_util.tree_map(jnp.copy, weights)
    ms = jax.tree_util.tree_map(jnp.zeros_like, weights)
    vs = jax.tree_util.tree_map(jnp.zeros_like, weights)
    losses, grad_sq, grad = [], None, None
    for i, batch in enumerate(batches[:STEPS]):
        loss, grads = ref.value_and_grad(
            params, batch, cfg, control,
            **({} if keys[i] is None else {"key": keys[i]}))
        losses.append(float(loss))
        if i == 0:
            grad_sq = {k: float(v) for k, v in
                       jax.jit(ref.leaf_sq_norms)(grads).items()}
            grad = jax.jit(ref.by_program_name)(grads)
        params, ms, vs = _opt_step(cfg["optimizer"], i + 1)(
            params, grads, ms, vs)
        del grads
    delta = jax.jit(lambda a, b: ref.leaf_sq_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))(params, keep0)
    return {"losses": losses, "grad_sq": grad_sq, "grad": grad,
            "delta_sq": {k: float(v) for k, v in delta.items()}}


def worst_leaf_gap(got_sq, ref_sq, among=None):
    """Worst leaf's |norm - reference norm| over max(reference norm of
    the leaf, of the median leaf). Returns (gap, leaf name). ``among``
    narrows the leaves judged (the median is still over all)."""
    names = sorted(ref_sq)
    if sorted(got_sq) != names:
        return float("inf"), "leaf names differ"
    ref = {n: ref_sq[n] ** 0.5 for n in names}
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, ""
    for n in names if among is None else among:
        gap = abs(got_sq[n] ** 0.5 - ref[n]) / max(ref[n], med, 1e-30)
        if not np.isfinite(gap):
            return float("inf"), n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def compare(got, ref):
    """{number: value} of the three comparisons, and where the worst
    leaves are."""
    loss_gap = max(abs(a - b) for a, b in zip(got["losses"], ref["losses"]))
    g, gw = worst_leaf_gap(got["grad_sq"], ref["grad_sq"])
    # a leaf whose true gradient is zero (a key bias: softmax ignores a
    # shift of every score) gets rounding noise for a gradient, and Adam
    # normalises that noise into a full-sized step: its change is judged
    # on no side. Such leaves are those whose reference gradient is under
    # a thousandth of the median leaf's.
    med = float(np.median([v ** 0.5 for v in ref["grad_sq"].values()]))
    live = [n for n, v in sorted(ref["grad_sq"].items())
            if v ** 0.5 >= 1e-3 * med]
    d, dw = worst_leaf_gap(got["delta_sq"], ref["delta_sq"], live)
    if not all(np.isfinite(got["losses"])):
        loss_gap = float("inf")
    diff_sq = jax.jit(lambda a, b: {n: jnp.sum(jnp.square(a[n] - b[n]))
                                    for n in b})(
        {n: jnp.asarray(a) for n, a in got["grad"].items()}, ref["grad"])
    worst, xw = 0.0, ""
    for n, v in sorted(diff_sq.items()):
        rel = float(v) ** 0.5 / max(ref["grad_sq"][n] ** 0.5, med, 1e-30)
        if rel > worst:
            worst, xw = rel, n
    x = (sum(float(v) for v in diff_sq.values())
         / max(sum(ref["grad_sq"].values()), 1e-60)) ** 0.5
    if not np.isfinite(x):
        x = float("inf")
    xw = f"{xw} ({worst:.4f})"
    return ({"loss_gap": loss_gap, "grad_norm_gap": g, "delta_norm_gap": d,
             "grad_diff": x},
            f"worst leaves: grad norm {gw}, grad diff {xw}, delta {dw}; losses "
            f"{[round(x, 5) for x in got['losses']]} vs reference "
            f"{[round(x, 5) for x in ref['losses']]}")
