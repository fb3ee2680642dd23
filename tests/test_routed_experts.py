"""RoutedExperts' two later options - `activation="relu2"` (an expert of
two matrices, no gate) and `latent_size` (the routed experts work in a
narrower width, one down- and one up-projection a layer) - and the
proof that off the chip the layer is what it was before the experts'
kernel (ops/pallas/grouped_experts.py, taken on a TPU only): the
layer's forward and `in_chunks` as they stood at the parent (PR 40's,
kept below word for word) put in the new ones' place lower every ring
program of the four families that use the layer to the same text.

The plain whole layer is the benchmark reference's
(benchmark/configs/nemotron-3-super-120b/reference.py `_moe`, every
expert held), which imports nothing of the program."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.errors import InvalidArgumentError
from paddle_tpu.parallel.moe import RoutedExperts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(ROOT, "benchmark", "configs",
                         "nemotron-3-super-120b", "reference.py"),
            "nemotron_reference")
H, L, F, FS, E, K = 32, 16, 24, 40, 16, 6


def _layer(held, **kw):
    return RoutedExperts(H, F, E, K, held=held, shared_width=FS,
                         score="sigmoid", norm_topk_prob=True,
                         routed_scaling_factor=5.0, selection_bias=True,
                         activation="relu2", latent_size=L,
                         initializer_range=0.2, **kw)


def _whole(seed=7):
    from paddle_tpu.framework.random import seed as set_seed

    set_seed(seed)
    m = _layer(None)
    m.select_bias._array = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed), (E,), jnp.float32)
    return m


def _share(whole, first, count):
    m = _layer((first, count))
    for name, p in whole.named_parameters():
        a = p._array
        getattr(m, name)._array = a[first:first + count] \
            if name in ("w_up", "w_down") else a
    return m


def test_relu2_latent_leaves_are_two_a_held_expert_and_two_projections():
    m = _layer((4, 4))
    shapes = {n: tuple(p._array.shape) for n, p in m.named_parameters()}
    assert shapes == {
        "router": (H, E), "select_bias": (E,), "latent_down": (H, L),
        "latent_up": (L, H), "w_up": (4, L, F), "w_down": (4, F, L),
        "shared_up": (H, FS), "shared_down": (FS, H)}
    with pytest.raises(InvalidArgumentError, match="activation"):
        RoutedExperts(H, F, E, K, activation="gelu")


@pytest.mark.parametrize("parts", [4, 2, 1])
def test_the_shares_add_up_to_the_uncut_reference(parts):
    """`parts` members of a group, each holding E / parts experts: their
    results, the shared expert counted once, add up to the whole layer
    as the plain reference computes it (a loop over all 16 experts in
    the latent, the up-projection after the weighted sum)."""
    whole = _whole()
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 9, H), jnp.float32)
    w = {n: p._array for n, p in whole.named_parameters()}
    cfg = dict(experts_held=[0, E], num_experts_per_tok=K,
               norm_topk_prob=True, routed_scaling_factor=5.0)
    with jax.default_matmul_precision("highest"):
        want = REF._moe(x.reshape(-1, H), w, {"held": E}, cfg,
                        REF._mm(False)).reshape(x.shape)
        shared = REF._relu2(x @ w["shared_up"]) @ w["shared_down"]
    count = E // parts
    got, pairs = 0.0, 0
    for j in range(parts):
        m = _share(whole, j * count, count)
        got = got + m(x)
        pairs += int(m.last_load.sum())
    got = got - (parts - 1) * shared
    assert pairs == 3 * 9 * K                 # every pair landed once
    assert np.asarray(want).std() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_latent_is_what_the_experts_see_and_the_router_does_not():
    """Zeroing the down-projection silences the routed experts and
    leaves the choice of experts and the shared expert as they were."""
    m = _whole()
    x = jax.random.normal(jax.random.PRNGKey(2), (5, H), jnp.float32)
    idx, w = m.route(x)
    full = m(x)
    m.latent_down._array = jnp.zeros_like(m.latent_down._array)
    idx2, w2 = m.route(x)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_array_equal(w, w2)
    only_shared = REF._relu2(x @ m.shared_up._array) @ m.shared_down._array
    np.testing.assert_allclose(m(x), only_shared, atol=1e-5)
    assert np.abs(np.asarray(full - only_shared)).max() > 1e-2


# -- the defaults are what they were -----------------------------------------

def _forward_as_it_was(self, x, valid=None):
    """RoutedExperts.forward at PR 40 (commit 08a77ec), word for word."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    t, k, n = x.shape[0], self.top_k, self.count
    with jax.named_scope("moe_experts"):
        idx, w = self.route(x)
        local = idx - self.first
        here = (local >= 0) & (local < n)
        # pairs in expert order, those of other chips' experts last
        group = jnp.where(here, local, n).reshape(-1).astype(jnp.int32)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        if self.latent_size:
            with jax.named_scope("moe_latent"):
                xs = jnp.matmul(x, self.latent_down._array)[order // k]
        else:
            xs = x[order // k]
        if self.gated:
            gate = jax.lax.ragged_dot(xs, self.w_gate._array, sizes)
            up = jax.lax.ragged_dot(xs, self.w_up._array, sizes)
            hid = jax.nn.silu(gate.astype(jnp.float32)) \
                * up.astype(jnp.float32)
        else:
            hid = jnp.square(jax.nn.relu(jax.lax.ragged_dot(
                xs, self.w_up._array, sizes).astype(jnp.float32)))
        out = jax.lax.ragged_dot(hid.astype(x.dtype),
                                 self.w_down._array, sizes)
        # back to (token, choice) order; rows past the last group are
        # whatever the kernel left there and are masked, not scaled
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        pair = out[back].reshape(t, k, -1).astype(jnp.float32)
        y = jnp.where(here[..., None], pair * w[..., None], 0.0).sum(1)
        if self.latent_size:
            with jax.named_scope("moe_latent"):
                y = jnp.matmul(y.astype(x.dtype), self.latent_up._array,
                               preferred_element_type=jnp.float32)
        if valid is None:
            self.last_load = sizes
        else:
            counted = here & valid.reshape(-1)[:, None]
            self.last_load = jnp.zeros((n + 1,), jnp.int32).at[
                jnp.where(counted, local, n).reshape(-1)].add(1)[:n]
        if self.zero_experts:
            with jax.named_scope("moe_zero"):
                zero = idx >= self.num_experts
                y = y + jnp.where(zero, w, 0.0).sum(
                    -1, keepdims=True) * x.astype(jnp.float32)
                if valid is not None:
                    zero = zero & valid.reshape(-1)[:, None]
                self.last_zero = zero.sum().astype(jnp.int32)
        if self.shared_width:
            if self.gated:
                hid = jax.nn.silu(jnp.matmul(
                    x, self.shared_gate._array,
                    preferred_element_type=jnp.float32)) * jnp.matmul(
                    x, self.shared_up._array,
                    preferred_element_type=jnp.float32)
            else:
                hid = jnp.square(jax.nn.relu(jnp.matmul(
                    x, self.shared_up._array,
                    preferred_element_type=jnp.float32)))
            y = y + jnp.matmul(hid.astype(x.dtype),
                               self.shared_down._array,
                               preferred_element_type=jnp.float32)
        return y.astype(x.dtype).reshape(shape)


def _in_chunks_as_it_was(self, x, valid=None, chunk=1024):
    """RoutedExperts.in_chunks at PR 40 (commit 08a77ec), word for word."""
    b, t, h = x.shape
    if t <= chunk or t % chunk:
        return self(x, valid=valid)
    if valid is None:
        valid = jnp.ones((b, t), bool)

    def one(c):
        y = self(c[0], valid=c[1])
        return y, self.last_load, (
            self.last_zero if self.zero_experts
            else jnp.zeros((), jnp.int32))

    out, loads, zeros = jax.lax.map(
        one, (x.reshape(b, -1, chunk, h).swapaxes(0, 1),
              valid.reshape(b, -1, chunk).swapaxes(0, 1)))
    self.last_load = loads.sum(0)
    if self.zero_experts:
        self.last_zero = zeros.sum(0)
    return out.swapaxes(0, 1).reshape(b, t, h)


_FAMILIES = {"solar-open2": "test_hybrid_moe.py",
             "k-exaone": "test_exaone_moe.py",
             "longcat-flash": "test_longcat_flash.py",
             "nemotron-h": "test_nemotron_h.py"}


def _programs(family):
    """{program: StableHLO text} of the family's toy engine (its own
    test file's `_model` / `_engine`): the decode step and a prefill
    bucket, as the engine lowers them."""
    t = _load(os.path.join(ROOT, "tests", _FAMILIES[family]),
              "family_" + family.replace("-", "_"))
    m, _ = t._model()
    eng = t._engine(m)
    calls = {"decode": eng._decode_call(np.zeros(eng.slots, np.int32),
                                        np.zeros(eng.slots, np.float32), 0),
             "prefill": eng._prefill_call(0, np.zeros(16, np.int32), 12,
                                          0.0, 0)}
    return {name: fn.lower(*make()).as_text()
            for name, (_, fn, make) in calls.items()}, m


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_the_defaults_lower_the_four_families_programs_as_before(
        family, monkeypatch):
    """With the forward and `in_chunks` of PR 40 in the layer's place the
    decode and the prefill program of each family lower to the same
    text, character for character, as with today's (off the chip the
    kernel's gate is closed: the three gated families never ask it, and
    `nemotron_h`, whose prompt here is two chunks of its expert layers,
    runs the two `ragged_dot` calls); and the whole model's logits are
    the same bits."""
    from paddle_tpu.models import nemotron_h

    monkeypatch.setattr(nemotron_h, "_MOE_CHUNK", 8)
    now, m = _programs(family)
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 64, (1, 21)))
    logits = np.asarray(m(toks)._array)
    monkeypatch.setattr(RoutedExperts, "forward", _forward_as_it_was)
    monkeypatch.setattr(RoutedExperts, "in_chunks", _in_chunks_as_it_was)
    then, m = _programs(family)
    assert set(now) == {"decode", "prefill"}
    for name in now:
        assert now[name] == then[name], (family, name)
    np.testing.assert_array_equal(np.asarray(m(toks)._array), logits)
