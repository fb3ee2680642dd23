"""The three gated families behind the experts' kernel
(ops/pallas/grouped_experts.py), which a TPU takes and the CPU does not:
with the layer's gate opened here every expert layer of a decode step
and of a prompt is one interpreted `grouped_experts` call (toy widths
that are no whole lanes: the interpreter does not mind). Each family's
own engine test still passes - the served tokens are the plain
reference's - and the programs return the rows the kernel's tiles
multiplied beside the pairs, from inside the models' chunk loops too."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# family: its test file, its engine test, the module whose constant cuts
# a long prompt's expert layers into chunks (None: the layer runs whole)
FAMILIES = {
    "solar-open2": ("test_hybrid_moe.py",
                    "test_engine_serves_the_references_own_tokens", None),
    "k-exaone": (
        "test_exaone_moe.py",
        "test_engine_serves_the_references_own_tokens_two_slots_at_once",
        ("paddle_tpu.models.exaone_moe", "_FFN_CHUNK")),
    "longcat-flash": (
        "test_longcat_flash.py",
        "test_engine_serves_the_references_own_tokens_two_slots_at_once",
        ("paddle_tpu.models.longcat_flash", "_MOE_CHUNK")),
}


def _family(name):
    spec = importlib.util.spec_from_file_location(
        "seam_" + name.replace("-", "_"),
        os.path.join(ROOT, "tests", FAMILIES[name][0]))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def opened(monkeypatch):
    """The layer's gate open; the shapes each call asked about."""
    calls = []
    monkeypatch.setattr(moe, "can_emit_mosaic", lambda: True)
    monkeypatch.setattr(moe, "grouped_relu2_supported",
                        lambda *a: calls.append(a) or True)
    return calls


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_engine_serves_the_references_tokens_through_the_kernel(
        family, opened):
    from paddle_tpu import profiler

    t = _family(family)
    m, w = t._model()
    getattr(t, FAMILIES[family][1])((m, w))
    layers = len(m.routing_stats()["pairs"])
    # every expert layer of every program asked, with its gate's shape
    assert opened and len(opened) % layers == 0
    assert all(len(a) == 5 and a[4] == a[1] for a in opened)
    assert m.routing_stats()["tile_rows"].shape == (layers,)
    eng = t._engine(m).warmup()
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    try:
        eng.admit(1, t._tokens(13).tolist())
        eng.step(np.zeros(eng.slots, np.int32),
                 np.zeros(eng.slots, np.float32))
        got = {ev["name"]: ev["args"]["value"]
               for ev in profiler.counter_samples()}
    finally:
        profiler.stop_profiler()
        profiler.reset_profiler()
    pairs, rows = got["moe::pairs_here"], got["moe::tile_rows"]
    assert len(pairs) == len(rows) == layers
    assert all(p <= r and r % 8 == 0 for p, r in zip(pairs, rows))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_prompt_in_chunks_carries_the_count_out_of_the_loop(
        family, monkeypatch):
    """A forward of 16 tokens with the family's chunk at 8: the expert
    layers run inside `jax.lax.map`, and the program returns the chunks'
    summed `tile_rows` with the other statistics (a count left on the
    layer by the loop's body would be a leaked tracer). The logits are
    the `ragged_dot` path's to the order of the sums; the pairs are the
    same; two 8-token chunks multiply no fewer rows than their pairs."""
    t = _family(family)
    m, _ = t._model()
    if FAMILIES[family][2]:
        module, name = FAMILIES[family][2]
        monkeypatch.setattr(importlib.import_module(module), name, 8)
    toks = jnp.asarray(t._tokens(16, seed=3))[None]

    @jax.jit
    def forward(toks):
        return m(toks)._array, m.routing_stats()

    want, plain = forward(toks)
    assert "tile_rows" not in plain
    monkeypatch.setattr(moe, "can_emit_mosaic", lambda: True)
    monkeypatch.setattr(moe, "grouped_relu2_supported", lambda *a: True)
    jax.clear_caches()
    got, stats = forward(toks)
    np.testing.assert_allclose(got, want, atol=2e-4 * float(
        np.abs(want).max()))
    np.testing.assert_array_equal(stats["pairs"], plain["pairs"])
    assert stats["tile_rows"].shape == stats["pairs"].shape
    assert (np.asarray(stats["tile_rows"]) >= np.asarray(stats["pairs"])).all()
    assert (np.asarray(stats["tile_rows"]) % 8 == 0).all()


@pytest.mark.parametrize("on_tpu,imported,started", [
    (True, False, True),    # a layer that will emit the kernel: import now
    (True, True, False),    # the modules are there already
    (False, False, False),  # off the chip nothing asks for them
])
def test_an_expert_layer_starts_the_kernels_imports_when_it_is_built(
        on_tpu, imported, started, monkeypatch):
    """Where a Mosaic call may be emitted, building a `RoutedExperts`
    layer starts the first import of the Pallas TPU modules on a thread
    of its own (PERF.md, PR 47: the import is 1.4-1.5 s, which else
    falls inside the first program's trace, on the thread a server's
    warm-up waits for); nowhere else, and once."""
    import sys
    import threading

    from paddle_tpu.ops.pallas import _platform

    name = "a_package_that_stands_for_pallas"
    asked = []
    monkeypatch.setattr(_platform, "_PALLAS", name)
    monkeypatch.setattr(_platform, "on_tpu_platform", lambda: on_tpu)
    monkeypatch.setattr(_platform, "import_module", asked.append)
    if imported:
        monkeypatch.setitem(sys.modules, name, object())
    moe.RoutedExperts(32, 64, 8, 2)
    for th in threading.enumerate():
        if th.name == "pallas-import":
            th.join()
    assert asked == [name + ".tpu"] * started
