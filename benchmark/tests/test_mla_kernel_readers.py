"""The two readers of the latent decode kernel (PR 39): each on
written-out device events and counter samples, `None` where the program
has no such counter (the parent of PR 39) or the trace no such
instruction (XLA's path); then a rehearsal `--trace 1` run of the cell at
tiny size on the CPU, where the rings are read whole: the ratio is the
whole rings over the live rows and the kernel's share is left out."""
import os

import pytest

from benchmark.lib import common, tracing
from benchmark.tests import test_longcat_flash as cell_tests
from benchmark.tests import tiny
from paddle_tpu import profiler

root = cell_tests.root  # the cell's tiny checkout


def reader(name):
    return common.load_module(os.path.join(tiny.BENCH, "layer_metrics",
                                           name + ".py"))


US = 1e3
KERNEL = ("%mla_decode.9 = bf16[32,64,576]{2,1,0:T(8,128)(2,1)} "
          "custom-call(s32[32] %len, bf16[32,64,576] %q, bf16[32,576,8192]"
          ' %bitcast.142), custom_call_target="tpu_custom_call"')
EVENTS = [
    ("dynamic_update_slice.256", 0.0, 10 * US,
     "%dynamic_update_slice.256 = bf16[32,8192,576]{1,2,0} "
     "dynamic-update-slice(bf16[32,8192,576] %ring, bf16[1,1,576] %row)"),
    ("mla_decode.9", 10 * US, 30 * US, KERNEL),
    ("mla_decode.10", 50 * US, 20 * US, KERNEL.replace(".9", ".10")),
    # an XLA fusion that happens to carry the name, and another Mosaic
    # kernel: neither is the decode kernel
    ("mla_decode_fusion", 70 * US, 5 * US,
     "%mla_decode_fusion = bf16[32,64,512] fusion(bf16[32,64,576] %o)"),
    ("ragged-dot-none.3", 75 * US, 5 * US,
     "%ragged-dot-none.3 = bf16[384,2048] custom-call(bf16[384,6144] %x),"
     ' custom_call_target="tpu_custom_call"'),
    # the same kernel outside a decode run
    ("mla_decode.9", 210 * US, 30 * US, KERNEL),
]
MODULES = [("jit__decode_pure(1)", 0.0, 100 * US),
           ("jit__prefill_pure(2)", 200 * US, 50 * US)]


def ctx_of(events):
    class Cell:
        dir = tiny.BENCH
        cfg = common.load_json(os.path.join(
            tiny.BENCH, "configs", cell_tests.NAME, "config.json"))

    tr = tracing.DeviceTrace({
        "devices": {"/device:TPU:0": events}, "marks": [],
        "modules": {"/device:TPU:0": MODULES}})
    return {"cell": Cell, "trace": tr,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
            "res": {"window": (0.0, 1e9), "slots": 32}}


@pytest.fixture()
def samples():
    """`record(name, value)` while the profiler is on; reset after."""
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    yield profiler.record_counter
    profiler.stop_profiler()
    profiler.reset_profiler()


def test_rows_fetched_over_live_is_mean_over_mean(samples):
    read = reader("mla_rows_fetched_over_live").read
    ctx = ctx_of(EVENTS)
    samples("generation::kv_rows_read", [0, 0, 8 * 32 * 1000])
    samples("generation::kv_rows_read", [0, 0, 8 * 32 * 2000])
    # the parent's program: rows read, no rows fetched
    assert read(ctx) is None
    samples("generation::kv_rows_fetched", [0, 0, 8 * 32 * 1024])
    samples("generation::kv_rows_fetched", [0, 0, 8 * 32 * 2048])
    samples("generation::kv_rows_fetched", [4096, 128])  # another family's
    assert read(ctx) == pytest.approx(3072 / 3000)


def test_kernel_roofline_reads_the_mosaic_calls_inside_decode_runs(samples):
    read = reader("mla_kernel_roofline_pct").read
    # no counter (the parent): nothing, whatever the trace holds
    assert read(ctx_of(EVENTS)) is None
    samples("generation::kv_rows_read", [0, 0, 8 * 32 * 1500])
    samples("generation::kv_rows_read", [5, 7])   # another family's
    least = 8 * 32 * 1500 * 1152 / 819e9          # the rows' bytes bind
    assert read(ctx_of(EVENTS)) == pytest.approx(100 * least / 50e-6)
    # XLA's path: row writes and fusions, no such instruction
    assert read(ctx_of([EVENTS[0]] + EVENTS[3:5])) is None
    assert read(dict(ctx_of(EVENTS), peaks=None)) is None


def test_rehearsal_traced_run_prints_the_ratio_and_leaves_the_share_out(
        root):
    res, text = cell_tests._run(root, trace=1)
    assert res["correct"], text
    m = res["metrics"]
    assert "mla_kernel_roofline_pct" not in m
    assert m["mla_rows_fetched_over_live"]["unit"] == "ratio"
    # every ring read whole, and a slot has a live row at the least
    ring = cell_tests.config()["engine"]["cache_len"]
    assert 1.0 < m["mla_rows_fetched_over_live"]["value"] <= ring
