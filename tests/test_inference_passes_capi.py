"""Inference IR passes + C API tests.

Reference parity: inference/analysis/ir_pass_manager.cc (pass pipeline
behind switch_ir_optim), inference/capi/paddle_c_api.h + its C test
(inference/capi/tests), and the AnalysisConfig no-op warning contract.
"""
import ctypes
import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu import ops
from paddle_tpu.inference import Config, create_predictor


@pytest.fixture(autouse=True)
def _fresh_static_state():
    static.reset_default_programs()
    static.global_scope().clear()
    yield
    static.disable_static()
    static.reset_default_programs()
    static.global_scope().clear()


def _save_const_heavy_model(tmp_path):
    """A model with foldable constant subgraphs: weight transforms and
    literals not reachable from the feed."""
    static.enable_static()
    x = static.data("x", [None, 4], "float32")
    w = static.nn.create_parameter([4, 3], "float32")
    # foldable: transpose(w) then transpose back, scaled literal
    wt = ops.transpose(w, [1, 0])
    wtt = ops.transpose(wt, [1, 0])
    scale = ops.full([3], 2.0)
    y = ops.add(ops.matmul(x, wtt), scale)
    exe = static.Executor()
    exe.run_startup()
    feed = np.random.RandomState(0).randn(5, 4).astype("float32")
    ref = exe.run(feed={"x": feed}, fetch_list=[y])[0]
    path = str(tmp_path / "model")
    static.save_inference_model(path, ["x"], [y], exe)
    static.disable_static()
    static.reset_default_programs()
    static.global_scope().clear()
    return path, feed, ref


def test_ir_optim_folds_and_matches(tmp_path):
    path, feed, ref = _save_const_heavy_model(tmp_path)
    pred = create_predictor(Config(path))
    stats = pred.pass_stats
    assert stats["ops_after"] < stats["ops_before"], stats
    assert stats["folded"] >= 2, stats  # both transposes + full at least
    h = pred.get_input_handle("x")
    h.copy_from_cpu(feed)
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_ir_optim_off_keeps_graph(tmp_path):
    path, feed, ref = _save_const_heavy_model(tmp_path)
    cfg = Config(path)
    cfg.switch_ir_optim(False)
    pred = create_predictor(cfg)
    assert pred.pass_stats == {}
    h = pred.get_input_handle("x")
    h.copy_from_cpu(feed)
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_dead_op_elimination():
    from paddle_tpu.inference.passes import dead_op_elimination_pass

    static.enable_static()
    x = static.data("x", [2], "float32")
    live = ops.add(x, ops.full([2], 1.0))
    dead = ops.multiply(x, ops.full([2], 3.0))  # no fetch needs this
    dead2 = ops.exp(dead)
    prog = static.default_main_program()
    before = len(prog.global_block().ops)
    removed = dead_op_elimination_pass(prog, [live.name])
    assert removed >= 2, (before, removed)
    names = [o.type for o in prog.global_block().ops]
    assert "exp" not in names


def test_config_noops_warn():
    cfg = Config("/nonexistent")
    with pytest.warns(UserWarning, match="enable_use_gpu"):
        cfg.enable_use_gpu(100, 0)
    with pytest.warns(UserWarning, match="memory_optim"):
        cfg.enable_memory_optim()
    with pytest.warns(UserWarning, match="tensorrt"):
        cfg.enable_tensorrt_engine()


def test_rng_ops_never_fold(tmp_path):
    """Dropout-style RNG ops must not be precomputed at load time."""
    from paddle_tpu.inference.passes import constant_folding_pass

    static.enable_static()
    x = static.data("x", [4], "float32")
    noise = ops.normal(0.0, 1.0, shape=[4])
    y = ops.add(x, noise)
    prog = static.default_main_program()
    scope = static.global_scope()
    folded = constant_folding_pass(prog, scope, ["x"], [y.name])
    types = [o.type for o in prog.global_block().ops]
    assert any("gaussian" in t for t in types), types


# -- C API -------------------------------------------------------------------


C_TEST_SRC = r"""
#include <stdio.h>
#include <stdlib.h>

extern const char* PD_GetLastError();
extern int PD_Init();
extern void* PD_CreatePredictor(const char*);
extern void PD_DeletePredictor(void*);
extern int PD_GetInputNum(void*);
extern int PD_GetOutputNum(void*);
extern const char* PD_GetInputName(void*, int);
extern const char* PD_GetOutputName(void*, int);
extern int PD_SetInputFloat(void*, const char*, const float*,
                            const long long*, int);
extern int PD_Run(void*);
extern int PD_GetOutputNdim(void*, const char*);
extern int PD_GetOutputShape(void*, const char*, long long*);
extern int PD_CopyOutputFloat(void*, const char*, float*, long long);

#define CHECK(cond) \
  if (!(cond)) { \
    fprintf(stderr, "FAIL %s: %s\n", #cond, PD_GetLastError()); \
    return 1; \
  }

int main(int argc, char** argv) {
  CHECK(PD_Init() == 0);
  void* pred = PD_CreatePredictor(argv[1]);
  CHECK(pred != NULL);
  CHECK(PD_GetInputNum(pred) == 1);
  CHECK(PD_GetOutputNum(pred) == 1);
  const char* in_name = PD_GetInputName(pred, 0);
  CHECK(in_name != NULL);

  float data[20];
  for (int i = 0; i < 20; ++i) data[i] = (float)i * 0.1f;
  long long shape[2] = {5, 4};
  CHECK(PD_SetInputFloat(pred, in_name, data, shape, 2) == 0);
  CHECK(PD_Run(pred) == 0);

  const char* out_name = PD_GetOutputName(pred, 0);
  int ndim = PD_GetOutputNdim(pred, out_name);
  CHECK(ndim == 2);
  long long oshape[2];
  CHECK(PD_GetOutputShape(pred, out_name, oshape) == 0);
  long long numel = oshape[0] * oshape[1];
  float* buf = (float*)malloc(numel * sizeof(float));
  CHECK(PD_CopyOutputFloat(pred, out_name, buf, numel) == 0);
  printf("shape %lld %lld\n", oshape[0], oshape[1]);
  for (long long i = 0; i < numel; ++i) printf("%.6f\n", buf[i]);
  free(buf);
  PD_DeletePredictor(pred);
  return 0;
}
"""


@pytest.mark.slow
def test_c_api_end_to_end(tmp_path):
    """Reference capi test pattern: a real C program creates a predictor
    from a saved model, runs it, and its outputs match Python's."""
    path, feed, ref = _save_const_heavy_model(tmp_path)

    from paddle_tpu._native.capi import build_capi

    so = build_capi()
    cache_dir = os.path.dirname(so)
    c_src = tmp_path / "main.c"
    c_src.write_text(C_TEST_SRC)
    exe_path = str(tmp_path / "c_infer")
    libdir = sysconfig.get_config_var("LIBDIR")
    ldver = sysconfig.get_config_var("LDVERSION")
    subprocess.run(
        ["gcc", str(c_src), "-o", exe_path, so,
         f"-L{libdir}", f"-lpython{ldver}",
         f"-Wl,-rpath,{libdir}", f"-Wl,-rpath,{cache_dir}"],
        check=True, capture_output=True,
    )
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo  # this checkout only: a correctness test
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [exe_path, path], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.strip().splitlines() if l]
    assert lines[0].startswith("shape 5 3")
    got = np.array([float(v) for v in lines[1:]]).reshape(5, 3)

    # python-side reference with the same feed values
    feed2 = np.arange(20, dtype=np.float32).reshape(5, 4) * 0.1
    pred = create_predictor(Config(path))
    pred.get_input_handle("x").copy_from_cpu(feed2)
    pred.run()
    want = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_go_client_symbols_match_c_abi():
    """The Go client (go/paddle_tpu/, reference go/paddle parity) is
    build-tag-gated because no Go toolchain ships in CI — but its cgo
    extern declarations must stay in sync with capi.cpp. Parse both and
    compare symbol sets."""
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    go_dir = os.path.join(repo, "go", "paddle_tpu")
    go_decl = set()
    for fn in os.listdir(go_dir):
        if not fn.endswith(".go"):
            continue
        src = open(os.path.join(go_dir, fn)).read()
        go_decl |= set(re.findall(r"extern [^;]*?(PD_\w+)\s*\(", src))
    capi = open(os.path.join(
        repo, "paddle_tpu", "_native", "capi.cpp")).read()
    c_syms = set(re.findall(r"^(?:\w[\w* ]*?)(PD_\w+)\s*\(", capi,
                            re.MULTILINE))
    missing = go_decl - c_syms
    assert not missing, f"Go client references absent C symbols: {missing}"
    # the Go client must cover the whole documented fetch surface
    for required in ["PD_CreatePredictor", "PD_Run", "PD_CopyOutputFloat",
                     "PD_SetInputFloat", "PD_SetInputInt64",
                     "PD_GetOutputShape"]:
        assert required in go_decl, f"Go client missing {required}"
