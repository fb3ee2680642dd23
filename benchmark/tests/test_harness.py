"""The harness end to end at tiny sizes on the CPU, skipping only its
look for a chip: a sound run is `correct`, a run whose timed path is
broken underneath is not, and a configuration, a mix, a cell and a
per-layer metric are picked up when added as files and entries."""
import io
import json
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.tests import tiny


@pytest.fixture()
def root(tmp_path):
    return tiny.checkout(tmp_path)


def _run(root, workload, seed=31, seconds=2.0, trace=0):
    out = io.StringIO()
    res = harness.run_cell(root, workload, seed, seconds, trace,
                           require_chip=False, out=out)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == res
    return res, out.getvalue()


def test_training_run_is_correct_and_prints_limits(root):
    res, text = _run(root, "bert-base.pretrain-seq128")
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert "check: grad_norm_gap" in text and "limit <=" in text


def test_step_that_leaves_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    from paddle_tpu.framework import jit as fjit

    real = fjit._apply_optimizer

    def unchanged(model, optimizer, state, grads, lr):
        _, opt = real(model, optimizer, state, grads, lr)
        return type(state["params"])(state["params"]), opt  # no update

    monkeypatch.setattr(fjit, "_apply_optimizer", unchanged)
    res, text = _run(root, "bert-base.pretrain-seq128")
    assert not res["correct"]
    assert "delta_norm_gap" in text and "FAILED" in text


def test_serving_run_is_correct_and_altered_token_is_not(root, monkeypatch):
    res, _ = _run(root, "gpt2-large.chat-overload", seconds=3.0)
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    from paddle_tpu.generation import GenerationEngine

    sound = GenerationEngine.step

    def altered(self, tokens, temps):
        nxt = sound(self, tokens, temps).copy()
        nxt[0] = (nxt[0] + 17) % 200 + 3  # slot 0 serves a wrong token
        return nxt

    monkeypatch.setattr(GenerationEngine, "step", altered)
    res, text = _run(root, "gpt2-large.chat-overload", seconds=3.0)
    assert not res["correct"] and "gap_max" in text


def test_cell_mix_config_and_metric_added_as_files(root):
    """A later PR adds files and entries only: a configuration (a copy
    of bert-base's directory under a new name), a mix, a cell, and a
    per-layer metric with its reader."""
    b = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(b, "configs", "bert-base"),
                    os.path.join(b, "configs", "bert-wide"))
    with open(os.path.join(b, "traffic", "pretrain-seq128.json")) as f:
        mix = json.load(f)
    mix["batch"] = 4
    with open(os.path.join(b, "traffic", "pretrain-b4.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(b, "layer_metrics", "steps_done.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['res']['steps']\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "bert-wide", "source": "test",
        "file": "benchmark/configs/bert-wide/config.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "bert-wide.pretrain-b4", "config": "bert-wide",
        "traffic": "pretrain-b4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "bert-base.pretrain-seq128" in m["workloads"]:
            m["workloads"].append("bert-wide.pretrain-b4")
    bench["per_layer"].append({
        "name": "steps_done", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry points",
        "moves": "train_samples_per_s",
        "workloads": ["bert-wide.pretrain-b4"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    res, _ = _run(root, "bert-wide.pretrain-b4", trace=1)
    assert res["correct"]
    assert res["metrics"]["steps_done"]["value"] == res["attempted"]
    assert "step_ms.train" in res["metrics"]
    assert "busy_s" in res["device"] and "breakdown" in res


def test_no_chip_means_no_result(root, capsys):
    with pytest.raises(SystemExit) as e:
        harness.run_cell(root, "bert-base.pretrain-seq128", 1, 1.0, 0)
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out
