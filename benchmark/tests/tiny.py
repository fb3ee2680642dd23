"""Tiny sizes of the three configurations, for the CPU tests: the
committed config files with only sizes changed."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

SIZES = {
    "bert-base": dict(hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, intermediate_size=128,
                      vocab_size=512, max_position_embeddings=64),
    "resnet50": dict(depths=[1, 1, 1, 1], num_classes=10),
    "gpt2-large": dict(n_embd=64, n_head=4, n_layer=2, vocab_size=211,
                       n_positions=128, n_ctx=128),
}
LOOSE = {
    "bert-base": dict(loss_gap=0.05, grad_norm_gap=0.2, delta_norm_gap=0.5,
                      grad_diff=0.2, loss_drop_min=-100.0),
    "resnet50": dict(loss_gap=1.0, grad_norm_gap=0.6, delta_norm_gap=0.6,
                     grad_diff=1.0, loss_drop_min=-100.0),
    "gpt2-large": dict(gap_max=0.03, err_scale=1e-4, min_tokens=8,
                       requests=8),
}
MIXES = {
    "pretrain-seq128": dict(batch=8, seq=32, masked=4, pool_batches=4,
                            sync_every=2, trace_steps=4),
    "imagenet-b128": dict(batch=8, image=32, pool_batches=4, sync_every=2,
                          trace_steps=4),
    "chat-overload": dict(rate_per_s=4.0, context_limit=128, drain_s=30.0,
                        prompt_tokens=dict(median=12, sigma=0.5, min=4,
                                           max=48),
                        output_tokens=dict(median=8, sigma=0.5, min=2,
                                           max=16), check_requests=8),
}


def config(name):
    with open(os.path.join(BENCH, "configs", name, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(SIZES[name])
    cfg["check"] = dict(cfg.get("check", {}), **LOOSE[name])
    if name == "gpt2-large":
        cfg["engine"] = dict(cfg["engine"], slots=4, cache_len=128,
                             prefill_buckets=[16, 32, 64])
    return cfg


def checkout(tmp):
    """A copy of the benchmark in ``tmp`` with tiny sizes, beside a link
    to the program: what `run.run_cell(root=tmp, ...)` needs."""
    root = str(tmp)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name in SIZES:
        path = os.path.join(root, "benchmark", "configs", name,
                            "config.json")
        with open(path, "w") as f:
            json.dump(config(name), f)
    for name, sizes in MIXES.items():
        path = os.path.join(root, "benchmark", "traffic", name + ".json")
        with open(path) as f:
            mix = json.load(f)
        mix.update(sizes)
        with open(path, "w") as f:
            json.dump(mix, f)
    return root
