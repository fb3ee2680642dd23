#!/usr/bin/env python
"""Opprof smoke (ISSUE 19): per-op device-time attribution, certified.

Replay-profiles the BERT-, ResNet-, and GPT-shaped static smoke programs
(the ir_opt_smoke builders) and checks, end to end:

1. **Attribution coverage** — the stamped-scope trace attribution folds
   >= 0.9 of scored device/runtime time back onto ``op.type#<block>/
   <index>`` identities on every smoke program;
2. **Time-accuracy closure** — roofline-predicted program time vs
   replay-measured time lands inside the documented envelope
   (``monitor.opprof.TIME_ACCURACY_ENVELOPE``) on every smoke program;
3. **Top-op sanity** — the top-1 op by FLOPs is a matmul/conv-family op
   and a matmul/conv-family op sits in the top-3 by measured time;
4. **Fusion wins are measured, not asserted** — ``analysis.optimizer.
   measure_pass_deltas`` shows the fused conv+bn+relu measurably faster
   than the 3-op chain it replaced on the ResNet smoke;
5. **/profilez serves** — the debug endpoint returns the populated
   profile over HTTP (``?program=``/``?topk=`` views, 404 on unknown).

Run: ``make opprof-smoke`` (wired into ``tools/build_and_test.sh check``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

# matmul/conv-family registry op types: the compute-dense ops any real
# profile of these programs must rank at the top by FLOPs
_DENSE_FAMILY = ("matmul", "mul", "conv2d", "fused_conv_bn_relu",
                 "matmul_int8")


def _check(name, ok, detail=""):
    status = "ok" if ok else "FAIL"
    print(f"[opprof-smoke] {name}: {status} {detail}")
    if not ok:
        raise SystemExit(f"opprof smoke failed: {name} {detail}")


def _load_builders():
    """The ir_opt_smoke program builders."""
    spec = importlib.util.spec_from_file_location(
        "ir_opt_smoke",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "ir_opt_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _profile_one(name, build):
    import paddle_tpu.static as static
    from paddle_tpu.monitor import opprof

    static.global_scope().clear()
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        feeds, fetch = build()
    exe = static.Executor()
    exe.run_startup(startup)
    exe.run(main, feed=feeds, fetch_list=[fetch])
    prof = opprof.profile_program(main, feeds, name=name)
    print(f"[opprof-smoke] {name}: {prof['replayed_ops']}/{prof['n_ops']} "
          f"ops replayed, total {prof['total_us']:.1f}us, "
          f"coverage={prof['coverage']}, "
          f"time_accuracy={prof['time_accuracy']}")
    return prof


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.static as static
    from paddle_tpu.analysis import optimizer as iropt
    from paddle_tpu.monitor import opprof

    static.enable_static()
    builders = _load_builders()

    # 1+2+3) coverage, time-accuracy closure, top-op sanity on all three
    lo, hi = 1.0 / opprof.TIME_ACCURACY_ENVELOPE, opprof.TIME_ACCURACY_ENVELOPE
    for name, build in (("bert", builders.build_bert),
                        ("resnet", builders.build_resnet),
                        ("gpt", builders.build_gpt)):
        prof = _profile_one(name, build)
        _check(f"{name} attribution coverage >= 0.9",
               prof["coverage"] is not None and prof["coverage"] >= 0.9,
               f"(coverage {prof['coverage']})")
        _check(f"{name} time-accuracy within envelope",
               prof["time_accuracy"] is not None
               and lo <= prof["time_accuracy"] <= hi,
               f"({prof['time_accuracy']} in [{lo:.2f}, {hi:.1f}])")
        replayed = [r for r in prof["ops"] if r["replayed"]]
        by_flops = max(replayed, key=lambda r: r["flops"] or 0)
        by_time = sorted(replayed, key=lambda r: -r["time_us"])[:3]
        _check(f"{name} top-1 op by FLOPs is matmul/conv family",
               by_flops["op_type"] in _DENSE_FAMILY,
               f"({by_flops['scope']}, {by_flops['flops']:.0f} flops)")
        _check(f"{name} matmul/conv family in top-3 by time",
               any(r["op_type"] in _DENSE_FAMILY for r in by_time),
               f"({[r['scope'] for r in by_time]})")

    # 4) fused conv+bn+relu beats the 3-op chain it replaced, measured
    # per op through the same replay discipline (warmup=2, repeats=7:
    # best-of-N over enough repeats to shed scheduler noise on CI boxes)
    static.global_scope().clear()
    main_p, startup = static.Program(), static.Program()
    with static.program_guard(main_p, startup):
        feeds, fetch = builders.build_resnet()
    exe = static.Executor()
    exe.run_startup(startup)
    exe.run(main_p, feed=feeds, fetch_list=[fetch])
    fetch_name = fetch if isinstance(fetch, str) else fetch.name
    deltas = iropt.measure_pass_deltas(
        main_p, feeds, [fetch_name], level=1, name="resnet",
        warmup=2, repeats=7)
    _check("conv+bn+relu fusion rewrote the program", deltas["changed"],
           f"(passes {deltas['passes']})")
    chain_us = sum(
        deltas["deltas"].get(t, {}).get("before_us", 0.0)
        for t in ("conv2d", "batch_norm", "relu"))
    fused_us = deltas["deltas"].get(
        "fused_conv_bn_relu", {}).get("after_us", float("inf"))
    _check("fused conv+bn+relu measured faster than the 3-op chain",
           0.0 < fused_us < chain_us,
           f"(chain {chain_us:.1f}us -> fused {fused_us:.1f}us, "
           f"{chain_us / fused_us:.2f}x)")

    # 5) /profilez end to end over HTTP, populated from this very run
    import urllib.request

    from paddle_tpu import monitor

    srv = monitor.start_debug_server(port=0)
    try:
        body = json.load(urllib.request.urlopen(srv.url + "/profilez"))
        _check("/profilez serves the profile store",
               body["status"] == "ok"
               and {"bert", "resnet", "gpt"} <= set(body["programs"]),
               f"(programs {body['programs']})")
        body = json.load(urllib.request.urlopen(
            srv.url + "/profilez?program=resnet&topk=3"))
        _check("/profilez ?program=/?topk= views",
               body["program"] == "resnet" and len(body["ops"]) == 3
               and body["summary"]["coverage"] is not None,
               f"(top op {body['ops'][0]['scope']})")
    finally:
        monitor.stop_debug_server()

    print("[opprof-smoke] PASS")


if __name__ == "__main__":
    main()
