"""Roofline share of the absorbed latent attention's decode kernel
alone: the least time a step's attention could take over the latent rows
it had to read (opcount/longcat_flash.py `mla_decode_least_s` of the
window's mean live latent rows, the third place of
`generation::kv_rows_read`: the larger of the rows' bytes over the HBM
bandwidth and their operations over the peak, as
`mla_decode_roofline_pct` takes it) over the self time of the Mosaic
calls named `mla_decode*` (paddle_tpu/ops/pallas/mla_decode.py; the row
writes, the projections and the absorbed products with `W_kvb` are not
in it) inside the decode program's runs, per run. The kernel fetches at
least the live rows, so this cannot pass 100. Nothing where the program
has no such counter or the trace no such instruction."""
import os

from benchmark.lib import common, program_time, tracing


def is_kernel(name, text):
    return name.startswith("mla_decode") and tracing.is_mosaic(name, text)


def read(ctx):
    cell = ctx["cell"]
    if ctx["peaks"] is None:
        return None
    oc = common.load_module(os.path.join(cell.dir, "opcount",
                                         cell.cfg["opcount"] + ".py"))
    tl = common.load_module(os.path.join(cell.dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    rows = [r[2] for r in program_time.counter_values(
        "generation::kv_rows_read", *tl.window_ns(ctx)) if len(r) > 2]
    if not rows or not hasattr(oc, "mla_decode_least_s"):
        return None
    got = program_time.time_inside(ctx["trace"], is_kernel, "decode")
    if got is None or not got[0]:
        return None
    least = oc.mla_decode_least_s(cell.cfg, sum(rows) / len(rows),
                                  ctx["peaks"])
    return 100.0 * least / (got[0] / got[1] / 1e9)
