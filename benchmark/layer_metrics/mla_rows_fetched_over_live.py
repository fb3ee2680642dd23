"""Ring rows the absorbed latent attention brings from HBM over the rows
it had to read: the latent layers' part (third place) of the program's
`generation::kv_rows_fetched` samples in the window over that of its
`generation::kv_rows_read` samples, mean over mean. Both are counted a
decode step from the host's copy of `pos`, slots and attentions summed:
`kv_rows_read` the live rows, `kv_rows_fetched` the whole ring a slot
where XLA reads it (the ratio is then 1 / `kv_live_pct`) and the live
rows rounded up to whole key blocks where the decode kernel runs
(paddle_tpu/ops/pallas/mla_decode.py `rows_fetched`): 1.0 is the least,
and what lies over it is the last block's dead tail. Nothing where the
program has no such counter."""
import os

from benchmark.lib import common, program_time


def read(ctx):
    cell = ctx["cell"]
    tl = common.load_module(os.path.join(cell.dir, "layer_metrics",
                                         "host_gap_ms.serve.py"))
    window = tl.window_ns(ctx)
    fetched, live = (
        [r[2] for r in program_time.counter_values(name, *window)
         if len(r) > 2]
        for name in ("generation::kv_rows_fetched",
                     "generation::kv_rows_read"))
    if not fetched or not live or not sum(live):
        return None
    return (sum(fetched) / len(fetched)) / (sum(live) / len(live))
