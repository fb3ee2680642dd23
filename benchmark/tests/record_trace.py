"""Records tests/data/small.xplane.pb on the chip: 8 runs of a small
jitted function (a matmul, the program's fused layer-norm kernel, a
reduction) under the profiler, with the clock mark. Run on a TPU:
  python benchmark/tests/record_trace.py <out.xplane.pb>"""
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.lib import tracing  # noqa: E402
from paddle_tpu.ops.pallas import layernorm_residual  # noqa: E402


def main(out):
    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    x = jnp.ones((256, 256), jnp.bfloat16)
    g = jnp.ones((256,), jnp.float32)

    @jax.jit
    def small_step(x):
        y = x @ x
        return layernorm_residual(y, x, g, g, 1e-5).astype(jnp.float32).sum()

    small_step(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.join(ROOT, ".bench_tmp"))
    spans = tracing.Spans(tmp)
    spans.start_trace()
    for _ in range(8):
        small_step(x).block_until_ready()
    spans.stop_trace()
    shutil.copy(tracing.newest_xplane(spans.trace_dir), out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(os.path.getsize(out), "bytes")


if __name__ == "__main__":
    os.makedirs(os.path.join(ROOT, ".bench_tmp"), exist_ok=True)
    main(sys.argv[1])
