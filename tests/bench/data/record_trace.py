"""Records ``small_trace.xplane.pb``, the trace ``test_bench_trace.py``
reduces: on one TPU chip, inside a ``bench.window`` annotation, three
rounds of a bf16 matrix product read back to the host (``bench.step``), a
10 ms host sleep with nothing queued (``bench.host_wait``), and an
elementwise program (``bench.step2``).

    python3 tests/bench/data/record_trace.py [<output path>]
"""
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "bench"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402

assert jax.devices()[0].platform == "tpu", "record this trace on a TPU"
x = jnp.ones((2048, 2048), jnp.bfloat16)
f = jax.jit(lambda a: (a @ a).astype(jnp.float32).sum())
g = jax.jit(lambda a: jnp.tanh(a) * 2)
float(f(x))
g(x).block_until_ready()
out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "small_trace.xplane.pb"
spans = harness.Spans()
with harness.profiled(lambda p: shutil.copy(p, out) and {}, {}):
    for _ in range(3):
        with spans.span("step"):
            float(f(x))
        with spans.span("host_wait"):
            time.sleep(0.01)
        with spans.span("step2"):
            g(x).block_until_ready()
print({e.name: round(e.dur, 6) for e in spans.events})
print("bytes", out.stat().st_size)
