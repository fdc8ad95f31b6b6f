"""Records ``scoped_trace.xplane.pb``, the trace ``test_bench_scopes.py``
reduces by the program's named scopes: on one TPU chip, inside a
``bench.window`` annotation, two training steps (``bench.step``, each read
back) of the program's ``build_train_step`` on one granite-3-2b layer at
published widths (d_model 2048, 32 query and 8 KV heads of 64, d_ff 8192),
batch 1 x 2048, float32 AdamW, block remat, as the training cells run it.

    python3 tests/bench/data/record_scoped_trace.py [<output path>] [<vocab>]

The vocabulary is granite's 49155 unless given.
"""
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import jax  # noqa: E402

import harness  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.launch.steps import build_train_step  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.blocks import RunConfig  # noqa: E402
from repro.models.common import materialize  # noqa: E402
from repro.optim import adamw  # noqa: E402

assert jax.devices()[0].platform == "tpu", "record this trace on a TPU"
out = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "scoped_trace.xplane.pb"
vocab = int(sys.argv[2]) if len(sys.argv) > 2 else 49155
cfg = get_config("granite-3-2b").replace(num_layers=1, vocab_size=vocab)
run = RunConfig(attn_impl="auto", remat="block")
opt = adamw.OptConfig(lr=3e-4, warmup_steps=10, total_steps=100000)
params = materialize(M.model_specs(cfg), jax.random.PRNGKey(0))
state = adamw.init_state(opt, params)
step = jax.jit(build_train_step(cfg, run, opt), donate_argnums=(0, 1))
tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 2049), 0, vocab)
batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
for _ in range(2):  # compile, then one step with donated buffers
    params, state, m = step(params, state, batch)
    float(m["loss"])
spans = harness.Spans()
with harness.profiled(lambda p: shutil.copy(p, out) and {}, {}):
    for _ in range(2):
        with spans.span("step"):
            params, state, m = step(params, state, batch)
            float(m["loss"])
print({e.name: round(e.dur, 6) for e in spans.events})
print("bytes", out.stat().st_size)
