"""Tiny versions of the benchmark's cells, for the CPU tests: the committed
configuration and traffic files with their widths, depth and sizes cut,
and the program switched to float32, so that a sound run agrees with the
plain reference to rounding and ``TINY_LIMITS`` can be tight.  The cells'
own limits are set from readings at their full size on the chip
(``PERF.md``)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

TINY = {
    "granite-3-2b.l8": (
        dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
             head_dim=64, intermediate_size=512, num_hidden_layers=2,
             vocab_size=512),
        dict(num_layers=2, d_model=256, num_heads=4, num_kv_heads=2,
             head_dim=64, d_ff=512, vocab_size=512)),
    "mamba2-780m.l24": (
        dict(d_model=128, n_layer=2, vocab_size=512, d_state=16, headdim=32,
             chunk_size=32),
        dict(num_layers=2, d_model=128, vocab_size=512, ssm_state=16,
             ssm_head_dim=32, ssm_chunk=32)),
}
# float32 program against float32 reference on the CPU: gaps of 1e-6 to
# 1e-4 (rounding of different summation orders).  A tiny cell compares the
# numbers its committed limits file names, each at its limit here.
TINY_LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-2, "grad_median_gap": 1e-2,
               "change_gap": 1e-2, "embed_change_gap": 1e-2}


def tiny_config(name: str, dtype: str = "float32") -> dict:
    cfg = harness.config(name)
    widths, program = TINY[name]
    cfg.update(widths)
    cfg["program"] = dict(cfg["program"], replace=dict(program, dtype=dtype))
    return cfg


def tiny_cell(name: str) -> "harness.Cell":
    cell = harness.Cell.load(name)
    cell.config = tiny_config(next(
        w["config"] for w in harness.benchmark()["workloads"]
        if w["name"] == name))
    t = cell.traffic
    cell.traffic = dict(t, batch=4, seq=64, profile_steps=1,
                        tokens=dict(t["tokens"], doc_tokens=65,
                                    shared_ranks=64))
    cell.limits = {k: TINY_LIMITS[k] for k in cell.limits}
    return cell


def cpu_device() -> dict:
    return harness.device_info(1)
