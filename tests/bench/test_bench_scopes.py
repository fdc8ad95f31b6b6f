"""Device time by the program's named scopes: the ``op_name`` parser, the
``.xplane.pb`` decoder against ``ProfileData``, the sums on synthetic
events, and the scopes in the compiled training steps' HLO."""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import bench_tiny  # noqa: F401
import scope_reduce as sr
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "small_trace.xplane.pb"
SCOPED = DATA / "scoped_trace.xplane.pb"


@pytest.mark.parametrize("op_name, expected", [
    ("jit(train_step)/jvp(forward)/while/body/closed_call/block/attention/"
     "attention_core/bqkgd,bskd->bkgqs/dot_general",
     ("attention_core", "forward")),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/block/attention/attention_core/reduce_sum",
     ("attention_core", "backward")),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/rematted_computation/block/mlp/dot_general",
     ("mlp", "recompute")),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/block/mamba/ssd_scan/transpose;checkpoint/block/mamba/"
     "ssd_scan", ("ssd_scan", "backward")),
    ("jit(train_step)/transpose(jvp(forward))/head_loss/dot_general",
     ("head_loss", "backward")),
    ("jit(train_step)/transpose(jvp(forward))/convert_element_type",
     ("forward", "backward")),
    ("jit(train_step)/jvp(forward)/embed/jit(_take)/gather:",
     ("embed", "forward")),
    ("jit(train_step)/block/attention/cos", ("attention", "forward")),
    ("jit(train_step)/optimizer/jit(clip)/max", ("optimizer", "optimizer")),
    ("jit(<lambda>)/dot_general:", ("unscoped", "unscoped")),
    ("", ("unscoped", "unscoped")),
])
def test_op_name_to_component_and_pass(op_name, expected):
    assert sr.component_pass(op_name) == expected


def test_scope_path_keeps_every_scope_outermost_first():
    names, backward, recompute = sr.scope_path(
        "jit(s)/transpose(jvp(forward))/while/body/checkpoint/"
        "rematted_computation/block/attention/attention_core/exp")
    assert names == ("forward", "block", "attention", "attention_core")
    assert backward and recompute


def test_decoder_agrees_with_profile_data_on_the_chip_trace():
    from jax.profiler import ProfileData

    space = sr.read_xspace(str(SMALL))
    pd = ProfileData.from_file(str(SMALL))
    assert [p.name for p in space.planes] == [p.name for p in pd.planes]
    lo, hi = next((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                  for p in pd.planes if p.name.startswith("/host:")
                  for ln in p.lines for e in ln.events if e.name == tr.WINDOW)
    ops = []
    for a, b in zip(space.planes, pd.planes):
        if not re.match(r"/device:[A-Z]+:\d+$", a.name):
            continue
        assert a.name == "/device:TPU:0"
        names = sr.event_names(a)
        for la, lb in zip(a.lines, b.lines):
            if la.name != "XLA Ops":
                continue
            ours = sr.line_events(la, names)
            assert [(nm, s, e) for s, e, nm, _ in ours] == [
                (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for e in lb.events]
            ops += [op for s, e, _, op in ours if s < hi and e > lo]
    # every op in the window that carries an op_name names the jitted
    # lambda (the copies XLA adds carry none): all busy time is unscoped
    assert any(ops) and all(op.startswith("jit(<lambda>)/")
                            for op in ops if op)
    r = tr.reduce(str(SMALL))
    assert sr.scopes(str(SMALL)) == {"unscoped": {"unscoped": r["busy_s"]}}
    with_scopes = sr.reduce(str(SMALL))
    assert with_scopes.pop("scopes") and with_scopes == r


def _synthetic_trace(path):
    """A host plane with ``bench.window`` over [1, 9] ms and two device
    planes whose ops nest (a loop around its body) and straddle the
    window's edges; op names by ``str_value`` and by ``ref_value``."""
    space = sr.xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = tr.WINDOW
    line = host.lines.add(name="python", timestamp_ns=1_000_000)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=8_000_000_000)
    fwd = "jit(s)/jvp(forward)/while/body/closed_call/block/attention/"
    ops = {
        1: "jit(s)/jvp(forward)/while",
        2: fwd + "attention_core/dot_general",
        3: fwd + "dot_general",
        4: "jit(s)/transpose(jvp(forward))/while/body/checkpoint/"
           "rematted_computation/block/mlp/dot_general",
        5: "jit(s)/transpose(jvp(forward))/head_loss/dot_general",
        6: "jit(s)/optimizer/sqrt",
        7: "",
    }
    # (metadata id, start, end) in microseconds from the line's timestamp
    events = [(7, 0, 1500), (1, 1500, 5000), (2, 1600, 2600),
              (3, 2600, 3000), (4, 3200, 4800), (5, 5200, 6000),
              (6, 6000, 9500)]
    for dev in range(2):
        plane = space.planes.add(name=f"/device:TPU:{dev}")
        plane.stat_metadata.add(key=1).value.name = sr.TF_OP
        for mid, op in ops.items():
            md = plane.event_metadata.add(key=mid).value
            md.name = f"%op.{mid} = f32[8]{{0}} fusion(), op {mid}"
            if dev and op:  # on the second device, names held by reference
                plane.stat_metadata.add(key=100 + mid).value.name = op
                md.stats.add(metadata_id=1, ref_value=100 + mid)
            elif op:
                md.stats.add(metadata_id=1, str_value=op)
        ops_line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
        for mid, s, e in events:
            ops_line.events.add(metadata_id=mid, offset_ps=s * 1_000_000,
                                duration_ps=(e - s) * 1_000_000)
    path.write_bytes(space.SerializeToString())


def test_components_and_passes_sum_to_busy_time(tmp_path):
    path = tmp_path / "synthetic.xplane.pb"
    _synthetic_trace(path)
    r = tr.reduce(str(path))
    sc = sr.scopes(str(path))
    assert r["devices"] == 2
    ms = {c: {p: round(t * 1e3, 9) for p, t in v.items()}
          for c, v in sc.items()}
    assert ms == {
        "unscoped": {"unscoped": 0.5},      # 1.0-1.5 ms, clipped at 1 ms
        "forward": {"forward": 0.5},        # the loop less its body
        "attention_core": {"forward": 1.0},
        "attention": {"forward": 0.4},
        "mlp": {"recompute": 1.6},
        "head_loss": {"backward": 0.8},
        "optimizer": {"optimizer": 3.0},    # 6.0-9.0 ms, clipped at 9 ms
    }
    total = sum(t for v in sc.values() for t in v.values())
    assert total == pytest.approx(r["busy_s"], abs=1e-12)
    got = sr.readings(dict(r, scopes=sc), steps=2)
    assert got == pytest.approx({
        "train.forward_ms": 0.95, "train.backward_ms": 1.2,
        "train.optimizer_ms": 1.5, "train.attention_core_ms": 0.5,
        "train.ssd_scan_ms": 0.0,
        "train.unscoped_share": 100 * 0.5 / 7.8})
    assert sum(got[k] for k in ("train.forward_ms", "train.backward_ms",
                                "train.optimizer_ms")) * 2 + 0.5 == \
        pytest.approx(r["busy_s"] * 1e3)


def test_no_scopes_without_the_programs_scope_names(tmp_path, monkeypatch):
    path = tmp_path / "synthetic.xplane.pb"
    _synthetic_trace(path)
    monkeypatch.setitem(sys.modules, "repro.obs.scopes", None)
    out = sr.reduce(str(path))
    assert "scopes" not in out and out == tr.reduce(str(path))
    assert sr.readings(out, steps=2) == {}


def test_scoped_trace_recorded_on_the_chip():
    """Two steps of one granite layer at published widths, batch 1 x 2048,
    block remat (``record_scoped_trace.py``).  Every op the program names
    falls under a scope; what stays unscoped is what XLA adds with no
    ``op_name``, chiefly a relayout of the 49408 x 2048 float32 embedding
    (about 1.2 ms a step): 7% of this one-layer step's busy time, where
    the 8-layer cell's unscoped share is 0.3%."""
    r = tr.reduce(str(SCOPED))
    sc = sr.scopes(str(SCOPED))
    got = sr.readings(dict(r, scopes=sc), steps=2)
    for name in ("forward", "backward", "optimizer", "attention_core"):
        assert got[f"train.{name}_ms"] > 0, got
    assert got["train.ssd_scan_ms"] == 0
    assert sum(t for v in sc.values() for t in v.values()) == \
        pytest.approx(r["busy_s"])
    assert got["train.unscoped_share"] < 10
    space = sr.read_xspace(str(SCOPED))
    named = {op for p in space.planes if p.name == "/device:TPU:0"
             for ln in p.lines if ln.name == "XLA Ops"
             for _, _, _, op in sr.line_events(ln, sr.event_names(p)) if op}
    assert any("transpose(jvp(forward))" in op for op in named)
    assert [op for op in named if sr.component_pass(op)[0] == "unscoped"] \
        == []


GRANITE = ("forward", "embed", "block", "attention", "attention_core", "mlp",
           "head_loss")
MAMBA2 = ("forward", "embed", "block", "mamba", "ssd_scan", "head_loss")


@pytest.mark.parametrize("arch, replace, scopes", [
    ("granite-3-2b", dict(num_layers=2, d_model=64, num_heads=4,
                          num_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=128), GRANITE),
    ("mamba2-780m", dict(num_layers=2, d_model=64, vocab_size=128,
                         ssm_state=16, ssm_head_dim=16, ssm_chunk=8), MAMBA2),
], ids=["granite", "mamba2"])
def test_training_step_hlo_names_every_scope(arch, replace, scopes):
    """A tiny step compiled on the CPU with block remat, as the cells run:
    every scope of the model shows in forward and in transposed form."""
    from repro.configs.base import get_config
    from repro.launch.steps import build_train_step
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize
    from repro.obs.scopes import COMPONENTS
    from repro.optim.adamw import OptConfig, init_state

    cfg = get_config(arch).replace(**replace)
    opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    run = RunConfig(attn_impl="auto", remat="block")
    params = jax.eval_shape(
        lambda: materialize(M.model_specs(cfg), jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda p: init_state(opt, p), params)
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    hlo = jax.jit(build_train_step(cfg, run, opt)).lower(
        params, state, {"tokens": tok, "labels": tok}).compile().as_text()
    forward, transposed, recomputed = set(), set(), set()
    for op in set(re.findall(r'op_name="([^"]*)"', hlo)):
        names, backward, recompute = sr.scope_path(op)
        (transposed if backward else forward).update(names)
        if recompute:
            recomputed.update(names)
    assert set(scopes) <= forward and set(scopes) <= transposed
    assert "block" in recomputed
    assert "optimizer" in forward
    seen = forward | transposed
    assert seen == set(scopes) | {"optimizer"} and seen <= set(COMPONENTS)
