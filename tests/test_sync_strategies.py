"""repro.distributed tests: strategy zoo numerics vs the single-device
baseline, compression tolerances, and the Lemma 3.2 measured-vs-predicted
report. Fast multi-device numerics run *in-process* on the 8 forced host
devices (conftest pins XLA_FLAGS before jax loads — the `multi_device`
fixture asserts the axis exists instead of silently running dp=1); only
the heavyweight trainer runs re-exec via conftest.run_sub (slow-marked)."""
import pytest

from conftest import run_sub

# ---------------------------------------------------------------------------
# In-process unit tests (no devices needed)
# ---------------------------------------------------------------------------


def test_flatten_roundtrip():
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.collectives import flatten_tree, unflatten_tree

    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": jnp.full((2, 2, 2), -1.5, jnp.float32)}}
    flat, meta = flatten_tree(tree)
    assert flat.shape == (6 + 4 + 8,) and flat.dtype == jnp.float32
    back = unflatten_tree(flat, meta)
    assert back["b"]["c"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(tree["a"]), np.asarray(back["a"]))
    np.testing.assert_array_equal(
        np.asarray(tree["b"]["d"]), np.asarray(back["b"]["d"]))


def test_wire_bytes_and_lemma_predictions():
    from repro.core import ps
    from repro.distributed.collectives import STRATEGIES, get_strategy

    s_p, dp, bw = 1e9, 8, 1e9
    ar = get_strategy("all_reduce")
    rs = get_strategy("reduce_scatter_all_gather")
    # ring all-reduce and RS+AG move identical wire bytes
    assert ar.wire_bytes(s_p, dp) == rs.wire_bytes(s_p, dp) \
        == 2.0 * s_p * (dp - 1) / dp
    assert ar.predicted_comm_time(s_p, dp, bw) == ps.predicted_comm_time(
        "all_reduce", s_p, dp, bw)

    # PS: worker pushes+pulls everything; server-side time follows Eq. 7 and
    # is monotone decreasing in the server count
    prev = float("inf")
    for n in (1, 2, 4, 8, 16):
        t = get_strategy("parameter_server",
                         n_servers=n).predicted_comm_time(s_p, dp, bw)
        assert t == ps.io_time(s_p, dp, n, bw)
        assert t < prev
        prev = t
    assert get_strategy("parameter_server").wire_bytes(s_p, dp) == 2.0 * s_p

    # dp=1 edge: nothing crosses the wire for ANY schedule — including the
    # parameter server, whose old form charged 2*S_p with no second worker
    for name in STRATEGIES:
        strat = get_strategy(name)
        assert strat.name == name
        assert strat.wire_bytes(s_p, 1) == 0.0
        assert strat.predicted_comm_time(s_p, 1, bw) == 0.0


def test_parameter_server_rejects_explicit_zero_servers():
    """n_servers=None defers to the dynamic N_ps = dp default; an explicit
    0 (or negative) must raise instead of silently falling back."""
    from repro.distributed.collectives import get_strategy

    assert get_strategy("parameter_server").n_servers is None
    assert get_strategy("parameter_server", n_servers=None).n_servers is None
    with pytest.raises(ValueError):
        get_strategy("parameter_server", n_servers=0)
    with pytest.raises(ValueError):
        get_strategy("parameter_server", n_servers=-2)


def test_hier_wire_bytes_by_tier():
    """Per-tier accounting of the reduction tree: the full payload moves
    in-node, only the 1/d_inner shard crosses nodes, and the total beats a
    flat ring's bottleneck-tier traffic."""
    from repro.core import ps
    from repro.distributed.collectives import get_strategy

    s_p = 1e9
    hier = get_strategy("hier_all_reduce", tiers=(4, 2))
    flat = get_strategy("all_reduce")
    by_tier = hier.wire_bytes_by_tier(s_p, 8)
    # tier 0 (in-node, 4 chips): RS + AG of the full payload
    assert by_tier[0] == pytest.approx(2.0 * s_p * 3 / 4)
    # tier 1 (cross-node, 2 nodes): only the 1/4 shard is exchanged
    assert by_tier[1] == pytest.approx(2.0 * (s_p / 4) * 1 / 2)
    assert sum(by_tier) == pytest.approx(hier.wire_bytes(s_p, 8))
    assert by_tier == ps.hier_wire_bytes(s_p, (4, 2))
    # the flat ring pushes its whole wire volume across every spanning tier
    flat_by_tier = get_strategy("all_reduce").wire_bytes_by_tier(s_p, 8)
    assert flat_by_tier == (flat.wire_bytes(s_p, 8),)
    # cross-node bytes: hier moves strictly less than flat
    assert by_tier[1] < flat.wire_bytes(s_p, 8)
    # dp=1: nothing anywhere
    assert all(w == 0.0 for w in hier.wire_bytes_by_tier(s_p, 1))
    # per-tier pricing: slow outer link dominates a uniform-bw pricing
    t_uniform = hier.predicted_comm_time(s_p, 8, 1e9)
    t_tiered = hier.predicted_comm_time(s_p, 8, 1e9, tier_bws=(1e9, 1e7))
    assert t_tiered > t_uniform


def test_compressor_registry_and_ratios():
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.compression import COMPRESSORS, get_compressor

    g = {"w": jnp.asarray(np.linspace(-1.0, 1.0, 64, dtype=np.float32))}
    for name in COMPRESSORS:
        comp = get_compressor(name)
        out, ef = comp.apply(g, None)
        assert out["w"].shape == g["w"].shape
        assert comp.wire_bytes(4.0 * 64) <= 4.0 * 64 + 1e-9
        if comp.stateful:
            assert ef is not None
            # error feedback exactly accounts for what compression dropped
            np.testing.assert_allclose(
                np.asarray(out["w"] + ef["w"]), np.asarray(g["w"]),
                rtol=1e-6, atol=1e-7)
        else:
            assert ef is None
    # bf16 rounding error bounded by ulp
    bf = get_compressor("bf16").apply(g, None)[0]["w"]
    assert float(jnp.max(jnp.abs(bf - g["w"]))) < 2 ** -8


def test_plan_resolves_to_runnable_strategy():
    from repro.configs.base import get_config, get_shape
    from repro.core.planner import plan_train
    from repro.distributed.collectives import SyncStrategy

    p = plan_train(get_config("granite-3-2b"), get_shape("train_4k"))
    strat = p.resolve_sync()
    assert isinstance(strat, SyncStrategy)
    assert strat.name == p.sync_schedule
    assert p.grad_bytes > 0


# ---------------------------------------------------------------------------
# Multi-device numerics (8 simulated host devices, subprocess)
# ---------------------------------------------------------------------------

STRATEGY_BODY = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_config
from repro.distributed import DataParallelTrainer
from repro.launch.steps import build_train_step
from repro.models import model as M
from repro.models.blocks import RunConfig
from repro.models.common import materialize
from repro.optim.adamw import OptConfig, init_state

cfg = get_config("granite-3-2b").reduced().replace(
    vocab_size=256, d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
    d_ff=128)
opt = OptConfig(lr=1e-3, warmup_steps=0)
run = RunConfig(attn_impl="dense", remat="none")

params = materialize(M.model_specs(cfg), jax.random.PRNGKey(0))
state = init_state(opt, params)
rng = np.random.default_rng(0)
toks = rng.integers(0, cfg.vocab_size, (16, 32)).astype(np.int32)
batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
p1, s1, m1 = jax.jit(build_train_step(cfg, run, opt))(params, state, batch)

for strat in ("all_reduce", "reduce_scatter_all_gather", "parameter_server"):
    tr = DataParallelTrainer(cfg, run, opt, strategy=strat)
    p0, st0 = tr.init(0)
    b = {k: jax.device_put(v, NamedSharding(tr.mesh, P("data")))
         for k, v in batch.items()}
    p2, s2, m2 = tr.step_fn()(p0, st0, b)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5, atol=1e-6)
    # Adam normalizes by sqrt(v): near-zero grads amplify cross-shard
    # reduction-order noise
    for a, b_ in zip(jax.tree_util.tree_leaves(p1),
                     jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=3e-3)
    print(strat, "matches baseline OK")
"""


def test_strategy_sync_means_match_global_mean(multi_device):
    """Fast tier-1 numerics, in-process on the 8 forced host devices:
    every strategy's sync, run under shard_map, returns exactly the
    data-axis mean of a random gradient pytree (the property that makes
    the trainer equivalent to the single-device baseline)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.distributed.collectives import STRATEGIES, get_strategy

    dp = 8
    mesh = Mesh(np.array(multi_device), ("data",))
    rng = np.random.default_rng(0)
    # per-device gradient stacks with awkward (non-divisible) leaf sizes
    gstack = {
        "w": jnp.asarray(rng.standard_normal((dp, 5, 7)), jnp.float32),
        "b": {"x": jnp.asarray(rng.standard_normal((dp, 13)), jnp.float32),
              "y": jnp.asarray(rng.standard_normal((dp, 3, 2, 2)),
                               jnp.float32)},
    }
    want = jax.tree_util.tree_map(lambda g: np.asarray(g).mean(0), gstack)

    # every strategy with defaults, plus PS with an explicit (non-dp,
    # non-divisible) server count; the bare parameter_server entry covers
    # the dynamic N_ps = dp default path
    combos = [(name, None) for name in STRATEGIES] + [("parameter_server", 3)]
    for name, n_servers in combos:
        strat = get_strategy(name, n_servers=n_servers)

        def sync_one(stack):
            local = jax.tree_util.tree_map(lambda x: x[0], stack)
            return strat.sync(local, "data", dp)

        got = jax.jit(jax.shard_map(
            sync_one, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False))(gstack)
        for w, g in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(w, np.asarray(g), rtol=1e-6, atol=1e-7)


def test_hier_all_reduce_mean_on_2x4_topology(multi_device):
    """The hierarchical strategy, run in-process over nested (nodes, data)
    shard_map axes on a simulated 2-node x 4-chip topology, returns exactly
    the global mean — same tolerance as the flat zoo — for both the
    topology-derived and an awkward adapted tier split."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.distributed.collectives import get_strategy

    dp = 8
    rng = np.random.default_rng(0)
    gstack = {
        "w": jnp.asarray(rng.standard_normal((dp, 5, 7)), jnp.float32),
        "b": {"x": jnp.asarray(rng.standard_normal((dp, 13)), jnp.float32),
              "y": jnp.asarray(rng.standard_normal((dp, 3, 2, 2)),
                               jnp.float32)},
    }
    want = jax.tree_util.tree_map(lambda g: np.asarray(g).mean(0), gstack)

    for tiers in ((4, 2), (2, 4)):  # 2 nodes x 4 chips, and the transpose
        strat = get_strategy("hier_all_reduce", tiers=tiers)
        inner = tiers[0]
        mesh = Mesh(np.array(multi_device).reshape(dp // inner, inner),
                    ("nodes", "data"))

        def sync_one(stack):
            local = jax.tree_util.tree_map(lambda x: x[0], stack)
            return strat.sync(local, ("nodes", "data"), dp)

        got = jax.jit(jax.shard_map(
            sync_one, mesh=mesh, in_specs=(P(("nodes", "data")),),
            out_specs=P(), check_vma=False))(gstack)
        for w, g in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(w, np.asarray(g), rtol=1e-6, atol=1e-7)


def test_trainer_hier_topology_in_process(multi_device):
    """End to end in-process: DataParallelTrainer builds the nested mesh
    from the named 2x4 cluster and reports the per-tier wire split."""
    from repro.configs.base import get_config
    from repro.core.hardware import get_cluster
    from repro.distributed import DataParallelTrainer
    from repro.models.blocks import RunConfig
    from repro.optim.adamw import OptConfig

    cfg = get_config("granite-3-2b").reduced().replace(
        vocab_size=256, d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
        d_ff=128)
    tr = DataParallelTrainer(cfg, RunConfig(attn_impl="dense", remat="none"),
                             OptConfig(lr=1e-3, warmup_steps=0),
                             strategy="hier_all_reduce",
                             devices=multi_device,
                             topology=get_cluster("2x4"))
    assert dict(tr.mesh.shape) == {"nodes": 2, "data": 4}
    assert tr.strategy.tiers == (4, 2)
    tr.train(batch=16, seq=32, steps=3, log_every=0)
    rep = tr.report()
    assert rep.tiers == (4, 2)
    assert len(rep.wire_bytes_by_tier) == 2
    assert abs(sum(rep.wire_bytes_by_tier) - rep.wire_bytes) < 1e-6
    assert rep.wire_bytes_by_tier[1] < rep.wire_bytes_by_tier[0]


@pytest.mark.slow
def test_all_strategies_match_single_device_baseline():
    out = run_sub(STRATEGY_BODY, devices=8)
    assert out.count("matches baseline OK") == 3


@pytest.mark.slow
def test_compression_variants_close_to_baseline():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config
    from repro.distributed import DataParallelTrainer
    from repro.launch.steps import build_train_step
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize
    from repro.optim.adamw import OptConfig, init_state

    cfg = get_config("granite-3-2b").reduced().replace(
        vocab_size=256, d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
        d_ff=128)
    opt = OptConfig(lr=1e-3, warmup_steps=0)
    run = RunConfig(attn_impl="dense", remat="none")
    params = materialize(M.model_specs(cfg), jax.random.PRNGKey(0))
    state = init_state(opt, params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (16, 32)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    p1, _, m1 = jax.jit(build_train_step(cfg, run, opt))(params, state, batch)

    # documented looser tolerances: quantization error is bounded and fed back
    tols = {"bf16": 2e-2, "int8": 5e-2, "topk": 2e-1}
    for comp, atol in tols.items():
        tr = DataParallelTrainer(cfg, run, opt, strategy="all_reduce",
                                 compression=comp)
        p0, st0 = tr.init(0)
        if tr.compressor.stateful:
            assert "ef" in st0
        b = {k: jax.device_put(v, NamedSharding(tr.mesh, P("data")))
             for k, v in batch.items()}
        p2, s2, m2 = tr.step_fn()(p0, st0, b)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5, atol=1e-6)
        for a, b_ in zip(jax.tree_util.tree_leaves(p1),
                         jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=atol, rtol=1e-1)
        if tr.compressor.stateful:
            ef_mag = max(float(jnp.max(jnp.abs(e)))
                         for e in jax.tree_util.tree_leaves(s2["ef"]))
            assert ef_mag > 0, "error feedback never engaged"
        print(comp, "OK")
    """, devices=8)


@pytest.mark.slow
def test_trainer_report_measured_vs_lemma():
    out = run_sub("""
    import json
    import jax
    from repro.configs.base import get_config
    from repro.distributed import DataParallelTrainer
    from repro.models.blocks import RunConfig
    from repro.optim.adamw import OptConfig

    cfg = get_config("granite-3-2b").reduced().replace(
        vocab_size=256, d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
        d_ff=128)
    opt = OptConfig(lr=1e-3, warmup_steps=0, total_steps=4)
    run = RunConfig(attn_impl="dense", remat="none")
    tr = DataParallelTrainer(cfg, run, opt, strategy="reduce_scatter_all_gather")
    res = tr.train(batch=16, seq=32, steps=4, log_every=0)
    rep = tr.report()
    assert rep.dp == 8 and rep.grad_bytes > 0
    assert rep.measured_comm_s > 0 and rep.predicted_comm_s > 0
    assert rep.measured_compute_s > 0
    # StepTimes carried the split phases
    assert all(t.dist_update > 0 for t in res.step_times)
    assert all(t.param_update > 0 for t in res.step_times)
    print("REPORT", json.dumps(rep.as_dict(), default=str))
    """, devices=8)
    assert "REPORT" in out
