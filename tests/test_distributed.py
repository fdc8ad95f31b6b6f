"""Distributed-correctness tests. Each test runs in a subprocess with
--xla_force_host_platform_device_count set (the parent pytest process has
already locked jax to 1 device)."""
from conftest import run_sub


def test_moe_sharded_matches_baseline():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config
    from repro.models import moe
    from repro.models.common import materialize

    mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
    cfg = get_config("jamba-1.5-large-398b").reduced().replace(
        num_experts=8, top_k=2, moe_d_ff=64, d_model=64)
    specs = moe.moe_specs(cfg, 1)
    p = materialize(specs, jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda a: a[0], p)  # drop layer dim

    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64), jnp.float32)
    base, stats_b = moe.moe_mlp(p, x, cfg)

    xs = jax.device_put(x, NamedSharding(mesh, P(("data",), "model", None)))
    ps = {k: jax.device_put(v, NamedSharding(mesh, P("model", None, None)))
          for k, v in p.items() if k != "router"}
    ps["router"] = jax.device_put(p["router"], NamedSharding(mesh, P()))
    out, stats_s = jax.jit(lambda pp, xx: moe.moe_mlp_sharded(
        pp, xx, cfg, mesh=mesh))(ps, xs)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                               rtol=2e-5, atol=2e-5)
    # dropless: the expert shards compute every assignment between them
    assert int(stats_s["moe_held_rows"]) == int(stats_b["moe_held_rows"]) \
        == 4 * 16 * 2
    # the whole expert set on one device has one buffer size; each of the
    # 8 shards (2 data x 4 model) may outgrow its sized buffer
    assert int(stats_b["moe_overflow_layers"]) == 0
    assert 0 <= int(stats_s["moe_overflow_layers"]) <= 8
    # aux is a mean-based estimator: per-dp-shard aux averaged != global aux
    # exactly (nonlinear in the token partition); 2% window
    np.testing.assert_allclose(float(stats_b["aux"]), float(stats_s["aux"]),
                               rtol=2e-2)
    print("moe sharded == baseline OK")
    """)


def test_sharded_train_step_matches_single_device():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config
    from repro.launch import mesh as mesh_lib
    from repro.launch.steps import build_train_step
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize, partition_specs
    from repro.optim.adamw import OptConfig, init_state

    cfg = get_config("granite-3-2b").reduced().replace(vocab_size=512)
    opt = OptConfig(lr=1e-3, warmup_steps=0)
    run = RunConfig(attn_impl="dense", remat="none")
    params = materialize(M.model_specs(cfg), jax.random.PRNGKey(0))
    state = init_state(opt, params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}

    # single-device reference
    p1, s1, m1 = jax.jit(build_train_step(cfg, run, opt))(params, state, batch)

    # sharded on a (2,4) mesh with the production rules + seq parallel
    mesh = jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2)
    rules = mesh_lib.sharding_rules(mesh, cfg, None, fsdp=True)
    pspecs = partition_specs(M.model_specs(cfg), rules)
    params_s = jax.tree_util.tree_map(
        lambda a, ps: jax.device_put(a, NamedSharding(mesh, ps)), params, pspecs)
    state_s = {"step": state["step"],
               "m": jax.tree_util.tree_map(
                   lambda a, ps: jax.device_put(a, NamedSharding(mesh, ps)),
                   state["m"], pspecs),
               "v": jax.tree_util.tree_map(
                   lambda a, ps: jax.device_put(a, NamedSharding(mesh, ps)),
                   state["v"], pspecs)}
    batch_s = {k: jax.device_put(v, NamedSharding(mesh, P(("data",), None)))
               for k, v in batch.items()}
    run_s = RunConfig(attn_impl="dense", remat="none",
                      act_sharding=NamedSharding(mesh, P(("data",), "model", None)))
    with jax.set_mesh(mesh):
        p2, s2, m2 = jax.jit(build_train_step(cfg, run_s, opt))(
            params_s, state_s, batch_s)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4, atol=1e-5)
    # Adam normalizes by sqrt(v): for near-zero grads the update direction is
    # sensitive to cross-shard reduction order, so allow ~3 LR units of slack
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=3e-3)
    print("sharded train step == single device OK")
    """, devices=8)


def test_hlo_collective_accounting_known_program():
    run_sub("""
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.launch import hlo

    mesh = jax.make_mesh((4,), ("x",), (AxisType.Auto,))

    def f(a):  # force an all-reduce of a (256, 256) f32 = 256 KiB operand
        return jnp.sum(a * a)

    arr = jax.ShapeDtypeStruct((256, 1024), jnp.float32,
                               sharding=NamedSharding(mesh, P("x", None)))
    comp = jax.jit(f).lower(arr).compile()
    stats = hlo.collective_bytes(comp.as_text())
    assert "all-reduce" in stats, stats.keys()
    # the final scalar all-reduce is 4 bytes; wire = 2*4*(3/4) = 6
    wire = stats["all-reduce"]["wire_bytes"]
    assert 0 < wire < 1024, wire
    print("hlo accounting OK", stats)
    """, devices=4)


def test_dryrun_single_combo_small_mesh():
    """End-to-end dryrun machinery on a small mesh (reduced arch)."""
    run_sub("""
    import jax, json
    from jax.sharding import AxisType
    from repro.configs.base import get_config, get_shape, ShapeConfig
    from repro.launch import dryrun as D
    from repro.launch import mesh as mesh_lib
    import repro.launch.mesh as ml

    # monkeypatch a small production mesh
    ml.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
        (2, 4), ("data", "model"), (AxisType.Auto,) * 2)

    cfg = get_config("granite-3-2b").reduced()
    import repro.configs.base as base
    orig = base.get_config
    base.get_config = lambda a: cfg
    shape = ShapeConfig("smoke_train", 128, 8, "train")
    base.SHAPES["smoke_train"] = shape

    ok = D.run_one("granite-3-2b", "smoke_train", "single", "/tmp/dryrun_test")
    assert ok
    rec = json.loads(open(
        "/tmp/dryrun_test/granite-3-2b__smoke_train__single.json").read())
    assert rec["derived"]["flops"] > 0
    assert rec["full"]["memory"]["argument_bytes"] > 0
    print("dryrun smoke OK")
    """, devices=8)
