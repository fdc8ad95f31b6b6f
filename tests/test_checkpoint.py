"""Crash-safe elastic checkpointing (repro.checkpoint) + bounded-staleness
async PS (repro.distributed.async_ps).

Everything runs in-process on the 8 forced host devices (conftest pins
XLA_FLAGS before jax loads).  The io-level tests exercise the atomicity
protocol directly — torn steps, stale manifests, async races — and the
trainer-level tests check the two contracts the subsystem ships:

- staleness=0 is BIT-identical to the synchronous ``parameter_server``
  strategy (np.array_equal on every param leaf after K steps), and
- a killed run resumed from its checkpoint onto a *different* ``(dp,
  pipe)`` grid reproduces the uninterrupted loss trajectory to 1e-6.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, MANIFEST_SCHEMA_ID,
                              latest_step, restore, save, validate_manifest)
from repro.checkpoint import io as ckpt_io


def tiny_cfg():
    from repro.configs.base import get_config

    return get_config("granite-3-2b").reduced().replace(
        vocab_size=256, d_model=64, num_heads=2, num_kv_heads=1,
        head_dim=32, d_ff=128, dtype="float32")


def run_opt(lr=1e-3):
    from repro.models.blocks import RunConfig
    from repro.optim.adamw import OptConfig

    return RunConfig(attn_impl="dense", remat="none"), \
        OptConfig(lr=lr, warmup_steps=0)


def leaves_equal(a, b):
    import jax

    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    return [np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(fa, fb)]


# ---------------------------------------------------------------------------
# io primitives: dtypes, atomicity, manifest
# ---------------------------------------------------------------------------


def test_dtype_roundtrip_fp32_bf16_int(tmp_path):
    import jax.numpy as jnp
    import ml_dtypes

    tree = {
        "w": np.linspace(-1, 1, 12, dtype=np.float32).reshape(3, 4),
        "b": np.asarray(jnp.asarray([1.5, -2.25, 3e-2], jnp.bfloat16)),
        "step": np.asarray([7], np.int64),
        "mask": np.asarray([1, 0, 1], np.int32),
    }
    assert tree["b"].dtype == ml_dtypes.bfloat16  # the non-native case
    save(tree, str(tmp_path), step=3)

    template = {k: np.zeros_like(v) for k, v in tree.items()}
    out, step = restore(template, str(tmp_path))
    assert step == 3
    for k in tree:
        got = np.asarray(out[k])
        assert got.dtype == tree[k].dtype, k
        # bit-exact, not allclose: bf16 goes through the uint16 view
        assert np.array_equal(got.view(np.uint8), tree[k].view(np.uint8)), k

    # the step meta records the true dtype next to the stored bit-pattern
    meta = json.loads((tmp_path / "step_00000003.meta.json").read_text())
    validate_manifest(meta)
    assert meta["layout"]["b"]["dtype"] == "bfloat16"
    assert meta["layout"]["b"]["stored_dtype"] == "uint16"
    assert meta["layout"]["w"]["dtype"] == "float32"
    assert meta["layout"]["w"]["stored_dtype"] == "float32"


def test_manifest_validates_and_rejects_drift(tmp_path):
    save({"x": np.ones(2, np.float32)}, str(tmp_path), step=1)
    man = json.loads((tmp_path / "manifest.json").read_text())
    assert validate_manifest(man)["step"] == 1
    assert man["schema"] == MANIFEST_SCHEMA_ID
    with pytest.raises(ValueError):
        validate_manifest({**man, "schema": "repro.checkpoint/manifest/v9"})
    with pytest.raises(ValueError):
        validate_manifest({**man, "step": -1})
    with pytest.raises(ValueError):
        validate_manifest({"schema": MANIFEST_SCHEMA_ID, "step": 0})


def test_crash_between_npz_and_meta_is_invisible(tmp_path):
    """A step whose meta never landed (crash mid-protocol) must be
    unobservable: latest_step skips it, restore refuses it."""
    save({"x": np.full(3, 1.0, np.float32)}, str(tmp_path), step=1)
    # simulate the crash: step 2's npz landed, meta did not
    np.savez(tmp_path / "step_00000002.npz", x=np.full(3, 2.0, np.float32))
    assert latest_step(str(tmp_path)) == 1
    with pytest.raises(FileNotFoundError):
        restore({"x": np.zeros(3, np.float32)}, str(tmp_path), step=2)
    out, step = restore({"x": np.zeros(3, np.float32)}, str(tmp_path))
    assert step == 1 and float(out["x"][0]) == 1.0


def test_stale_manifest_falls_back_to_directory_scan(tmp_path):
    """The manifest pointer is advisory: if its step's files were deleted
    (operator GC, partial rsync) the newest *complete* step wins."""
    save({"x": np.ones(2, np.float32)}, str(tmp_path), step=1)
    save({"x": np.full(2, 2.0, np.float32)}, str(tmp_path), step=2)
    os.remove(tmp_path / "step_00000002.npz")
    assert json.loads((tmp_path / "manifest.json").read_text())["step"] == 2
    assert latest_step(str(tmp_path)) == 1


def test_manifest_is_step_monotonic(tmp_path):
    """A slow save of an OLDER step landing after a newer one must not
    move the pointer backwards (the async-save race the seed-era code
    lost)."""
    d = ckpt_io.Path(str(tmp_path))
    save({"x": np.ones(2, np.float32)}, str(tmp_path), step=5)
    ckpt_io._write_step(d, 3, {"x": np.full(2, 3.0, np.float32)})
    man = json.loads((tmp_path / "manifest.json").read_text())
    assert man["step"] == 5
    assert latest_step(str(tmp_path)) == 5
    # the old step is still restorable explicitly
    out, _ = restore({"x": np.zeros(2, np.float32)}, str(tmp_path), step=3)
    assert float(out["x"][0]) == 3.0


def test_restore_reports_missing_and_extra_keys(tmp_path):
    save({"a": np.ones(2, np.float32), "b": np.ones(2, np.float32)},
         str(tmp_path), step=1)
    with pytest.raises(ValueError) as e:
        restore({"a": np.zeros(2, np.float32),
                 "c": np.zeros(2, np.float32)}, str(tmp_path))
    msg = str(e.value)
    assert "c" in msg and "b" in msg  # one error names BOTH directions


def test_restore_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore({"x": np.zeros(2)}, str(tmp_path))
    assert latest_step(str(tmp_path)) is None


def test_tmp_files_never_observable(tmp_path):
    """Dead tmp files from a crashed writer are ignored by every reader."""
    save({"x": np.ones(2, np.float32)}, str(tmp_path), step=1)
    (tmp_path / "step_00000009.npz.tmp.12345").write_bytes(b"torn")
    (tmp_path / "manifest.json.tmp.12345").write_text("{")
    assert latest_step(str(tmp_path)) == 1


# ---------------------------------------------------------------------------
# CheckpointManager: serialized async saves
# ---------------------------------------------------------------------------


def test_async_saves_serialize_and_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    for s in range(1, 6):
        mgr.save(s, {"x": np.full(4, float(s), np.float32)})
    mgr.wait()
    assert mgr.latest_step() == 5
    out, step = mgr.restore({"x": np.zeros(4, np.float32)})
    assert step == 5 and float(out["x"][0]) == 5.0
    # every step landed complete (serialized writer, no lost updates)
    assert [int(p.stem.split("_")[1])
            for p in sorted(tmp_path.glob("step_*.npz"))] == [1, 2, 3, 4, 5]
    mgr.close()
    mgr.close()  # idempotent


def test_async_save_snapshots_at_enqueue(tmp_path):
    """The caller may donate/mutate its arrays right after save():
    flattening happens on the calling thread at enqueue time."""
    mgr = CheckpointManager(str(tmp_path))
    arr = np.full(4, 1.0, np.float32)
    mgr.save(1, {"x": arr})
    arr[:] = -99.0  # mutate after enqueue, before the writer drains
    mgr.wait()
    out, _ = mgr.restore({"x": np.zeros(4, np.float32)})
    assert float(out["x"][0]) == 1.0
    mgr.close()


def test_async_rejects_non_monotonic_steps(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(4, {"x": np.ones(2, np.float32)})
    with pytest.raises(ValueError):
        mgr.save(4, {"x": np.ones(2, np.float32)})
    with pytest.raises(ValueError):
        mgr.save(2, {"x": np.ones(2, np.float32)})
    mgr.close()


def test_async_writer_error_surfaces_on_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "sub"))
    # non-array payload: np.savez pickles objects only with allow_pickle;
    # the writer thread fails and wait() must re-raise, not swallow
    mgr.save(1, {"x": object()})
    with pytest.raises(RuntimeError):
        mgr.wait()


# ---------------------------------------------------------------------------
# Elastic restore across device grids
# ---------------------------------------------------------------------------


def test_restore_is_topology_independent(tmp_path, multi_device):
    """One checkpoint, three targets: host arrays, a dp=4 mesh, a dp=2
    mesh — identical bits everywhere (the on-disk layout is logical)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    tree = {"w": np.arange(24, dtype=np.float32).reshape(4, 6),
            "b": np.ones(6, np.float32)}
    save(tree, str(tmp_path), step=1)

    host, _ = restore({k: np.zeros_like(v) for k, v in tree.items()},
                      str(tmp_path))
    for dp in (4, 2):
        mesh = Mesh(np.array(multi_device[:dp]), ("data",))
        rep = NamedSharding(mesh, P())
        tmpl = {k: jax.device_put(np.zeros_like(v), rep)
                for k, v in tree.items()}
        out, step = restore(tmpl, str(tmp_path))
        assert step == 1
        for k in tree:
            assert out[k].sharding.mesh == mesh  # landed on the target grid
            assert np.array_equal(np.asarray(out[k]), np.asarray(host[k]))
            assert np.array_equal(np.asarray(out[k]), tree[k])


# ---------------------------------------------------------------------------
# Trainer-level contracts (slower: real jitted steps on the forced axis)
# ---------------------------------------------------------------------------


def test_staleness_zero_bit_matches_synchronous(multi_device):
    """AsyncPSTrainer(staleness=0, backup_workers=0) IS the synchronous
    parameter_server trainer: same losses, bit-identical params after K
    steps."""
    from repro.distributed import AsyncPSTrainer, DataParallelTrainer

    cfg = tiny_cfg()
    run, opt = run_opt()
    devs = multi_device[:4]
    kw = dict(batch=4, seq=16, steps=4, seed=0, log_every=0)

    sync = DataParallelTrainer(cfg, run, opt, strategy="parameter_server",
                               devices=devs)
    ps, ss = sync.init(0)
    r_sync = sync.train(params=ps, opt_state=ss, **kw)

    anc = AsyncPSTrainer(cfg, run, opt, staleness=0, backup_workers=0,
                         devices=devs)
    pa, sa = anc.init(0)
    r_async = anc.train(params=pa, opt_state=sa, **kw)

    assert r_async.losses == r_sync.losses
    rep = anc.async_report()
    assert rep.max_age == 0 and rep.mean_age == 0.0 and rep.drops == 0


def test_staleness_bounds_measured_age(multi_device):
    from repro.distributed import AsyncPSTrainer

    cfg = tiny_cfg()
    run, opt = run_opt()
    tr = AsyncPSTrainer(cfg, run, opt, staleness=2, backup_workers=1,
                        devices=multi_device[:4])
    tr.train(batch=4, seq=16, steps=5, seed=0, log_every=0)
    rep = tr.async_report()
    assert 0 < rep.max_age <= 2          # the bound holds, and it binds
    assert 0.0 < rep.mean_age <= rep.max_age
    assert rep.drops == 1 * 5            # k grads dropped per step
    assert rep.t_step_model["pull"] == pytest.approx(
        rep.t_step_model["push"] / 3)    # pull amortized over s+1


def test_kill_and_resume_elastic_dp4_to_dp2(tmp_path, multi_device):
    """The acceptance trajectory: train dp=4 with checkpoints, 'kill' it
    mid-run, resume the SAME directory on dp=2 — the stitched loss curve
    matches an uninterrupted run to 1e-6."""
    from repro.distributed import DataParallelTrainer

    cfg = tiny_cfg()
    run, opt = run_opt()
    kw = dict(batch=4, seq=16, seed=0, log_every=0)
    ck = str(tmp_path / "ck")

    ref = DataParallelTrainer(cfg, run, opt, strategy="all_reduce",
                              devices=multi_device[:4])
    losses_ref = ref.train(steps=6, **kw).losses

    # interrupted run: same recipe, checkpoints every 2 steps, killed at 4
    part = DataParallelTrainer(cfg, run, opt, strategy="all_reduce",
                               devices=multi_device[:4])
    r1 = part.train(steps=4, ckpt_dir=ck, ckpt_every=2, **kw)
    assert r1.start_step == 0 and latest_step(ck) == 4

    # resume on HALF the grid; the loop auto-restores and fast-forwards
    resumed = DataParallelTrainer(cfg, run, opt, strategy="all_reduce",
                                  devices=multi_device[:2])
    r2 = resumed.train(steps=6, ckpt_dir=ck, ckpt_every=2, **kw)
    assert r2.start_step == 4
    assert len(r2.losses) == 2
    np.testing.assert_allclose(r2.losses, losses_ref[4:], atol=1e-6)
    assert latest_step(ck) == 6


def test_kill_and_resume_pipe2_to_dp(tmp_path, multi_device):
    """Elastic across the OTHER axis: checkpoints written by a pipe=2
    pipeline run restore into a flat dp run (the 1F1B trainer is
    bit-identical to the data-parallel trainer on the same token stream,
    so the stitched trajectory must match its uninterrupted run)."""
    from repro.distributed import DataParallelTrainer, PipelineTrainer

    cfg = tiny_cfg().replace(num_layers=2)  # >= 1 layer cycle per stage
    run, opt = run_opt()
    kw = dict(batch=4, seq=16, seed=0, log_every=0)
    ck = str(tmp_path / "ck")
    # the dp twin of pipe=2 x data=2 with 2 microbatches accumulates the
    # same 1-row passes per device (batch 4 / dp 2 / 2 microbatches)
    dp_run = dataclasses.replace(run, microbatch=1)

    ref = DataParallelTrainer(cfg, dp_run, opt, strategy="all_reduce",
                              devices=multi_device[:2])
    losses_ref = ref.train(steps=4, **kw).losses

    pipe = PipelineTrainer(cfg, run, opt, pipe=2, n_microbatch=2,
                           strategy="all_reduce", devices=multi_device[:4])
    rp = pipe.train(steps=2, ckpt_dir=ck, ckpt_every=2, **kw)
    np.testing.assert_allclose(rp.losses, losses_ref[:2], atol=1e-6)

    resumed = DataParallelTrainer(cfg, dp_run, opt, strategy="all_reduce",
                                  devices=multi_device[:2])
    r2 = resumed.train(steps=4, ckpt_dir=ck, ckpt_every=2, **kw)
    assert r2.start_step == 2
    np.testing.assert_allclose(r2.losses, losses_ref[2:], atol=1e-6)
