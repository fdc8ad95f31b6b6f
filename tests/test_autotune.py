"""Closed-loop autotuner + the paper's memory procedure (ISSUE 4).

Fast-tier coverage of the Eq.-5 minibatch search edge cases, the Table-2
conv-algorithm ordering, `train_memory` at the dp/tp extremes, the
Calibration overlay/cache, and the acceptance property end to end:
`Session.tune()` returns a validated Report whose chosen minibatch is the
largest batch satisfying `m_bound`, and the calibrated re-plan lands closer
to the measured step time than the datasheet prediction.
"""
import json

import pytest

from repro.configs.base import get_config, get_shape
from repro.core import memory_model as mm
from repro.core.autotune import (Calibration, cached_calibration,
                                 choose_conv_algs, save_calibration,
                                 TUNING_SCHEMA_ID)
from repro.core.hardware import ClusterSpec, MeshSpec, Tier, TPU_V5E


# ---------------------------------------------------------------------------
# Eq. 5: the minibatch bound and its binary search
# ---------------------------------------------------------------------------


def test_m_bound_negative_at_infeasible_minibatch():
    hbm = TPU_V5E.hbm_bytes
    assert mm.m_bound(mm.ALEXNET, 1, hbm) > 0
    # m_fm is linear in X_mini, so some batch always breaks the budget
    assert mm.m_bound(mm.ALEXNET, 10_000_000, hbm) < 0


def test_max_x_mini_matches_brute_force():
    # small budget keeps the brute-force check cheap (AlexNet's classifier
    # alone needs ~700 MB at the paper's fp32 x3, so 1 GiB leaves room for
    # only a few dozen samples)
    m_gpu = 1 * 2 ** 30
    x_star = mm.max_x_mini(mm.ALEXNET, m_gpu)
    assert x_star >= 1
    assert mm.m_bound(mm.ALEXNET, x_star, m_gpu) >= 0
    assert mm.m_bound(mm.ALEXNET, x_star + 1, m_gpu) < 0
    brute = max(x for x in range(1, x_star + 2)
                if mm.m_bound(mm.ALEXNET, x, m_gpu) >= 0)
    assert x_star == brute


def test_max_x_mini_nothing_fits():
    # a budget below the model's own footprint: not even X_mini=1 fits
    assert mm.max_x_mini(mm.ALEXNET, 1 * 2 ** 20) == 0


def test_max_x_mini_monotone_in_memory():
    sizes = [2 ** 30, 2 ** 32, 2 ** 34]
    stars = [mm.max_x_mini(mm.ALEXNET, s) for s in sizes]
    assert stars == sorted(stars)
    assert stars[-1] > stars[0] > 0
    # below the model's own footprint (~750 MB fp32 x3) nothing fits
    assert mm.max_x_mini(mm.ALEXNET, 2 ** 28) == 0


# ---------------------------------------------------------------------------
# Table 2: conv algorithm memory ordering
# ---------------------------------------------------------------------------


def test_conv_alg_memory_ordering_matches_table2():
    """FFT's working set dominates GEMM's on every Table-2 layer, conv1 is
    the extreme case, and our ratios track the paper's within 20%."""
    ratios = []
    for row, paper in mm.TABLE2_ROWS:
        gemm, fft = mm.conv_alg_memory(*row)
        assert fft > gemm > 0
        ours = fft / gemm
        ratios.append(ours)
        assert abs(ours - paper) / paper < 0.20, (row, ours, paper)
    assert ratios[0] == max(ratios)  # conv1 (11.6x) dominates


def test_choose_conv_algs_is_feasibility_driven():
    rich = choose_conv_algs(128, TPU_V5E.hbm_bytes)
    assert all(l["chosen"] == "fft" for l in rich["layers"])
    # a budget that cannot hold every FFT working set: the choice must obey
    # the feasibility rule per layer, and at least one layer falls back
    used = (mm.m_fm(mm.ALEXNET, 128) + mm.m_mp(mm.ALEXNET)
            + mm.m_c(mm.ALEXNET)) / 8.0
    poor = choose_conv_algs(128, used + 250 * 2 ** 20)
    b = poor["m_bound_bytes"]
    for l in poor["layers"]:
        if l["fft_bytes"] <= b:
            assert l["chosen"] == "fft"
        elif l["gemm_bytes"] <= b:
            assert l["chosen"] == "gemm"
        else:
            assert l["chosen"] == "none" and not l["feasible"]
    assert any(l["chosen"] != "fft" for l in poor["layers"])


# ---------------------------------------------------------------------------
# train_memory at the dp/tp extremes + the microbatch search
# ---------------------------------------------------------------------------


def _train_mem(cfg, shape, **kw):
    base = dict(fsdp=False, microbatch=1, attn_impl="chunked", remat="block",
                seq_parallel=True, opt_kind="adamw")
    base.update(kw)
    return mm.train_memory(cfg, shape, **base)


def test_train_memory_tp_extremes():
    cfg, shape = get_config("granite-3-2b"), get_shape("train_4k")
    lone = _train_mem(cfg, shape, dp=256, tp=1)
    wide = _train_mem(cfg, shape, dp=16, tp=16)
    # model-parallel sharding must shrink params/grads/logits per chip
    assert wide.params < lone.params
    assert wide.grads < lone.grads
    assert wide.logits < lone.logits


def test_train_memory_dp_extremes():
    cfg, shape = get_config("granite-3-2b"), get_shape("train_4k")
    # dp = global_batch: one sample per replica, the smallest activations
    narrow = _train_mem(cfg, shape, dp=shape.global_batch, tp=1, microbatch=1)
    fat = _train_mem(cfg, shape, dp=1, tp=1,
                     microbatch=shape.global_batch)
    assert narrow.activations < fat.activations
    # optimizer state is ZeRO-1 sharded over all chips either way
    assert narrow.opt_state < fat.opt_state


def test_max_microbatch_edge_of_feasibility():
    cfg, shape = get_config("granite-3-2b"), get_shape("train_4k")
    kw = dict(dp=16, tp=16, fsdp=False, attn_impl="chunked", remat="block",
              seq_parallel=True)
    hbm = TPU_V5E.hbm_bytes
    mb = mm.max_microbatch(cfg, shape, hbm_bytes=hbm, **kw)
    b_rep = shape.global_batch // 16
    assert 1 <= mb <= b_rep
    mem = mm.train_memory(cfg, shape, microbatch=mb, opt_kind="adamw", **kw)
    assert mem.total <= 0.9 * hbm
    if mb < b_rep:  # the next microbatch must break the budget
        over = mm.train_memory(cfg, shape, microbatch=mb + 1,
                               opt_kind="adamw", **kw)
        assert over.total > 0.9 * hbm
    # an impossible budget: nothing fits
    assert mm.max_microbatch(cfg, shape, hbm_bytes=1.0, **kw) == 0


# ---------------------------------------------------------------------------
# Calibration overlay + cache
# ---------------------------------------------------------------------------


def _cal(**kw):
    base = dict(backend="cpu", cluster="2x4", achieved_flops=5e10,
                matmul_flops=8e10, hbm_bw=2e10, link_bw=1e9)
    base.update(kw)
    return Calibration(**base)


def test_calibration_apply_scales_chip_and_tiers():
    cluster = ClusterSpec("2x4", TPU_V5E,
                          (Tier("node", 4, 50e9), Tier("cluster", 2, 2.5e9)))
    mesh = MeshSpec.from_cluster(cluster)
    cal = _cal()
    out = cal.apply(mesh)
    assert out.chip.calibrated and out.chip.name == "tpu-v5e+cal"
    assert out.chip.peak_flops == 5e10
    assert out.chip.hbm_bw == 2e10
    # bottleneck tier anchored at the measured link bw, hierarchy preserved
    assert out.cluster.min_bw == pytest.approx(1e9)
    ratio = out.cluster.tiers[0].bw / out.cluster.tiers[1].bw
    assert ratio == pytest.approx(50e9 / 2.5e9)
    # the serialized plan topology still round-trips (+cal chip tolerated)
    back = ClusterSpec.from_dict(out.cluster.to_dict())
    assert back.chip.name == "tpu-v5e"


def test_calibration_unmeasured_link_leaves_tiers():
    mesh = MeshSpec(chips=8, dp=8, tp=1)
    out = _cal(link_bw=0.0).apply(mesh)
    assert out.cluster.tiers[0].bw == TPU_V5E.link_bw
    assert out.chip.peak_flops == 5e10


def test_calibration_cache_roundtrip(tmp_path):
    path = tmp_path / "cache.json"
    cal = _cal()
    save_calibration(path, cal)
    assert cached_calibration(path, "cpu/2x4") == cal
    assert cached_calibration(path, "cpu/other") is None
    # a second key merges rather than clobbers
    save_calibration(path, _cal(cluster="flat8", achieved_flops=7e10))
    assert cached_calibration(path, "cpu/2x4") == cal
    d = json.loads(path.read_text())
    assert sorted(d["calibrations"]) == ["cpu/2x4", "cpu/flat8"]


def test_calibration_key_is_arch_qualified():
    """The cached wall clock only compares to predictions for the config
    it was measured on — a reduced member must not share a key with the
    full config, nor with another arch."""
    from repro.core.autotune import cfg_cache_key

    full = get_config("granite-3-2b")
    assert cfg_cache_key(full) != cfg_cache_key(full.reduced())
    assert cfg_cache_key(full) != cfg_cache_key(get_config("minicpm3-4b"))
    assert _cal(arch=cfg_cache_key(full)).key.startswith("cpu/2x4/")


def test_tuning_schema_id_matches_api():
    from repro.api import TUNING_SCHEMA_ID as API_ID
    assert TUNING_SCHEMA_ID == API_ID


# ---------------------------------------------------------------------------
# Acceptance: Session.tune() end to end on the CPU backend
# ---------------------------------------------------------------------------


def test_session_tune_acceptance(tmp_path):
    """The ISSUE's acceptance criteria: a validated tune Report whose
    chosen minibatch is the largest `m_bound`-feasible batch, and whose
    calibrated step-time prediction beats the datasheet one."""
    from repro.api import JobSpec, Report, Session, validate_report

    spec = JobSpec(arch="granite-3-2b", reduced=True, steps=2, batch=2,
                   seq=16, log_every=0, tune=True, tune_steps=2,
                   tune_cache=str(tmp_path / "cal.json"))
    sess = Session(spec)
    rep = sess.tune()
    assert isinstance(rep, Report) and rep.kind == "tune"
    d = json.loads(rep.to_json())
    validate_report(d)

    t = d["measured"]["tuning"]
    assert t["schema"] == TUNING_SCHEMA_ID
    # chosen == the largest batch satisfying m_bound (feasibility edge)
    chosen, hbm = t["minibatch"]["chosen"], t["minibatch"]["m_gpu_bytes"]
    assert mm.m_bound(mm.ALEXNET, chosen, hbm) >= 0
    assert mm.m_bound(mm.ALEXNET, chosen + 1, hbm) < 0
    # the calibrated re-plan is the better predictor of the wall clock
    r = t["replan"]
    assert r["calibrated_closer"]
    assert (r["abs_err_calibrated_s"] <= r["abs_err_uncalibrated_s"])
    # every tunable op got a measured winner
    assert set(t["kernels"]) == {"flash_attention", "decode_attention",
                                 "paged_decode_attention", "ssd_scan"}
    assert all(e["chosen"] in e["times_s"] for e in t["kernels"].values())
    # the calibration persisted under backend/cluster/executed-config
    key = Calibration.from_dict(t["calibration"]).key
    assert key.count("/") == 2  # arch-qualified: another config must re-fit
    cached = cached_calibration(spec.tune_cache, key)
    assert cached is not None and cached.achieved_flops > 0
    # a train() on the same session adopts the tuned knobs and carries the
    # tuning section
    trep = sess.train()
    validate_report(json.loads(trep.to_json()))
    assert trep.measured["tuning"]["minibatch"]["chosen"] == chosen
    run, _ = sess.build_run_opt()
    assert run.attn_impl == ("dense" if t["kernels"]["flash_attention"]
                             ["chosen"] == "ref" else "auto")


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_bench_kernels_reraises_pallas_failure_on_tpu(monkeypatch, backend):
    """A failing variant is an infeasible algorithm on the CPU; on a TPU a
    Pallas failure is a broken kernel and must not hand the op to the
    reference."""
    import jax
    import jax.numpy as jnp

    from repro.core import autotune
    from repro.kernels import ops

    def broken(*a):
        raise ValueError("block shape refused")

    monkeypatch.setattr(ops, "TUNABLE_OPS", ("flash_attention",))
    monkeypatch.setattr(ops, "tune_inputs", lambda op, seq: (jnp.ones(4),))
    monkeypatch.setattr(ops, "tune_candidates", lambda op, ssd_chunks: {
        "pallas": broken, "ref": lambda x: x * 2})
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if backend == "tpu":
        with pytest.raises(ValueError, match="refused"):
            autotune.bench_kernels()
    else:
        out = autotune.bench_kernels()["flash_attention"]
        assert out["chosen"] == "ref" and "pallas" in out["errors"]
