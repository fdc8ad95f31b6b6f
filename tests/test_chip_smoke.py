"""chip_smoke.py's phases, run on the CPU at granite-3-2b's reduced() config,
and its refusal to run without a TPU."""
import importlib.util
import math

import numpy as np
import pytest

from conftest import REPO


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    from repro.configs.base import get_config

    return get_config("granite-3-2b").reduced()


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line, no readings
    assert "no TPU" in out.err


def test_train_phase_losses_finite_and_falling(smoke, cfg):
    out = smoke.train_phase(cfg, batch=4, seq=64, steps=4)
    losses = out["losses"]
    assert len(losses) == 4 and len(out["step_s"]) == 4
    assert abs(losses[0] - math.log(cfg.vocab_size)) <= smoke.LOSS_BAND
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("losses, why", [
    ([10.8, 10.5, float("nan"), 10.0], "finite"),
    ([10.8, 10.5], "finite"),
    ([12.0, 11.0, 10.5, 10.0], "ln\\(vocab\\)"),
    ([10.8, 10.9, 10.9, 10.85], "did not fall"),
], ids=["nan", "too_few", "off_band", "not_falling"])
def test_check_losses_rejects(smoke, losses, why):
    with pytest.raises(AssertionError, match=why):
        smoke.check_losses(losses, steps=4, vocab_size=49155)
    smoke.check_losses([10.8, 10.6, 10.4, 10.2], steps=4, vocab_size=49155)


def test_serve_phase_answers_every_request(smoke, cfg):
    out = smoke.serve_phase(cfg, requests=4, n_new=6)
    assert sorted(out["tokens"]) == [0, 1, 2, 3]
    for rid, toks in out["tokens"].items():
        assert len(toks) == out["asked"][rid] >= 1
        assert all(0 <= t < cfg.vocab_size for t in toks)


def test_serve_fit_counts_the_weights(smoke, cfg):
    from repro.core.memory_model import n_params

    need = smoke.serve_fit(cfg, s_max=64, max_batch=2)
    assert need >= 4 * n_params(cfg)  # float32 weights are an argument


def test_four_chip_phase_matches_one_device(smoke, cfg, multi_device):
    out = smoke.four_chip_phase(cfg, batch=4, seq=32, steps=3)
    one = out["one_chip_losses"]
    for sync in ("all_reduce", "reduce_scatter_all_gather"):
        r = out[sync]
        assert r["rel_param_diff"] <= smoke.REL_TOL
        np.testing.assert_allclose(r["losses"], one, rtol=1e-4)


def test_rel_param_diff_flags_a_dropped_shard(smoke):
    init = {"w": np.zeros(64, np.float32)}
    ref = {"w": np.full(64, 1e-3, np.float32)}
    three_of_four = {"w": np.full(64, 0.75e-3, np.float32)}
    assert smoke.rel_param_diff(ref, ref, init) == 0.0
    assert smoke.rel_param_diff(ref, three_of_four, init) > smoke.REL_TOL
