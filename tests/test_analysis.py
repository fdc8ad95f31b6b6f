"""repro.analysis — known-bad/known-good fixtures per rule + repo self-run.

Every analyzer must (a) flag its known-bad fixture with the exact finding
code, (b) stay silent on the known-good twin, and (c) the combined pass
must run *clean* on this repo (zero unbaselined findings) — the same gate
``tools/repro_lint.py`` enforces in CI.
"""
import importlib.util
import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (apply_baseline, determinism, kernel_contracts,
                            load_baseline, make_baseline, mesh_axes,
                            run_analyzers, schema_drift, validate_baseline,
                            validate_findings)
from repro.analysis.findings import Finding, make_findings_payload

REPO = Path(__file__).resolve().parent.parent


def codes(findings):
    return sorted({f.code for f in findings})


def dedent(s):
    return textwrap.dedent(s).lstrip()


# ---------------------------------------------------------------------------
# Kernel contracts (KC1xx)
# ---------------------------------------------------------------------------


def test_kc101_block_must_tile_array():
    c = kernel_contracts.KernelContract(
        op="flash_attention", context="fixture", grid=(1, 1, 2),
        blocks=(kernel_contracts.Block("q", (1, 48, 128), 2, "in",
                                       (1, 128, 128)),))
    assert codes(kernel_contracts.check_contract(c)) == ["KC101"]


def test_kc102_lane_misalignment():
    # last dim 100: not a lane multiple, not the full array dim
    c = kernel_contracts.KernelContract(
        op="flash_attention", context="fixture", grid=(1, 2),
        blocks=(kernel_contracts.Block("q", (8, 100), 2, "in",
                                       (8, 200)),))
    assert "KC102" in codes(kernel_contracts.check_contract(c))


def test_kc103_sublane_misalignment():
    # bf16 wants sublane %16; 12 is split (array 24), not 1, not full
    c = kernel_contracts.KernelContract(
        op="flash_attention", context="fixture", grid=(2,),
        blocks=(kernel_contracts.Block("q", (12, 128), 2, "in",
                                       (24, 128)),))
    assert codes(kernel_contracts.check_contract(c)) == ["KC103"]


@pytest.mark.parametrize("block", [
    # the TPU compiler refused both: a second-minor 1 over a longer dim
    kernel_contracts.Block("pos", (1, 1), 4, "in", (4, 1)),
    kernel_contracts.Block("dt", (1, 1, 256), 4, "in", (4, 48, 4096)),
], ids=["decode_pos", "ssd_dt"])
def test_kc103_unit_sublane_over_a_longer_dim(block):
    c = kernel_contracts.KernelContract(
        op="decode_attention", context="fixture", grid=(4,),
        blocks=(block,))
    assert codes(kernel_contracts.check_contract(c)) == ["KC103"]


def test_kc104_ssd_chunk_contract():
    c, findings = kernel_contracts.ssd_contract(
        B=1, H=4, L=100, P=64, N=128, chunk=64, context="fixture")
    assert c is None and codes(findings) == ["KC104"]


def test_kc105_vmem_budget():
    # a 256 MiB block cannot fit the 64 MiB (vmem/2) budget
    c = kernel_contracts.KernelContract(
        op="flash_attention", context="fixture", grid=(1,),
        blocks=(kernel_contracts.Block("q", (16384, 4096), 4, "scratch"),))
    assert "KC105" in codes(kernel_contracts.check_contract(c))


def test_kc106_gqa_head_mapping():
    c, findings = kernel_contracts.flash_contract(
        B=1, H=7, KV=2, Sq=128, Sk=128, D=64, context="fixture")
    assert c is None and codes(findings) == ["KC106"]


def test_kc_known_good_contract_is_clean():
    c, findings = kernel_contracts.flash_contract(
        B=1, H=8, KV=2, Sq=4096, Sk=4096, D=128, context="fixture")
    assert not findings
    assert kernel_contracts.check_contract(c) == []


def test_kc_flash_backward_contracts():
    # granite's training widths at the kernel's own blocks are clean
    from repro.kernels.flash_attention import block_sizes

    (tq, tk), (bq, bk) = block_sizes(2048, 2048, 64)
    c, findings = kernel_contracts.flash_contract(
        B=4, H=32, KV=8, Sq=2048, Sk=2048, D=64, q_block=tq, kv_block=tk,
        context="fixture:granite")
    assert not findings and kernel_contracts.check_contract(c) == []
    bwd, findings = kernel_contracts.flash_bwd_contracts(
        B=4, H=32, KV=8, Sq=2048, Sk=2048, D=64, q_block=bq, kv_block=bk,
        context="fixture:granite")
    assert not findings
    assert [b.context for b in bwd] == ["fixture.dkv:granite",
                                        "fixture.dq:granite"]
    assert bwd[0].grid == (4, 8, 2048 // bk, 4, 2048 // bq)
    assert bwd[1].grid == (4, 32, 2048 // bq, 2048 // bk)
    assert all(kernel_contracts.check_contract(b) == [] for b in bwd)
    # a q block of 24 positions is off the 128-wide lane for q, dO and the
    # log-sum-exp rows
    bwd, _ = kernel_contracts.flash_bwd_contracts(
        B=1, H=8, KV=2, Sq=2048, Sk=2048, D=64, q_block=24, kv_block=512,
        context="fixture")
    for b in bwd:
        assert "KC102" in codes(kernel_contracts.check_contract(b))

    # and the GQA map is checked once for both
    bwd, findings = kernel_contracts.flash_bwd_contracts(
        B=1, H=7, KV=2, Sq=128, Sk=128, D=64, context="fixture")
    assert bwd == [] and codes(findings) == ["KC106"]


def test_kc_registry_clean_and_audited():
    findings, audit = kernel_contracts.check_registry()
    assert findings == [], [str(f) for f in findings]
    from repro.kernels.ops import TUNABLE_OPS
    for op in TUNABLE_OPS:
        # acceptance: every tunable op checked against >= 2 registry
        # configs (distinct archs, not just dtype variants)
        archs = {ctx.split(":")[1] for ctx in audit[op]}
        assert len(archs) >= 2, (op, audit[op])


def test_kc_mla_decode_wide_lane_is_admitted():
    # deepseek-v2 absorbed MLA decode: D=576 (not a lane multiple) must
    # pass as a full, 8-aligned unsplit dim
    c, findings = kernel_contracts.decode_contract(
        B=1, H=128, KV=1, S=32768, D=576, context="fixture")
    assert not findings and kernel_contracts.check_contract(c) == []


def test_kc107_stage_overflow_fires_on_tiny_hbm():
    # known-bad fixture: a 2 GiB chip cannot hold granite's pipe=4 stage
    # working set at m=64 — every stage must flag
    import dataclasses

    from repro.configs.base import get_config, get_shape
    from repro.core.hardware import TPU_V5E

    tiny = dataclasses.replace(TPU_V5E, hbm_bytes=2 * 2 ** 30,
                               name="tiny-hbm")
    found = kernel_contracts.pipeline_stage_findings(
        get_config("granite-3-2b"), get_shape("train_4k"),
        pipe=4, n_microbatch=64, dp=2, chip=tiny, context="fixture")
    assert found and codes(found) == ["KC107"]


def test_kc107_uncuttable_pipe_is_a_finding():
    from repro.configs.base import get_config, get_shape

    cfg = get_config("granite-3-2b")
    cycles = (cfg.num_layers - cfg.first_k_dense) // len(cfg.pattern)
    found = kernel_contracts.pipeline_stage_findings(
        cfg, get_shape("train_4k"), pipe=cycles + 1,
        n_microbatch=2 * (cycles + 1), dp=1, context="fixture")
    assert codes(found) == ["KC107"]
    assert "non-empty stages" in found[0].message


def test_kc107_pipeline_registry_clean_and_audited():
    findings, audit = kernel_contracts.check_pipeline_registry()
    assert findings == [], [str(f) for f in findings]
    # non-vacuous: the Eq.-5 gate admits cells at both pipe depths
    cells = audit["pipeline_stage"]
    assert len(cells) >= 3, cells
    depths = {c.split(":")[3] for c in cells}
    assert {"p2", "p4"} <= depths, cells


# ---------------------------------------------------------------------------
# Determinism (DT1xx)
# ---------------------------------------------------------------------------


DT_BAD_RNG = dedent("""
    import numpy as np
    import random

    def sample():
        a = np.random.rand(4)                  # legacy global RNG
        rng = np.random.default_rng()          # unseeded generator
        r = random.Random()                    # unseeded instance
        x = random.random()                    # module-level draw
        return a, rng, r, x
""")

DT_GOOD_RNG = dedent("""
    import numpy as np
    import random

    def sample(seed):
        rng = np.random.default_rng(seed)
        r = random.Random(seed)
        return rng.standard_normal(4), r.random()
""")


def test_dt101_unseeded_rng():
    found = determinism.analyze_source(DT_BAD_RNG, "src/repro/fix.py")
    assert codes(found) == ["DT101"] and len(found) == 4


def test_dt101_seeded_rng_is_clean():
    assert determinism.analyze_source(DT_GOOD_RNG, "src/repro/fix.py") == []


DT_BAD_CLOCK = dedent("""
    import time
    from time import perf_counter as pc

    def measure(fn):
        t0 = time.perf_counter()
        fn()
        return pc() - t0
""")

DT_GOOD_CLOCK = dedent("""
    from repro.obs.trace import monotonic

    def measure(fn):
        t0 = monotonic()
        fn()
        return monotonic() - t0
""")


def test_dt102_wall_clock_reads():
    found = determinism.analyze_source(DT_BAD_CLOCK, "src/repro/fix.py")
    assert codes(found) == ["DT102"] and len(found) == 2


def test_dt102_exempts_the_clock_module():
    assert determinism.analyze_source(
        DT_BAD_CLOCK, "src/repro/obs/trace.py") == []


def test_dt102_monotonic_is_clean():
    assert determinism.analyze_source(DT_GOOD_CLOCK, "src/repro/fix.py") == []


DT_BAD_SYNC = dedent("""
    import jax
    import numpy as np

    def sync_phase(grads, axis):
        g = jax.lax.psum(grads, axis)
        host = float(g.sum())       # device->host sync inside the phase
        arr = np.asarray(g)
        return host, arr, g.mean().item()
""")

DT_GOOD_SYNC = dedent("""
    import jax

    def sync_phase(grads, axis):
        return jax.lax.psum(grads, axis)

    def report(metrics):
        return float(metrics["loss"])  # no collective in this scope
""")


def test_dt103_host_sync_in_collective_phase():
    found = determinism.analyze_source(DT_BAD_SYNC, "src/repro/fix.py")
    assert codes(found) == ["DT103"] and len(found) == 3


def test_dt103_host_sync_outside_collectives_is_clean():
    assert determinism.analyze_source(DT_GOOD_SYNC, "src/repro/fix.py") == []


DT_BAD_WRITE = dedent("""
    import json
    import numpy as np

    def save_meta(d, meta):
        (d / "meta.json").write_text(json.dumps(meta))

    def save_arrays(d, arrays):
        with open(d / "step.npz", "wb") as f:
            np.savez(f, **arrays)
""")

DT_GOOD_WRITE = dedent("""
    import json
    import os
    import numpy as np

    def save_meta(d, meta):
        tmp = d / "meta.json.tmp"
        tmp.write_text(json.dumps(meta))
        os.replace(tmp, d / "meta.json")

    def save_arrays(d, arrays):
        tmp = d / "step.npz.tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        tmp.replace(d / "step.npz")  # Path.replace = same atomic syscall
""")


def test_dt104_bare_write_in_checkpoint_path():
    found = determinism.analyze_source(DT_BAD_WRITE,
                                       "src/repro/checkpoint/fix.py")
    assert codes(found) == ["DT104"] and len(found) == 2


def test_dt104_tmp_plus_replace_is_clean():
    assert determinism.analyze_source(DT_GOOD_WRITE,
                                      "src/repro/checkpoint/fix.py") == []


def test_dt104_scoped_to_checkpoint_subtree():
    # the same bare writes elsewhere in the repo are some other rule's
    # problem — DT104 only guards the checkpoint protocol
    assert determinism.analyze_source(DT_BAD_WRITE, "src/repro/fix.py") == []


# ---------------------------------------------------------------------------
# Mesh axes (MX1xx)
# ---------------------------------------------------------------------------


MX_DECL = dedent("""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(devices, ("nodes", "data"))
    spec = P("data", None)
""")

MX_BAD = dedent("""
    import jax

    def sync(g):
        return jax.lax.psum(g, "model")  # axis never declared
""")

MX_MISSING = dedent("""
    import jax

    def sync(g):
        return jax.lax.psum(g)  # no axis at all
""")

MX_GOOD = dedent("""
    import jax

    def sync(g):
        return jax.lax.psum(g, ("nodes", "data"))
""")


def test_mx101_unbound_axis():
    found = mesh_axes.analyze_sources(
        [("src/repro/mesh.py", MX_DECL), ("src/repro/bad.py", MX_BAD)])
    assert codes(found) == ["MX101"]


def test_mx102_missing_axis_argument():
    found = mesh_axes.analyze_sources([("src/repro/bad.py", MX_MISSING)])
    assert codes(found) == ["MX102"]


def test_mx_bound_axes_are_clean():
    assert mesh_axes.analyze_sources(
        [("src/repro/mesh.py", MX_DECL), ("src/repro/ok.py", MX_GOOD)]) == []


def test_mx_variable_axis_is_skipped():
    src = dedent("""
        import jax

        def sync(g, axis):
            return jax.lax.psum(g, axis)
    """)
    assert mesh_axes.analyze_sources([("src/repro/var.py", src)]) == []


def test_mx_repo_declares_the_pipe_axis():
    """The 1F1B trainer's (pipe, data) grid must keep the ``pipe`` axis in
    the repo-global declared set — a rename there would silently orphan
    any collective that reduces over it."""
    axes = set()
    for p in sorted((REPO / "src" / "repro").rglob("*.py")):
        axes |= mesh_axes.declared_axes(
            p.read_text(), p.relative_to(REPO).as_posix())
    assert {"data", "nodes", "pipe"} <= axes, sorted(axes)


# ---------------------------------------------------------------------------
# Schema drift (SD1xx)
# ---------------------------------------------------------------------------


def test_sd101_orphan_schema_id():
    src = 'SCHEMA_ID = "repro.api/phantom/v9"\n'
    found = schema_drift.analyze_literals(
        [("src/repro/phantom.py", src)], schema_drift.known_schema_ids())
    assert any(f.code == "SD101" for f in found)
    assert all(f.code in ("SD101", "SD102") for f in found)


def test_sd_known_ids_have_validators_and_no_orphans():
    known = schema_drift.known_schema_ids()
    assert "repro.api/report/v1" in known
    assert "repro.analysis/findings/v1" in known
    pairs = []
    for d in schema_drift.SCAN_DIRS:
        pairs.extend((p.relative_to(REPO).as_posix(), p.read_text())
                     for p in sorted((REPO / d).rglob("*.py")))
    assert schema_drift.analyze_literals(pairs, known) == []


def test_sd103_histogram_keys_reconcile():
    assert schema_drift.check_histogram_keys() == []


def test_sd104_sd105_goldens(tmp_path):
    g = tmp_path / "tests" / "goldens"
    g.mkdir(parents=True)
    (g / "report_broken.json").write_text('{"schema": "nope"}')
    (g / "mystery_thing.json").write_text("{}")
    got = {f.code for f in schema_drift.check_goldens(tmp_path)}
    assert got == {"SD104", "SD105"}


def test_sd_repo_goldens_validate():
    assert schema_drift.check_goldens(REPO) == []


# ---------------------------------------------------------------------------
# Baseline + findings schema plumbing
# ---------------------------------------------------------------------------


def test_baseline_roundtrip_and_stale(tmp_path):
    f1 = Finding("src/repro/a.py", 10, "DT102", "clock", "f")
    f2 = Finding("src/repro/b.py", 20, "DT101", "rng", "g")
    doc = make_baseline([f1], {f1.fingerprint: "justified: startup only"})
    validate_baseline(doc)
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(doc))
    sup = load_baseline(p)
    kept, suppressed, stale = apply_baseline([f1, f2], sup)
    assert kept == [f2] and suppressed == [f1] and stale == []
    # fingerprints are line-stable: moving the finding keeps it suppressed
    moved = Finding("src/repro/a.py", 99, "DT102", "clock", "f")
    kept2, suppressed2, _ = apply_baseline([moved], sup)
    assert kept2 == [] and suppressed2 == [moved]
    # a suppression matching nothing is reported stale
    _, _, stale3 = apply_baseline([f2], sup)
    assert stale3 == [f1.fingerprint]


def test_baseline_requires_reasons():
    with pytest.raises(ValueError):
        validate_baseline({"schema": "repro.analysis/baseline/v1",
                           "suppressions": [{"fingerprint": "A:b:c",
                                             "reason": ""}]})


def test_findings_payload_validates():
    f = Finding("src/repro/a.py", 1, "MX101", "axis", "fn")
    payload = make_findings_payload([f], [], [], 0.5)
    validate_findings(payload)
    assert payload["clean"] is False
    clean = make_findings_payload([], [f], ["X:y:z"], 0.1)
    validate_findings(clean)
    assert clean["clean"] is True


# ---------------------------------------------------------------------------
# Self-run: the repo itself is clean, and the CLI gate agrees
# ---------------------------------------------------------------------------


def _load_cli():
    spec = importlib.util.spec_from_file_location(
        "repro_lint", REPO / "tools" / "repro_lint.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_repo_self_run_is_clean():
    findings = run_analyzers(REPO)
    sup = load_baseline(REPO / "tools" / "lint_baseline.json")
    unbaselined, _, stale = apply_baseline(findings, sup)
    assert unbaselined == [], [str(f) for f in unbaselined]
    assert stale == [], stale


def test_cli_exits_zero_on_repo_and_writes_valid_payload(tmp_path, capsys):
    cli = _load_cli()
    out = tmp_path / "findings.json"
    assert cli.main(["--json", "--out", str(out)]) == 0
    payload = json.loads(out.read_text())
    validate_findings(payload)
    assert payload["clean"] and payload["findings"] == []
    capsys.readouterr()


def test_cli_exits_nonzero_on_known_bad_tree(tmp_path, capsys):
    bad = tmp_path / "src" / "repro"
    bad.mkdir(parents=True)
    (bad / "bad.py").write_text(DT_BAD_CLOCK + DT_BAD_RNG)
    cli = _load_cli()
    assert cli.main(["--root", str(tmp_path),
                     "--analyzer", "determinism"]) == 1
    capsys.readouterr()
