"""The benchmark's files: found by name, named by the rules, and a run that
finds no TPU exits non-zero with no result."""
import json
import math
import re

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on sys.path)
import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys():
    assert set(BENCH) == KEYS
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert (harness.ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(cell):
    c = harness.Cell.load(cell)
    assert harness.module("drivers", c.traffic["driver"]).measure
    assert harness.module("flops", c.config["flops"]).train_step_flops
    assert harness.module("reference", c.config["family"]).row_nll
    assert c.limits and all(v > 0 for v in c.limits.values())
    for m in c.per_layer:
        assert callable(harness.module("metrics", m["name"]).read)


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        data = json.loads((harness.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|_size|hidden|intermediate)$",
                                 key) or key == "vocab_size", key
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200


def test_every_metric_moves_a_metric_its_cells_report():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        for cell in m["workloads"]:
            assert cell in cells
            e2e = harness.metrics_for(BENCH, cell)["end_to_end"]
            assert m["moves"] in {e["name"] for e in e2e}, (m["name"], cell)
    for cell in cells:
        got = harness.metrics_for(BENCH, cell)
        assert "setup_s" in {e["name"] for e in got["end_to_end"]}
        assert len(got["end_to_end"]) >= 2 and got["per_layer"]


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, math.floor(len(BENCH["workloads"]) / 2))


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    import run

    cell = BENCH["workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 5),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "TPU" in out.err
