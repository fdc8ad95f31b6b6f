"""Shared test plumbing.

- The in-process backend is pinned to CPU **and** forced to 8 simulated
  host devices before anything imports jax, so multi-device code paths
  (repro.distributed strategies, bucketed overlap, hierarchical meshes)
  execute *inside* pytest instead of silently degenerating to dp=1 — the
  same environment CI's fast tier runs (`XLA_FLAGS` in ci.yml).
- ``multi_device``: fixture for tests that require the forced device
  count; it fails (not skips) when the axis is missing, so a broken
  environment cannot silently pass the suite with dp=1.
- ``run_sub``: run a snippet in a fresh subprocess with its own
  ``--xla_force_host_platform_device_count`` (for tests that need a
  different device count, or heavyweight compiles kept out of the main
  process). Subprocess tests must carry the ``slow`` marker unless listed
  in ``tools/test_budget.py``'s allowlist (tier-1 budget guard).
- The ``slow`` marker (registered in pytest.ini) keeps tier-1
  (``pytest -x -q``) to the fast subset; ``pytest -m ""`` runs everything.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# Pin the in-process backend before anything imports jax: without it jax
# probes the TPU backend (libtpu is installed) and stalls ~8 min in
# GCP-metadata retries on non-TPU hosts.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Force a real multi-device axis in-process (matches ci.yml's fast tier).
# Only when the caller has not already forced a count of their own.
N_FORCED_DEVICES = 8
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={N_FORCED_DEVICES}").strip()

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def multi_device():
    """The in-process devices of the forced multi-device axis.  Tests that
    exercise dp>1 paths take this fixture so they *assert* the axis exists
    instead of silently falling back to a single device."""
    import jax

    devs = jax.devices()
    assert len(devs) >= N_FORCED_DEVICES, (
        f"expected >= {N_FORCED_DEVICES} forced host devices, got "
        f"{len(devs)} — XLA_FLAGS was set after jax initialized?")
    return devs[:N_FORCED_DEVICES]


def run_sub(body: str, devices: int = 8, timeout: int = 520) -> str:
    script = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(body)
    )
    r = subprocess.run(
        [sys.executable, "-c", script],
        # JAX_PLATFORMS=cpu is load-bearing: without it jax probes the TPU
        # backend and libtpu retries GCP metadata fetches for ~8 MINUTES
        # before falling back to CPU
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=timeout,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def pytest_configure(config):
    """Give the benchmark tests the tiny widths of every cell's
    configuration (``tests/bench/bench_tiny_cells.py`` adds those of the
    cells that ``bench_tiny`` does not know) before they are collected."""
    bench_tests = str(REPO / "tests" / "bench")
    if bench_tests not in sys.path:
        sys.path.insert(0, bench_tests)
    import bench_tiny_cells  # noqa: F401
