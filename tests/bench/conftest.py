"""Fixtures of the benchmark's tests: the repo root on the path, and a
checkout-like root holding the benchmark plus the tests' own tiny cell
(tests/bench/data), which the harness finds by name like any other."""

import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "tests", "bench", "data")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A root with BENCHMARK.json naming only the tiny cell, the
    benchmark's files, and the tests' config, mix, limits and metric."""
    root = tmp_path_factory.mktemp("root")
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(DATA, "bench"), root / "bench",
                    dirs_exist_ok=True)
    shutil.copy(os.path.join(DATA, "BENCHMARK.json"), root)
    return str(root)


@pytest.fixture
def jax_cache_restored():
    """run_cell points JAX's persistent cache into its root; put the
    process's settings back afterwards."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
