"""Fixtures of the benchmark's own tests."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH / "tests"))

from bench_small import make_small_root  # noqa: E402


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return make_small_root(tmp_path_factory.mktemp("bench_root"))
