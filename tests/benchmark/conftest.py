"""Fixtures for the tests of the benchmark itself (bench_helpers.py)."""

import pytest

from bench_helpers import make_bench_root


@pytest.fixture()
def bench_root(tmp_path):
    """(root, spec) of a temporary copy of the benchmark with tiny cells."""
    return make_bench_root(tmp_path)
