"""The benchmark tests' fixture: a tiny copy of ``bench/`` per test."""

import pytest

from benchtest import make_tiny_bench


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_bench(str(tmp_path))
