"""Fixtures of the benchmark's CPU tests."""
from __future__ import annotations

import pytest

from tinybench import write_tiny_root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return write_tiny_root(tmp_path_factory.mktemp("tiny_checkout"))
