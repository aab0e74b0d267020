"""Shared set-up of the benchmark's CPU tests: the repository's ``src``,
its root and this directory on the path, and a tiny cell fixture."""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench_tiny import make_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
