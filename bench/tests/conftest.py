"""Fixtures for the benchmark's own tests (run them by path:
``python -m pytest bench/tests``).

``tiny_root`` builds a checkout in a temporary directory: a copy of
``bench/`` and a BENCHMARK.json with two small cells added the way a later
change adds one, as new files found by name (a configuration, a traffic mix,
the cell's limits) plus entries; ``src`` links to the program."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.tests.helpers import make_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path))
