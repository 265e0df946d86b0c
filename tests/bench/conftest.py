"""Fixtures of the benchmark's tests."""
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (os.path.dirname(__file__), REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench_cells import add_tiny_cells  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """The benchmark with the test-size cells, and the trainer's compile
    cache left off (``use_compile_cache`` would turn it on for the whole
    test process)."""
    from repro.launch import train as T

    monkeypatch.setattr(T, "use_compile_cache", lambda: "off")
    return add_tiny_cells(str(tmp_path))
