"""Fixtures of the benchmark's CPU tests (helpers: ``benchtools``)."""
import pytest

from benchtools import make_root


@pytest.fixture
def tiny_root(tmp_path):
    return lambda *cells, **kw: make_root(str(tmp_path / "root"), cells, **kw)
