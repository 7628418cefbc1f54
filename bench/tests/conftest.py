import pytest

from .tiny import copy_tiny


@pytest.fixture
def tiny_root(tmp_path):
    """A copy of the benchmark at a tiny size."""
    return copy_tiny(tmp_path)
