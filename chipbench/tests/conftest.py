import pytest

from benchroot import make_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path))
