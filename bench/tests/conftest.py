import pytest

import bench_helpers


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    """A copy of the benchmark with tiny cells (``bench_helpers.TINY``)."""
    return bench_helpers.tiny_root(str(tmp_path_factory.mktemp("bench_root")))
