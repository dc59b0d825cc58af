"""The benchmark's tests of the committed manifest run twice: on the
repository, and on ``bench_helpers.grown_copy``, the manifest as a later PR
could leave it.  A test that pins the whole manifest fails on the second."""

import pytest
from bench_helpers import REPO, grown_copy


@pytest.fixture(scope="session", params=["repo", "grown"])
def manifest_root(request, tmp_path_factory):
    """The root of a tree whose ``BENCHMARK.json`` holds every committed
    entry: the repository, or a copy with entries appended."""
    if request.param == "repo":
        return REPO
    return grown_copy(str(tmp_path_factory.mktemp("grown")))
