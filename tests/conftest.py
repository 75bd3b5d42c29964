import os
import sys

# the tests run on the CPU unless the caller picks a platform
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest                                                 # noqa: E402


# the plain helpers the test files share live in simhelpers.py


@pytest.fixture(scope="session")
def mini_setup():
    """3 paper jobs on the paper fabric — small enough for CPU tests."""
    from repro.core import build_setup, paper_cluster, paper_jobs
    return build_setup(paper_jobs(seed=0, n_each=1), paper_cluster(),
                       split=2)
