"""Each test file's runs keep their plan caches, traces and window logs in a
directory of their own, so files that run in parallel share none and
nothing is written into the checkout."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def results_dir(tmp_path_factory):
    old = harness.RESULTS
    harness.RESULTS = tmp_path_factory.mktemp("results")
    yield harness.RESULTS
    harness.RESULTS = old
