"""Fixtures of the benchmark's CPU tests.

``tiny_root`` is a checkout copy that holds the benchmark, the program's
sources and one extra configuration at smoke size, with a closed-loop and
an open-loop cell on it (``benchkit``).
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import pytest  # noqa: E402

from benchkit import TINY_MIXES, add_cell, copy_checkout, tiny_config  # noqa: E402


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = copy_checkout(tmp_path_factory.mktemp("checkout"))
    add_cell(root, "tiny", tiny_config(), TINY_MIXES)
    return root
