"""Fixtures of the benchmark's own tests.  They need no chip and describe
no topology: every cell they run is a tiny one on the CPU's virtual
devices, added to a copy of the benchmark the way a later PR adds a cell."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture(scope="module")
def harness(tree):
    """(run.py as a module, harness.spec) with ``tree`` as the checkout."""
    return tiny.harness_of(tree)
