"""Fixtures of the benchmark's CPU tests."""

import pytest

from chipbench_cells import full_width_modulation, small_cell


@pytest.fixture
def tiny_cell():
    return small_cell()


@pytest.fixture(autouse=True)
def _modulation():
    undo = full_width_modulation()
    yield
    undo()
