"""The model families the cells run, under the trainer's accumulation:
the second half of tests/test_trainer_accumulates_models.py's families
(the stacks of several kinds of layer), through the same check at 1, 2
and 4 microbatches on one device and on ``data=4``. Split by family so
that neither file is a tier-1 run's wall; every case is kept."""

import pytest

from tests.test_trainer_accumulates_models import (
    FAMILIES, HERE, MESHES, check_train_step,
)

THERE = tuple(name for name in FAMILIES if name not in HERE)


def test_the_two_files_share_the_families_between_them():
    assert THERE == ("kimi", "mellum", "deepseek", "phi4_flash")
    assert set(HERE) | set(THERE) == set(FAMILIES)


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("accum", [1, 2, 4])
@pytest.mark.parametrize("family", THERE)
def test_train_step_is_the_mean_of_the_microbatches(family, accum, mesh_name):
    check_train_step(family, accum, mesh_name)
