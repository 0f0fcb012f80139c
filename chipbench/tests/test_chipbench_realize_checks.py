"""The realize cell's check: a sound run of ``tiny.realize`` is correct; a
run with its timed path broken underneath, or with the control in the
program's place, is not (CPU)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.tests.tiny import drive, make_root  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("chipbench"))


CELL = "tiny.realize"


def test_sound_run_is_correct(root):
    res = drive(root, CELL)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault,check", [
    ("pass-state-unchanged", "cube_rel_err"),
    ("pass-half-batch", "cube_rel_err"),
    ("pass-answer-altered", "cube_rel_err"),
])
def test_fault_is_not_correct(root, fault, check):
    res = drive(root, CELL, fault=fault)
    assert not res["correct"]
    c = res["checks"][check]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("check", [
    "cube_rel_err",
])
def test_control_is_not_correct(root, check):
    """The reference in the program's place, one precision down, fails
    its limit."""
    res = drive(root, CELL, control=True)
    assert not res["correct"]
    c = res["checks"][check]
    assert c["value"] > c["limit"], res["checks"]
