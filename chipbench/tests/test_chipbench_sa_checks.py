"""The sweep cell's check: a sound run of ``tiny.sa`` is correct; a run
with its timed path broken underneath, or with the control in the
program's place, is not (CPU)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.tests.tiny import drive, make_root  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("chipbench"))


CELL = "tiny.sa"


def test_sound_run_is_correct(root):
    res = drive(root, CELL)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault,check", [
    ("fused-answer-altered", "fused_gap"),
    ("fused-half-batch", "fused_gap"),
    ("exact-state-unchanged", "objective_gap"),
    ("exact-answer-altered", "objective_gap"),
])
def test_fault_is_not_correct(root, fault, check):
    res = drive(root, CELL, fault=fault)
    assert not res["correct"]
    c = res["checks"][check]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("check", [
    "objective_gap",
    "fused_gap",
])
def test_control_is_not_correct(root, check):
    """The reference in the program's place, one precision down, fails
    its limit."""
    res = drive(root, CELL, control=True)
    assert not res["correct"]
    c = res["checks"][check]
    assert c["value"] > c["limit"], res["checks"]


def test_window_tasks_do_fresh_work_after_set_up():
    """The window replays nothing that set-up computed: after set-up, as a
    run does it in-process, each window task misses in the program's
    process-wide result caches (geometry, intra-core search), where a
    replay of that same task misses in neither."""
    from itertools import islice

    from chipbench import run as harness
    from chipbench.drivers import sa_pool
    from chipbench.tests.tiny import TINY_CONFIG, TINY_POOL
    from repro.core.analyzer import _GEO_CACHE
    from repro.core.intra_core import explore_intra_core

    spec = harness.workload_spec(TINY_CONFIG)

    def misses():
        return _GEO_CACHE.misses, explore_intra_core.cache_info().misses

    def task(point, seed):
        m0 = misses()
        sa_pool._task(TINY_CONFIG, TINY_POOL, spec, point, seed)
        return [b - a for a, b in zip(m0, misses())]

    for point, seed in sa_pool.setup_tasks(TINY_POOL):
        task(point, seed)
    for i, seed in islice(sa_pool.window_tasks(TINY_POOL, 2 ** 31 + 3),
                          len(TINY_POOL["pool"])):
        point = TINY_POOL["pool"][i]
        assert sum(task(point, seed)) > 0, i
        assert task(point, seed) == [0, 0], i
