"""CPU tests of the check that decides ``correct``, on the micro
``dp`` cell (``micro.py``): a sound run is correct, each planted fault
is not, and the float8 control fails a limit."""

import pytest

from chipbench import control
from chipbench import micro

KIND = "dp"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return micro.write_bench(tmp_path_factory.mktemp("micro"))


def test_sound_run_is_correct(bench):
    out = micro.run_cell(bench, KIND)
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", sorted(micro.FAULTS))
def test_planted_fault_is_not_correct(bench, fault):
    out = micro.run_cell(bench, KIND, **micro.FAULTS[fault]())
    assert not out["correct"], out["check"]


def test_control_fails_a_limit(bench):
    rows = control.readings(f"micro-{KIND}", [micro.SEED], bench_path=bench,
                            variants=("control",))
    nums = rows[0]["control"]
    assert any(nums[k] > micro.LIMITS[KIND][k] for k in nums), nums
