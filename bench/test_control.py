"""Each cell's control, at a size a test run can hold, comes out as not
correct, where the program comes out correct.

* scheduling: the plain reference's EASY over socket slots, which lets
  two serial jobs share a node, put in the program's place (the
  guarantee of whole-node allocations broken);
* serving: the reference in float8 e4m3 (the step below the bf16 the
  configuration states): the tokens it puts first, read against the
  float32 reference, lie further below its best than the limit."""
import pytest
from conftest import run_small


@pytest.mark.parametrize("workload", ["quartz.backlog", "quartz.open"])
def test_sched_control_fails(small_sched, workload):
    from harness.sched import ControlSite
    cell = small_sched(workload)
    res, _ = run_small(cell, seconds=1.0)
    assert res["correct"], res["checks"]
    res, _ = run_small(cell, seconds=0.01,
                       system=ControlSite(cell.config["site"]))
    assert not res["correct"]
    assert res["checks"]["alloc_faults"]["value"] > 0


@pytest.mark.parametrize("seed", [7, 1000010, 2000013])
def test_decode_control_fails(small_decode, seed):
    res, extra = run_small(small_decode, seed=seed, control=True)
    limit = small_decode.limits["served_logit_gap"]
    assert res["correct"], res["checks"]
    assert extra["control_gap"] > limit, extra["control_gap"]
