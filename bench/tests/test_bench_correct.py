"""The comparison that decides ``correct``: sound runs of every cell of
BENCHMARK.json pass it at a tiny scale on the CPU, and the control and
every planted fault fail it (in the cells with PPR traffic, a float state
kept in bfloat16 too)."""
import pytest

from bench import spec
from bench.tests import faults

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
PPR_CELLS = [w for w in CELLS if "ppr" in spec.cell(w)["traffic"]["mix"]]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = faults.run_tiny(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    for name, check in r["checks"].items():
        if check["limit"] is not None:
            assert check["value"] <= check["limit"], name
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"queries_per_s", "latency_p50_s",
                                 "latency_p90_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(workload, fault):
    r = faults.run_tiny(workload, faults.FAULTS[fault])
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    r = faults.run_tiny(workload, control=True)
    assert not r["correct"], r["checks"]
    assert r["checks"]["unanswered"]["value"] == 0
    assert any(v["value"] > 0 for k, v in r["checks"].items()
               if k.endswith("_wrong"))


@pytest.mark.parametrize("fault", sorted(faults.FLOAT_FAULTS))
@pytest.mark.parametrize("workload", PPR_CELLS)
def test_float_fault_is_caught(workload, fault):
    r = faults.run_tiny(workload, faults.FLOAT_FAULTS[fault])
    assert not r["correct"], r["checks"]
    assert r["checks"]["ppr_wrong"]["value"] > 0
    assert r["checks"]["ppr_l1_max"]["value"] > r["checks"]["ppr_l1_max"][
        "limit"]
