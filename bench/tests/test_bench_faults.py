"""A whole run of the harness, on the CPU at a tiny size, with the program
broken underneath: ``correct`` holds only for the program as it is."""
import json
import time

import pytest

from bench import check
from bench.faults import FAULTS
from bench.run import run_cell


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_correct_only_without_a_fault(tiny_cell, fault):
    res = run_cell(tiny_cell, 2**31 + 17, 0.5, False, time.perf_counter(),
                   plant=FAULTS.get(fault))
    json.dumps(res)
    assert set(res["compared"]) == set(check.NAMES)
    assert list(res)[-1] == "compared"
    assert res["correct"] is (fault is None), res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"samples_per_s", "eval_ne", "setup_s"}
    assert 0 < res["metrics"]["eval_ne"]["value"] < 2
