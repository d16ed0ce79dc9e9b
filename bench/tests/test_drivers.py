"""Each driver end to end at tiny sizes on the CPU, through ``run()``."""

import json

import pytest

from cells import CELLS, args, tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("name", CELLS)
def test_result_line_has_the_contract_keys(harness, name):
    res = harness.run(args(name), require_chip=False, cell=tiny(harness, name))
    assert list(res) == KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"] for m in tiny(harness, name)["end_to_end"]}
    assert set(res["metrics"]) == want and "setup_s" in want
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    for name_, c in res["checks"].items():
        assert c["value"] <= c["limit"], name_
    json.dumps(res, allow_nan=False)


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics(harness, name):
    cell = tiny(harness, name)
    res = harness.run(args(name, trace=1), require_chip=False, cell=cell)
    # the CPU has no device plane: no busy time, so no breakdown
    assert list(res) == KEYS
    assert set(res["metrics"]) == {m["name"] for m in cell["per_layer"]}
    assert cell["per_layer"]


def test_no_accelerator_exits_nonzero_with_no_result(harness, capsys):
    rc = harness.main(["--workload", "plan-nofe.ragged", "--seed", "1",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no accelerator" in out.err
