"""Each cell's control, small on the CPU: the reference one precision step
below the configuration's, in the program's place, fails the cell's limits
in the same run in which the program passes them."""
from __future__ import annotations

import pytest

from test_chipbench_faults import failing, run


@pytest.mark.parametrize("cell_name", ["qwen3-1.7b-L4.train-async",
                                       "qwen3-1.7b-L4.refresh"])
def test_control_is_not_correct(cell_name):
    out = run(cell_name, 2**31 + 99, control=True)
    assert not failing(out), out["checks"]
    bad = {k for k, v in out["control"].items() if not v["value"] <= v["limit"]}
    assert bad, out["control"]
