"""The control, kept at a size a test run can hold: a whole run of the
tiny cell, judged by the committed limit of the serving cell, passes,
and the reference in float8, read at each position of the prompts and
tokens the program served and judged by the same limit, fails.  On the
chip the same readings, at the cell's own size, set that limit
(PERF.md)."""
import json

import pytest

from chipbench import run
from chipbench.tests.tiny import CELL, FakeDevice, make_root

COMMITTED = json.loads((run.BENCH / "cells" /
                        "internlm2-1.8b.prefill_heavy.json").read_text())


@pytest.mark.parametrize("seed", [11, 2**31 + 3])
def test_float8_control_fails_where_the_program_passes(tmp_path, seed):
    limit = COMMITTED["limits"]["token_gap"]["limit"]
    root = make_root(tmp_path, limit=limit)
    out = run.run_cell(CELL, seed, 1.5, False, root=root,
                       devices=[FakeDevice()], controls=("fp8",))
    assert out["correct"] is True
    fp8 = out["controls"]["fp8"]
    assert fp8["correct"] is False
    assert fp8["checks"]["token_gap"]["limit"] == limit
