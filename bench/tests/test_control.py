"""The control must come out as not correct: a lower precision in the
program's place fails one of each cell's compared numbers.

Fit: the program's own bfloat16 storage path, at a tiny size, against the
cell's limits. Serving: the plain reference with bfloat16 inputs in one
bfloat16 pass, and the float32 reference with the acceptance bar ignored
or doubled, at the cell's own tenant size and sample (one seed; the chip
readings over more seeds are in PERF.md). Each is judged by the cell's
own checks."""

import json

import numpy as np
import pytest

import harness
import reference
from test_rehearsal import tiny


def fit_checks(dtype):
    import control
    cell = tiny(harness.load_cell("fit.blobs100k"))
    cell = cell._replace(config=dict(cell.config, dtype=dtype))
    row = next(control.fit_rows(cell, [5], []))
    limits = cell.config["checks"]
    return {name: harness.Check(name, row[name], spec["limit"],
                                spec["op"]).ok
            for name, spec in limits.items()}


def test_fit_program_passes_and_bf16_control_fails():
    assert all(fit_checks("float32").values())
    control = fit_checks("bfloat16")
    assert not (control["density_gap_max"] and control["density_bias"])


def test_fit_control_row_is_judged_by_the_cells_checks():
    import control
    cell = tiny(harness.load_cell("fit.blobs100k"))
    program, low = list(control.fit_rows(cell, [5], [5]))
    assert program["correct"] is True and program["checks_failed"] == []
    assert low["correct"] is False
    assert set(low["checks_failed"]) & {"density_gap_max", "density_bias"}


@pytest.mark.parametrize("name", ["serve.blobs1m.r80"])
def test_serve_control_and_bar_faults_fail_at_cell_size(name):
    import control
    cell = harness.load_cell(name)
    rows = {r["side"]: r for r in control.serve_rows(cell, [5])}
    assert set(rows) == {"control", "fault.bar_ignored", "fault.bar_doubled"}
    assert "answer_gap" in rows["control"]["checks_failed"], rows
    for fault in ("fault.bar_ignored", "fault.bar_doubled"):
        assert "labelled_share" in rows[fault]["checks_failed"], rows
    assert not any(r["correct"] for r in rows.values()), rows
