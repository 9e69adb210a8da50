"""The control of each one-chip cell: the plain reference one precision
lower (float8) in the program's place, judged by the driver's own
comparison, comes out as not correct.  At the cell's own widths, two
layers deep, on the CPU (the cell's own size on the chip: PERF.md)."""

from __future__ import annotations

import pytest

from _bench_tiny import WIDE, harness


@pytest.mark.parametrize("name", ["minicpm-2b.train-1chip", "minicpm-2b.serve-backlog"])
def test_control_fails_a_limit(name):
    c = harness().load_cell(name, overrides=WIDE[name])
    rows = c.module("drivers", c.workload["driver"]).control(c, 11, lambda m: None)
    assert {r["name"] for r in rows} == set(c.workload["limits"])
    assert not harness().verdict(rows), rows
