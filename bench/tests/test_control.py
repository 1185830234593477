"""The control fails the check that the program passes.

``bench/control.py`` at a size a test run holds: the cnn8 configuration
at its published widths, a small mix (tiers 1, 2, 4), a short window on
the CPU.  The control is the reference with its operands rounded to the
precision below the one the configuration states; it has to read above
the configuration's limit, and the program below it.
"""
import dataclasses

import pytest

import cells
import control
import harness

SMALL_MIX = {"arrivals": "poisson", "pool_images": 64, "max_batch": 4,
             "max_delay_ms": 2.0,
             "sizes": {"zipf_exponent": 1.5, "min": 1, "max": 4}}


@pytest.mark.parametrize("workload", ["cnn8.poisson", "densenet40.poisson"])
def test_control_reads_above_the_limit(workload):
    cell = cells.load_cell(workload)
    cell = dataclasses.replace(cell, traffic=SMALL_MIX,
                               fixed={"rate_per_s": 100.0})
    limit = cell.config["check"]["worst_rel_err_limit"]
    operands = cell.config["precision"]["control_operands"]
    server = harness.Server(cell.config, cell.traffic, seed=1)
    for seed in (11, 2**31 + 12, 13):
        r = control.readings(server, cell, seed, 0.3, operands)
        assert r["requests"] > 0
        assert r["program"] < limit < r["control"], r
