"""The control at a size a test run holds: the reference computed with
float8 weights, put in the program's place, fails the comparison that the
program passes.

On the chip the same readings are taken at each cell's own size by
``bench/control.py``; ``PERF.md`` gives them and the limits set from them.
"""
from __future__ import annotations

import pytest

from bench.tests.tiny import TINY_LIMIT, run_tiny, tiny_root


@pytest.mark.parametrize("workload", ["sc2-swap-short", "phi4-mixed-long"])
def test_control_fails_where_program_passes(tmp_path, workload):
    r = run_tiny(tiny_root(tmp_path), workload, seed=2 ** 31 + 99,
                 control=True)
    assert r["correct"], r["checks"]
    assert r["checks"]["logit_err"]["value"] <= TINY_LIMIT
    assert r["control"]["logit_err"] > TINY_LIMIT
