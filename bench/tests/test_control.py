"""The control, at a test size: the reference computed in float8 put in
the program's place must fail the cell's limit on every seed, while the
program passes it.  (At the cells' own sizes the same readings come
from ``bench/control.py`` on the chip; PERF.md gives them.)"""

import time

import pytest

from bench import harness

import checkout


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    return root, checkout.make(root)


@pytest.mark.parametrize("workload", ["tiny.open", "tiny-w4.open"])
def test_control_fails_where_the_program_passes(cell, workload):
    root, spec = cell
    for seed in (3, 2**31 + 9, 2**33 + 5):
        r = harness.run(root, spec, workload, seed, 2.0, False,
                        time.perf_counter(), require_chip=False,
                        controls=("fp8",))
        assert r["correct"], r["checks"]
        assert not r["control"]["fp8"]["correct"], r["control"]
