"""Whole runs on the CPU, at a test size, with the timed path broken
underneath: each fault has to turn ``correct`` false, and the sound
path has to keep it true.  The look for a chip is skipped; everything
else is the run the benchmark makes."""

import time

import pytest

from bench import faults, harness

import checkout


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    return root, checkout.make(root)


def _run(cell, workload, seed=2**33 + 5):
    root, spec = cell
    return harness.run(root, spec, workload, seed, 2.0, False,
                       time.perf_counter(), require_chip=False)


@pytest.mark.parametrize("workload", ["tiny.open", "tiny.closed",
                                      "tiny-w4.open"])
def test_sound_path_is_correct(cell, workload):
    r = _run(cell, workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", ["tiny.open", "tiny.closed"])
def test_fault_is_caught(cell, workload, fault, monkeypatch):
    faults.plant(monkeypatch.setattr, fault)
    r = _run(cell, workload)
    assert not r["correct"], r["checks"]
