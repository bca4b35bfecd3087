"""The benchmark's correctness comparison fails what it must fail.

* The control — the chunk product one precision step below the
  configuration's (``high``: bfloat16 x3 in place of float32 at
  ``highest``) — is put in the program's place and must read as not
  correct.
* The timed path is broken underneath (``bench.backends.FaultyBackend``),
  once for each fault a cell can have, and ``correct`` must come out
  false.

Both run the whole harness but its look for a chip, at a size the CPU
holds.
"""

import pytest

from bench_tiny import run_tiny, tiny_root

from bench.backends import FaultyBackend, HighPrecisionControl


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("cell", ["tiny-mv.b1", "tiny-mv.b1-balanced",
                                  "tiny-mv.b16"])
def test_control_is_not_correct(root, cell):
    r = run_tiny(root, cell, backend_wrap=lambda inner: HighPrecisionControl())
    assert r["correct"] is False
    med = r["compared"]["median_rel_err"]
    assert med["value"] > med["limit"]


@pytest.mark.parametrize("cell,fault", [
    ("tiny-mv.b1", "unchanged"), ("tiny-mv.b1", "altered"),
    ("tiny-mv.b16", "unchanged"), ("tiny-mv.b16", "half_batch"),
    ("tiny-mv.b16", "altered")])
def test_fault_is_not_correct(root, cell, fault):
    r = run_tiny(root, cell,
                 backend_wrap=lambda inner: FaultyBackend(inner, fault))
    assert r["correct"] is False, r["compared"]
