"""The control, the reference one precision below the configuration's
bfloat16 (float8 weights and matmul operands), fails the cell's limits
at a size a test run holds; the chip readings at the cells' own sizes
are in PERF.md (``bench/study.py``)."""
import jax
import pytest

from bench import check, model as M, reference
from bench.tests.test_faults import CELLS, _traffic


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_control_fails(kind):
    cfg, cell = CELLS[kind]
    m = M.from_config(cfg)
    devs = jax.devices()[:1]
    ref = reference.train(m, _traffic(), 5, devs)
    ctl = reference.train(m, _traffic(), 5, devs, mode="fp8")
    ok, rows = check.verdict(check.readings(ctl, ref),
                             check.load_limits(cell))
    assert not ok, rows
