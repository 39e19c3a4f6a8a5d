"""The control — the plain reference put in the program's place and
computed one precision below the configuration's bf16 (every
contraction's operands rounded to float8 e4m3) — comes out not
correct under each cell's limits, at a size a test run holds."""

import jax
import pytest

from chipbench import harness
from chipbench.tests import tiny

#: each benchmark cell with the tiny cell of its family; the SSM cells'
#: float8 error compounds over 48 layers, so their stand-in has 8
CELLS = {"mamba2-370m.c2.t2048": dict(fam="ssm", layers=8),
         "mamba2-370m.c2.t256": dict(fam="ssm", layers=8),
         "qwen3-4b-l3v8.c2.t2048": dict(fam="dense")}


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 11])
@pytest.mark.parametrize("name", list(CELLS))
def test_the_control_fails_the_limits(name, seed):
    kw = dict(CELLS[name])
    cell = tiny.cell(kw.pop("fam"), limits=harness.load_cell(name).limits,
                     **kw)
    ref = harness.reference_readings(cell, seed, jax.devices())
    control = harness.reference_readings(cell, seed, jax.devices(),
                                         quant="fp8")
    correct, checks = harness.check(cell, control, ref)
    assert not correct, checks
