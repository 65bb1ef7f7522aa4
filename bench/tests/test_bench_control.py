"""The comparison's control: the reference itself, computed in bfloat16 and
put in the program's place, must come out not correct."""
import jax.numpy as jnp
import pytest

from bench import check
from bench.reference import ctr
from bench.reference.shapes import Shapes
from bench.run import CheckInputs, check_reference


@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_bfloat16_reference_fails(tiny_cell, seed):
    shapes = Shapes.from_config(tiny_cell.config)
    tr = tiny_cell.traffic
    inputs = CheckInputs(shapes, tr, ctr.make_teacher(shapes, tr["task_seed"]), seed)
    ref = check_reference(shapes, tr, seed, inputs)
    again = check_reference(shapes, tr, seed, inputs)
    ctl = check_reference(shapes, tr, seed, inputs, jnp.bfloat16)
    assert check.verdict(check.gaps(again, ref), tiny_cell.limits)
    assert not check.verdict(check.gaps(ctl, ref), tiny_cell.limits)
