"""The plain reference against the program, the control against the
limits, and the FLOP count against the program's own accounting, at a
tiny size on the CPU (n=128, k=4, p=4 on virtual devices)."""
import jax.numpy as jnp
import pytest

from bench import compare, flops, harness
from bench.conftest import tiny
from bench.program import model_config

# the tensor configuration on four ranks is the tensor p=4 plan
CASES = [("dense16k-b256", 1), ("dense16k-b256", 4),
         ("phantom16k-p4-b256", 4)]


def _program_and_reference(cell, seed):
    prog, tp, devices = harness.build(cell)
    _, _, got = harness.first_steps(cell, prog, tp, seed)
    ref = harness.reference_steps(cell, tp, seed, devices[0])
    return got, ref, tp, devices


@pytest.mark.parametrize("name,chips", CASES)
def test_reference_reproduces_program(name, chips):
    """Loss, first gradient and change of every leaf after three AdamW
    steps agree with the program for the tensor (p=1, p=4) and phantom
    plans; on the CPU both sides are float32 throughout."""
    cell = tiny(name, chips)
    got, ref, _, _ = _program_and_reference(cell, seed=2 ** 31 + 7)
    nums = compare.numbers(got, ref)
    assert all(v < 1e-4 for v in nums.values()), nums
    assert len(got["grad_norms"]) == len(ref["grad_norms"]) > 0
    assert ref["losses"][2] < ref["losses"][0]


@pytest.mark.parametrize("name,chips", CASES)
def test_lower_precision_fails(name, chips):
    """The reference in bfloat16, put in the program's place, fails the
    cell's limits."""
    cell = tiny(name, chips)
    prog, tp, devices = harness.build(cell)
    ref = harness.reference_steps(cell, tp, 11, devices[0])
    ctl = harness.reference_steps(cell, tp, 11, devices[0],
                                  dtype=jnp.bfloat16, precision="default")
    nums = compare.numbers(ctl, ref)
    assert not compare.judge(nums, cell.limits), nums


@pytest.mark.parametrize("name,chips", CASES)
def test_flops_match_strategy(name, chips):
    """Every product of the step, the first layer's input gradients
    included, is 3 x the strategy's forward FLOPs x L; the count the
    benchmark uses leaves out only the first layer's batch gradients."""
    from repro.core.ffn import ffn_strategy

    cell = tiny(name, chips)
    cfg, B = cell.config, cell.traffic["global_batch"]
    tp = cfg["tp"] or chips
    fwd = ffn_strategy(model_config(cfg), tp).flops(B)
    L = cfg["num_layers"]
    n = cfg["ffn_width"]
    if cfg["projection"] == "tensor":
        batch_grads = 2.0 * B * n * (n // tp)
    else:
        m, k = n // tp, cfg["phantom"]["k"]
        batch_grads = 2.0 * B * (m * m + m * k)
    assert flops.step_flops(cfg, tp, B) + batch_grads == 3 * fwd * L
