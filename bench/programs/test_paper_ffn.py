"""The paper-FFN program module at a tiny size on the CPU (n=128, k=4,
p=4 on virtual devices): its plain reference against the program, the
control against the limits, its FLOP count against the program's own
accounting, its step and counts against the construction the harness
used before the module existed, and whole runs with the timed path
broken underneath, where ``correct`` has to come out false for each
fault a training cell can have and true for the sound program."""
import re

import jax
import jax.numpy as jnp
import pytest

import repro.core.ffn as ffn
from bench import compare, harness, spec
from bench.conftest import tiny

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REAL_STEP = ffn.make_ffn_train_step

# the tensor configuration on four ranks is the tensor p=4 plan
CASES = [("dense16k-b256", 1), ("dense16k-b256", 4),
         ("phantom16k-p4-b256", 4), ("phantom16k-p4-pallas-b256", 4)]


def _program_and_reference(cell, seed):
    prog, tp, devices = harness.build(cell)
    _, _, got = harness.first_steps(cell, prog, tp, seed)
    ref = harness.reference_steps(cell, tp, seed, devices[0])
    return got, ref, tp, devices


@pytest.mark.parametrize("name,chips", CASES)
def test_reference_reproduces_program(name, chips):
    """Loss, first gradient and change of every leaf after three AdamW
    steps agree with the program for the tensor (p=1, p=4) and phantom
    plans, the latter through XLA and through the Pallas kernels (in the
    interpreter); on the CPU both sides are float32 throughout."""
    cell = tiny(name, chips)
    got, ref, _, _ = _program_and_reference(cell, seed=2 ** 31 + 7)
    nums = compare.numbers(got, ref)
    assert all(v < 1e-4 for v in nums.values()), nums
    assert len(got["grad_norms"]) == len(ref["grad_norms"]) > 0
    assert ref["losses"][2] < ref["losses"][0]


@pytest.mark.parametrize("name,chips", CASES[:3])
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


@pytest.mark.parametrize("name,chips", CASES[:3])
def test_flops_match_strategy(name, chips):
    """Every product of the step, the first layer's input gradients
    included, is 3 x the strategy's forward FLOPs x L; the count the
    benchmark uses leaves out only the first layer's batch gradients."""
    from repro.core.ffn import ffn_strategy

    cell = tiny(name, chips)
    cfg, B = cell.config, cell.traffic["global_batch"]
    tp = cfg["tp"] or chips
    fwd = ffn_strategy(cell.program.model_config(cfg), tp).flops(B)
    L = cfg["num_layers"]
    n = cfg["ffn_width"]
    if cfg["projection"] == "tensor":
        batch_grads = 2.0 * B * n * (n // tp)
    else:
        m, k = n // tp, cfg["phantom"]["k"]
        batch_grads = 2.0 * B * (m * m + m * k)
    assert (cell.program.step_flops(cfg, tp, cell.traffic) + batch_grads
            == 3 * fwd * L)


# FLOPs and matmul floor (s) a step on a chip, tiny and full size, as the
# benchmark's code counted them before the program module existed
COUNTS = {
    "dense16k-b256": ((2621440.0, 2.5006105006105006e-07),
                      (687194767360.0, 0.003488298311472081)),
    "dense16k-b2048": ((2621440.0, 2.5006105006105006e-07),
                       (5497558138880.0, 0.02790638649177665)),
    "phantom16k-p4-b256": ((258048.0, 4.954334554334554e-08),
                           (43721424896.0, 0.0002625340952380953)),
}
PEAK_V5E = {"bf16_flops_per_s": 197000000000000.0,
            "hbm_bytes_per_s": 819000000000.0}
_META = re.compile(r", metadata=\{[^}]*\}")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _step_text(compiled) -> str:
    """A compiled step's text without source locations, which name the
    files that built it."""
    blocks = [b for b in compiled.as_text().split("\n\n")
              if b.split("\n", 1)[0] not in _TABLES]
    return _META.sub("", "\n\n".join(blocks))


def _step_as_built_before(cfg, batch, mesh):
    """The step as the harness built it before the program module: the
    program's ``make_ffn_train_step`` with its ``AdamW``, lowered for
    float32 batches of ``batch`` rows."""
    from repro.optim import AdamW
    from repro.parallel.params import abstract

    o = cfg["optimizer"]
    opt = AdamW(o["lr_times_width"] / cfg["ffn_width"], b1=o["b1"],
                b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"])
    step, decls, opt_decls = ffn.make_ffn_train_step(
        spec.load_program("paper_ffn").model_config(cfg), mesh, opt, batch)
    xs = jax.ShapeDtypeStruct((batch, cfg["ffn_width"]), jnp.float32)
    return step.lower(abstract(decls), abstract(opt_decls),
                      jax.ShapeDtypeStruct((), jnp.int32), xs, xs).compile()


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_program_module_keeps_the_step_and_counts(name):
    """The module's compiled step is the step the harness compiled
    before, op for op; its FLOPs and matmul floor are the numbers the
    benchmark's code gave before, at the tiny and at the full size."""
    from repro.launch.mesh import make_local_mesh

    full, cell = spec.load_cell(name), tiny(name)
    assert cell.program is not None and cell.config["program"] == "paper_ffn"
    for c, want in zip((cell, full), COUNTS[name]):
        tp = c.program.mesh_tp(c.config, c.chips)
        assert (c.program.step_flops(c.config, tp, c.traffic),
                c.program.matmul_floor_s(c.config, tp, c.traffic,
                                         PEAK_V5E)) == want
    mesh = make_local_mesh(1, cell.chips)
    got = cell.program.build(cell.config, cell.traffic, mesh).compiled
    want = _step_as_built_before(cell.config, cell.traffic["global_batch"],
                                 mesh)
    assert _step_text(got) == _step_text(want)


def _run(cell, seed=3):
    return harness.run(cell, seed, 0.2, False, peak=PEAK, t_process=0.0)


def unchanged_state(cfg, mesh, opt, batch):
    step, d, od = REAL_STEP(cfg, mesh, opt, batch)

    def broken(p, o, s, x, y):
        _, _, loss = step(p, o, s, x, y)
        return p, o, loss
    return jax.jit(broken), d, od


def half_batch(cfg, mesh, opt, batch):
    step, d, od = REAL_STEP(cfg, mesh, opt, batch // 2)

    def broken(p, o, s, x, y):
        return step(p, o, s, x[:batch // 2], y[:batch // 2])
    return jax.jit(broken, donate_argnums=(0, 1)), d, od


def altered_loss(cfg, mesh, opt, batch):
    step, d, od = REAL_STEP(cfg, mesh, opt, batch)

    def broken(p, o, s, x, y):
        p, o, loss = step(p, o, s, x, y)
        return p, o, loss * 1.01
    return jax.jit(broken, donate_argnums=(0, 1)), d, od


def gather_nothing(x, axis_name, *, axis=0, tiled=False, **kw):
    """An all-gather that exchanges nothing: every rank's own block
    stands in for everyone's."""
    n = jax.lax.axis_size(axis_name)
    if tiled:
        return jnp.concatenate([x] * n, axis=axis)
    return jnp.stack([x] * n, axis=axis)


@pytest.mark.parametrize("name,chips", [("dense16k-b256", 1),
                                        ("phantom16k-p4-b256", 4),
                                        ("phantom16k-p4-pallas-b256", 4)])
def test_sound_run_is_correct(name, chips):
    r = _run(tiny(name, chips))
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"samples_per_s", "step_ms_p95", "mfu",
                                 "setup_s"}


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_loss])
@pytest.mark.parametrize("name,chips", [("dense16k-b256", 1),
                                        ("phantom16k-p4-b256", 4),
                                        ("phantom16k-p4-pallas-b256", 4)])
def test_fault_is_not_correct(monkeypatch, fault, name, chips):
    monkeypatch.setattr(ffn, "make_ffn_train_step", fault)
    r = _run(tiny(name, chips))
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("name", ["phantom16k-p4-b256", "dense16k-b256",
                                  "phantom16k-p4-pallas-b256"])
def test_exchange_left_out_is_not_correct(monkeypatch, name):
    """The exchange between four chips left out (the tensor
    configuration on four ranks is the tensor p=4 plan)."""
    monkeypatch.setattr(jax.lax, "all_gather", gather_nothing)
    r = _run(tiny(name, 4))
    assert not r["correct"], r["compared"]
