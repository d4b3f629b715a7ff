"""The reduction from trace events to per-layer numbers: on hand-made
events whose answers are known, and on a trace recorded on the chip."""
import json
import os

import pytest

from bench import devtrace, scopes, spec

# A 0.05 s window of phantom16k-p4-b256 on a TPU v5e 2x2, cut by
# ``bench/run.py --keep-trace``, and the map of the step's op names to
# its parts, made on the same machine
RECORDED = os.path.join(os.path.dirname(__file__), "testdata",
                        "phantom16k-p4-b256-scoped.xplane.pb")
# what the reduction read from it, pinned
RECORDED_STEPS, RECORDED_BUSY_S, RECORDED_WINDOW_S = (
    25, 0.061398795116501835, 0.063821478)
RECORDED_METRICS = {
    "dispatch_ms": 1.29654564,
    "vector_ms": 2.0383870444198995,
    "matmul_ms": 0.3950561829000366,
    "matmul_roofline": 66.45487568651112,
    "step_mfu": 9.036666214852275,
    "collective_ms": 0.13514025306000024,
    "exposed_collective_ms": 0.022508577340001164,
    "idle_share": 3.7960306771619545,
    "forward_ms": 0.4073558850800192,
    "backward_ms": 0.6177153452800072,
    "optimizer_ms": 1.4270115765800264,
}
RECORDED_TOP_OP = ["optimizer:multiply_subtract_fusion", 0.035409530976999996]

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reduced(events, cell="phantom16k-p4-b256", scope_map=None):
    return devtrace.Reduced(events, spec.load_cell(cell), peak=PEAK,
                            scopes=scope_map or {})


@pytest.mark.parametrize("name,category,cls", [
    ("fusion.12", "convolution fusion", "matmul"),
    ("convolution.3", "convolution", "matmul"),
    ("phantom_fused", "tpu_custom_call", "matmul"),
    ("all-gather.1", "all-gather", "collective"),
    ("all-reduce-start.2", "all-reduce", "collective"),
    ("reduce-scatter.7", "reduce-scatter", "collective"),
    ("fusion.40", "loop fusion", "vector"),
    ("copy.3", "data formatting", "vector"),
    ("async-collective-start", "custom fusion", "collective"),
    ("async-collective-done.2", "custom fusion", "collective"),
    ("fusion.7", "custom fusion", "vector"),
])
def test_classify(name, category, cls):
    assert devtrace.classify(category, name) == cls


def test_reduction_of_hand_made_events():
    # one chip, window 0..100 ns, 2 steps: matmul 0-30, an all-gather in
    # flight 20-50 (20-30 under the matmul) that the chip waits on 30-50,
    # vector 60-70, idle 50-60 and 70-100
    ev = {"window": [0, 100], "steps": 2,
          "devices": [[[0, 30, "fusion.1", "convolution fusion"],
                       [30, 20, "all-gather-done.1", "all-gather"],
                       [60, 10, "fusion.2", "loop fusion"]]],
          "async": [[[20, 30, "all-gather-start.1", "all-gather"]]],
          "host": [[0, 10, "bench.dispatch"], [10, 85, "bench.wait"],
                   [95, 5, "bench.dispatch"]]}
    r = _reduced(ev)
    assert r.busy_s == pytest.approx(60e-9)
    assert r.window_s == pytest.approx(100e-9)
    assert r.class_ms("matmul") == pytest.approx(15e-6)
    assert r.class_ms("collective") == pytest.approx(15e-6)
    assert r.class_ms("vector") == pytest.approx(5e-6)
    assert r.per_step_ms(r.exposed_collective_s) == pytest.approx(10e-6)
    assert r.host_ms("bench.dispatch") == pytest.approx(7.5e-6)
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    assert b["idle_gaps"][0] == ["wait chip0", pytest.approx(30e-9)]
    assert b["idle_gaps"][1] == ["wait chip0", pytest.approx(10e-9)]


def test_a_loop_keeps_what_its_body_leaves():
    # a while loop 0-100 whose body runs a matmul 10-30 and a vector op
    # 40-50, and an op 100-120 after it; window 0..200, 1 step
    ev = {"window": [0, 200], "steps": 1,
          "devices": [[[0, 100, "while.1", "while"],
                       [10, 20, "fusion.1", "convolution fusion"],
                       [40, 10, "fusion.2", "loop fusion"],
                       [100, 20, "fusion.3", "loop fusion"]]],
          "host": []}
    r = _reduced(ev)
    assert r.busy_s == pytest.approx(120e-9)
    assert r.class_ms("matmul") == pytest.approx(20e-6)
    assert r.class_ms("vector") == pytest.approx(100e-6)
    ops = dict(r.breakdown()["device_ops"])
    assert ops == pytest.approx({"while.1": 70e-9, "fusion.1": 20e-9,
                                 "fusion.2": 10e-9, "fusion.3": 20e-9})


def test_events_are_clipped_to_the_window():
    ev = {"window": [100, 200], "steps": 1,
          "devices": [[[50, 100, "fusion.1", "loop fusion"],
                       [190, 50, "fusion.2", "loop fusion"]]],
          "host": []}
    r = _reduced(ev)
    assert r.busy_s == pytest.approx(60e-9)
    assert r.class_ms("matmul") is None


def test_chip_with_no_op_in_the_window_is_refused():
    ev = {"window": [100, 200], "steps": 1,
          "devices": [[[0, 50, "fusion.1", "loop fusion"]]], "host": []}
    with pytest.raises(ValueError, match="clocks"):
        _reduced(ev)


def test_recorded_phantom_trace():
    """Every per-layer number of the recorded trace, to the digit; the
    ghost all-gather is an asynchronous collective fusion in flight
    across the local product, so most of the collective time is hidden."""
    cell = spec.load_cell("phantom16k-p4-b256")
    with open(scopes.map_path(RECORDED)) as f:
        scope_map = json.load(f)["scopes"]
    r = _reduced(devtrace.load(RECORDED, cell.chips), scope_map=scope_map)
    assert (r.steps, r.busy_s, r.window_s) == (
        RECORDED_STEPS, RECORDED_BUSY_S, RECORDED_WINDOW_S)
    assert {m["name"]: mod.read(r) for m, mod in cell.per_layer} == (
        RECORDED_METRICS)
    assert r.breakdown()["device_ops"][0] == RECORDED_TOP_OP
