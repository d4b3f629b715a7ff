"""Observability: tracer golden schema, metrics export, watchdog
trip/no-trip fixtures, the obs CLI, and the bench-regression gate.

The tracer tests run on a manually-advanced clock so span ids AND
timestamps are deterministic — the golden assertions pin the exact
Chrome-trace-event schema Perfetto loads (docs/observability.md)."""
import json
import os
import subprocess
import sys
import threading

import pytest

from repro.obs import (EnergyDriftWatchdog, MetricsRegistry,
                       SNAPSHOT_SCHEMA, TRACE_SCHEMA, Tracer, get_tracer,
                       load_trace, set_tracer, span_events, use_tracer)
from repro.telemetry import Ledger, LedgerEntry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManualClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_trace_golden_schema():
    """Two identical schedules on a manual clock produce byte-identical
    Chrome-trace JSON with stable span ids."""
    def build():
        clk = ManualClock()
        tr = Tracer(clock=clk, meta={"run": "test"})
        with tr.span("plan/calibrate", cat="plan", source="paper"):
            clk.advance(0.25)
        sp = tr.begin("train/run", cat="train")
        clk.advance(0.5)
        with tr.span("train/step", cat="train", step=0):
            clk.advance(0.125)
        tr.instant("fault/straggler", cat="fault", step=0)
        tr.end(sp.annotate(final_step=1))
        return tr.to_chrome()

    doc = build()
    assert json.dumps(doc, sort_keys=True) == \
        json.dumps(build(), sort_keys=True)

    assert doc["otherData"]["schema"] == TRACE_SCHEMA
    assert doc["otherData"]["run"] == "test"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}

    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    cal = spans["plan/calibrate"]
    assert cal["cat"] == "plan" and cal["pid"] == 0 and cal["tid"] == 0
    assert cal["ts"] == 0.0 and cal["dur"] == 250_000.0
    assert cal["args"]["span_id"] == "s000000"
    assert cal["args"]["source"] == "paper"
    # ids assigned at BEGIN time: train/run opened before train/step
    assert spans["train/run"]["args"]["span_id"] == "s000001"
    assert spans["train/step"]["args"]["span_id"] == "s000002"
    assert spans["train/run"]["args"]["final_step"] == 1
    assert spans["train/run"]["dur"] == 625_000.0

    inst = [e for e in evs if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["s"] == "t"
    assert inst[0]["name"] == "fault/straggler"


def test_unclosed_span_survives_crash_dump():
    clk = ManualClock()
    tr = Tracer(clock=clk)
    tr.begin("train/run", cat="train")
    clk.advance(1.0)
    evs = span_events(tr.to_chrome())
    assert len(evs) == 1
    assert evs[0]["args"]["unclosed"] is True
    assert evs[0]["dur"] == 1_000_000.0


def test_null_tracer_is_free_noop():
    tr = Tracer(enabled=False)
    sp = tr.begin("x")
    sp.annotate(a=1).link_ledger(None)
    tr.end(sp)
    tr.instant("y")
    with tr.span("z"):
        pass
    assert len(tr) == 0
    # the module default is disabled
    assert get_tracer().enabled is False or get_tracer() is not None


def _host_events(profile_dir):
    """(name, start ns, end ns) of every event on a capture's host
    plane."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for line in plane.lines for ev in line.events]
    return out


def test_tracer_spans_land_on_the_profiler_clock(tmp_path):
    """An enabled tracer's span shows on a profiler capture's host
    plane, inside the annotation that encloses it; a disabled tracer's
    span adds no event."""
    import jax
    from jax.profiler import TraceAnnotation

    on, off = Tracer(), Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.outer"):
            with on.span("obs/enabled", cat="test"):
                sum(range(1000))
            with off.span("obs/disabled", cat="test"):
                sum(range(1000))
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    (_, o0, o1), = [e for e in evs if e[0] == "test.outer"]
    (_, s0, s1), = [e for e in evs if e[0] == "obs/enabled"]
    assert o0 <= s0 <= s1 <= o1
    assert not [e for e in evs if e[0] == "obs/disabled"]
    assert [e["name"] for e in span_events(on.to_chrome())] == [
        "obs/enabled"]
    assert len(off) == 0


def test_set_tracer_restores_previous():
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        assert get_tracer() is tr
    finally:
        set_tracer(prev)
    assert get_tracer() is not tr


def test_span_links_ledger_entry():
    tr = Tracer()
    entry = LedgerEntry(
        name="train_smoke_phantom", suite="train", kind="train",
        measured={"wall_us_median": 123.0, "total_s": 0.5, "calls": 4},
        predicted={"energy_j_per_iter": 1.5})
    with tr.span("train/run", cat="train") as sp:
        sp.link_ledger(entry)
    ev = span_events(tr.to_chrome())[0]
    link = ev["args"]["ledger"]
    assert link["entry"] == "train_smoke_phantom"
    assert link["wall_us_median"] == 123.0
    assert link["predicted_energy_j_per_iter"] == 1.5


def test_worker_thread_gets_own_tid():
    tr = Tracer()
    with tr.span("main/work"):
        t = threading.Thread(
            target=lambda: tr.end(tr.begin("ckpt/save", cat="ckpt")))
        t.start()
        t.join()
    evs = span_events(tr.to_chrome())
    tids = {e["name"]: e["tid"] for e in evs}
    assert tids["main/work"] == 0
    assert tids["ckpt/save"] == 1
    names = {e["args"]["name"] for e in tr.to_chrome()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"main", "worker-1"}


def test_trace_write_load_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("serve/prefill", cat="serve"):
        pass
    p = tr.write(str(tmp_path / "trace.json"))
    doc = load_trace(p)
    assert span_events(doc, cat="serve", name_prefix="serve/")
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.json"
        bad.write_text("[]")
        load_trace(str(bad))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    c = reg.counter("train_steps_total", "steps run")
    c.inc(3, suite="elastic")
    reg.gauge("pipeline_bubble_fraction").set(0.25, stages="2")
    h = reg.histogram("step_seconds", "step wall", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert reg.to_prometheus() == (
        "# TYPE pipeline_bubble_fraction gauge\n"
        'pipeline_bubble_fraction{stages="2"} 0.25\n'
        "# HELP step_seconds step wall\n"
        "# TYPE step_seconds histogram\n"
        'step_seconds_bucket{le="0.1"} 1\n'
        'step_seconds_bucket{le="1"} 2\n'
        'step_seconds_bucket{le="+Inf"} 3\n'
        "step_seconds_sum 5.55\n"
        "step_seconds_count 3\n"
        "# HELP train_steps_total steps run\n"
        "# TYPE train_steps_total counter\n"
        'train_steps_total{suite="elastic"} 3\n')


def test_registration_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        a.inc(-1)
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=())


def test_jsonl_snapshot_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve_prefill_tokens_total").inc(64, arch="ffn")
    reg.histogram("ttft_ms", buckets=(1, 10)).observe(3.0, arch="ffn")
    p = str(tmp_path / "metrics.jsonl")
    reg.write(p, meta={"run": "t"})
    reg.write(p)     # appends a second snapshot
    lines = [json.loads(ln) for ln in open(p)]
    assert len(lines) == 2
    snap = lines[0]
    assert snap["schema"] == SNAPSHOT_SCHEMA
    assert snap["meta"]["run"] == "t"
    m = snap["metrics"]["serve_prefill_tokens_total"]
    assert m["kind"] == "counter"
    assert m["values"]['{arch="ffn"}'] == 64
    hist = snap["metrics"]["ttft_ms"]["values"]['{arch="ffn"}']
    assert hist["count"] == 1 and hist["buckets"]["10"] == 1


def test_metrics_concurrent_updates_are_exact():
    """The checkpoint writer thread and the step loop both record; the
    registry lock must not drop increments."""
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("v", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000
    assert h.count() == 8000


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_silent_on_clean_run():
    wd = EnergyDriftWatchdog(predicted_s=0.1)
    for step in range(50):
        assert wd.observe(step, 0.1 + 0.01 * (step % 3)) is None
    assert wd.trips == []
    assert wd.summary()["observations"] == 50


def test_watchdog_spike_trips_and_records_anomaly():
    ledger = Ledger(run="t")
    wd = EnergyDriftWatchdog(predicted_s=0.1, ledger=ledger,
                             name="wd", profile_dir="/tmp/none")
    for step in range(5):
        wd.observe(step, 0.1)
    ev = wd.observe(5, 0.65)            # ratio 6.5 >= spike_factor 3
    assert ev is not None and ev.kind == "spike"
    assert ev.ratio == pytest.approx(6.5)
    assert wd.capture_pending()
    rows = [e for e in ledger.entries if e.kind == "anomaly"]
    assert len(rows) == 1
    assert rows[0].suite == "obs"
    assert rows[0].extra["event"] == "watchdog_spike"
    assert rows[0].measured["step"] == 5


def test_watchdog_drift_trips_on_window_mean():
    wd = EnergyDriftWatchdog(predicted_s=0.1, window=4)
    for step in range(8):
        wd.observe(step, 0.1)
    # creep up: each ratio 2.6 is under the 3.0 spike threshold, but
    # the trailing-window mean leaves the (0.5, 2.0) band
    kinds = []
    for step in range(8, 16):
        ev = wd.observe(step, 0.26)
        if ev:
            kinds.append(ev.kind)
    assert kinds == ["drift"]           # cooldown mutes the rest


def test_watchdog_cooldown_mutes_repeats():
    wd = EnergyDriftWatchdog(predicted_s=0.1, cooldown=5)
    trips = sum(1 for step in range(20)
                if wd.observe(step, 1.0) is not None)
    # 20 spiking observations, cooldown 5 -> at most every 6th trips
    assert 1 <= trips <= 4
    assert len(wd.trips) == trips


def test_watchdog_self_baseline_when_no_prediction():
    wd = EnergyDriftWatchdog(min_samples=3)
    for step in range(3):
        assert wd.observe(step, 0.2) is None     # collecting baseline
    assert wd.reference_s() == pytest.approx(0.2)
    ev = wd.observe(3, 1.0)                      # 5x the baseline
    assert ev is not None and ev.kind == "spike"


def test_watchdog_capture_oneshot(monkeypatch, tmp_path):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    wd = EnergyDriftWatchdog(predicted_s=0.1,
                             profile_dir=str(tmp_path / "prof"))
    assert wd.capture(lambda: 7) == 7            # not armed: plain call
    assert calls == []
    for step in range(5):
        wd.observe(step, 0.1)
    wd.observe(5, 1.0)                           # trip arms the capture
    assert wd.capture_pending()
    assert wd.capture(lambda x: x + 1, 1) == 2
    assert calls == [("start", str(tmp_path / "prof")), ("stop",)]
    assert not wd.capture_pending()              # one-shot
    assert wd.captures == [str(tmp_path / "prof")]


# ---------------------------------------------------------------------------
# the obs CLI
# ---------------------------------------------------------------------------

def _write_recovery_fixture(tmp_path, *, replan_s=0.2, restore_s=0.3,
                            compile_s=1.5, span_scale=1.0):
    """A trace + report pair whose recovery views agree up to scale."""
    clk = ManualClock()
    tr = Tracer(clock=clk)
    for name, secs in (("elastic/compile", compile_s),
                       ("elastic/replan", replan_s),
                       ("elastic/restore", restore_s)):
        with tr.span(name, cat="elastic"):
            clk.advance(secs * span_scale)
    trace = str(tmp_path / "trace.json")
    tr.write(trace)
    report = str(tmp_path / "report.json")
    with open(report, "w") as f:
        json.dump({"entries": [
            {"name": "elastic_run", "kind": "elastic",
             "extra": {"recovery": {
                 "schema": "recovery-account/v1",
                 "replan_s": replan_s, "restore_s": restore_s,
                 "compile_s": compile_s}}}]}, f)
    return trace, report


def test_obs_cli_verify_recovery(tmp_path, capsys):
    from repro.launch.obs import main as obs_main
    trace, report = _write_recovery_fixture(tmp_path)
    assert obs_main(["verify-recovery", "--trace", trace,
                     "--report", report]) == 0
    assert "OK" in capsys.readouterr().out

    trace, report = _write_recovery_fixture(tmp_path, span_scale=2.0)
    assert obs_main(["verify-recovery", "--trace", trace,
                     "--report", report]) == 1
    assert "FAIL" in capsys.readouterr().err


def test_obs_cli_verify_recovery_clean_run(tmp_path, capsys):
    """compile-only views (no recovery) must still reconcile."""
    from repro.launch.obs import main as obs_main
    trace, report = _write_recovery_fixture(
        tmp_path, replan_s=0.0, restore_s=0.0, compile_s=0.8)
    assert obs_main(["verify-recovery", "--trace", trace,
                     "--report", report]) == 0


def test_obs_cli_summary_and_metrics(tmp_path, capsys):
    from repro.launch.obs import main as obs_main
    trace, _ = _write_recovery_fixture(tmp_path)
    assert obs_main(["summary", "--trace", trace]) == 0
    out = capsys.readouterr().out
    assert "elastic" in out and "3 spans" in out

    reg = MetricsRegistry()
    reg.counter("a_total").inc()
    pj = str(tmp_path / "m.jsonl")
    reg.write(pj)
    assert obs_main(["metrics", pj]) == 0
    pp = str(tmp_path / "m.prom")
    reg.write(pp)
    assert obs_main(["metrics", pp]) == 0
    assert "a_total 1" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# ci/check_bench_regression.py
# ---------------------------------------------------------------------------

def _fake_report(tmp_path, *, seconds=2.0, ratio=1.0, status="ok"):
    rep = {"suites": {"train_smoke": {"status": status,
                                      "seconds": seconds}},
           "entries": [{"name": "train_smoke_phantom",
                        "ratios": {"energy_j_per_iter": ratio}}]}
    p = str(tmp_path / "rep.json")
    with open(p, "w") as f:
        json.dump(rep, f)
    return p


def _check(args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "ci",
                                      "check_bench_regression.py")]
        + args, capture_output=True, text=True)


def test_bench_regression_gate(tmp_path):
    rep = _fake_report(tmp_path)
    base = str(tmp_path / "base.json")
    r = _check(["--report", rep, "--baseline", base,
                "--update-baseline"])
    assert r.returncode == 0, r.stderr

    # fresh baseline passes
    r = _check(["--report", rep, "--baseline", base])
    assert r.returncode == 0, r.stderr + r.stdout
    assert "OK" in r.stdout

    # perturbed ratio fails
    bad = _fake_report(tmp_path, ratio=2.0)
    r = _check(["--report", bad, "--baseline", base])
    assert r.returncode == 1
    assert "ratio train_smoke_phantom/energy_j_per_iter" in r.stderr

    # suite wall-time blowup fails
    slow = _fake_report(tmp_path, seconds=60.0)
    r = _check(["--report", slow, "--baseline", base])
    assert r.returncode == 1
    assert "wall" in r.stderr

    # failed suite status fails regardless of bands
    broke = _fake_report(tmp_path, status="failed")
    r = _check(["--report", broke, "--baseline", base])
    assert r.returncode == 1


def test_bench_regression_checked_in_baseline_matches_schema():
    p = os.path.join(ROOT, "ci", "bench_baseline.json")
    base = json.load(open(p))
    assert base["schema"] == "bench-baseline/v1"
    assert base["suites"] and base["ratios"]
    # satellite: the analytic suites must report real (non-zero) wall
    # seconds now that run.py times them with perf_counter
    for name in ("fig6_large", "roofline"):
        assert base["suites"][name] > 0.0, (name, base["suites"])


# ---------------------------------------------------------------------------
# elastic integration: trace spans vs the priced recovery account
# ---------------------------------------------------------------------------

def _elastic_cfg(tmp_path, **kw):
    from repro.train.elastic import ElasticConfig
    base = dict(workdir=str(tmp_path / "elastic"), devices=8, hosts=4,
                width=32, depth=2, batch=16, target_loss=1e-9,
                max_steps=24, checkpoint_every=5, ks=(4,),
                audit_replan=False, heartbeat_timeout_s=2.5,
                initial_strategy="tensor_col")
    base.update(kw)
    return ElasticConfig(**base)


def test_elastic_trace_matches_recovery_account(tmp_path):
    from repro.train.elastic import run_elastic
    from repro.train.fault import FaultScript

    tr = Tracer()
    with use_tracer(tr):
        res = run_elastic(_elastic_cfg(tmp_path), ledger=Ledger(run="t"),
                          fault_script=FaultScript(
                              kills=((12, "host3"),)),
                          log_fn=lambda *a, **k: None)
    assert not res.aborted and len(res.recoveries) == 1

    doc = tr.to_chrome()
    names = {e["name"] for e in span_events(doc)}
    assert {"elastic/run", "elastic/plan", "elastic/compile",
            "elastic/replan", "elastic/restore",
            "elastic/step"} <= names
    # the detection instant marks the trace
    assert any(e["name"] == "elastic/detect"
               for e in doc["traceEvents"] if e["ph"] == "i")

    # recovery spans must sum to the priced recovery-account seconds
    from repro.launch.obs import RECOVERY_SPANS
    span_s = sum(e["dur"] * 1e-6 for e in span_events(doc)
                 if e["name"] in RECOVERY_SPANS)
    acct = res.account
    assert acct["schema"] == "recovery-account/v1"
    acct_s = sum(float(acct.get(k, 0.0))
                 for k in RECOVERY_SPANS.values())
    assert acct_s > 0
    assert span_s == pytest.approx(acct_s, rel=0.35)

    # the run span links the elastic ledger entry
    run_ev = [e for e in span_events(doc)
              if e["name"] == "elastic/run"][0]
    assert run_ev["args"]["ledger"]["kind"] == "elastic"


def test_elastic_slow_step_trips_watchdog(tmp_path):
    """An injected slow step trips the watchdog mid-run (anomaly row in
    the ledger); the same config without the injection stays silent.
    Both runs report virtual step times, so the host's load cannot
    make a spike of its own."""
    from repro.train.elastic import run_elastic

    ledger = Ledger(run="t")
    wd = EnergyDriftWatchdog(ledger=ledger, name="t")
    res = run_elastic(_elastic_cfg(tmp_path, max_steps=16,
                                   slow_steps=(12,),
                                   virtual_step_time=True),
                      watchdog=wd, ledger=ledger,
                      log_fn=lambda *a, **k: None)
    assert not res.aborted
    assert any(t.kind == "spike" and t.step == 12 for t in wd.trips)
    assert any(e.kind == "anomaly" for e in ledger.entries)

    wd2 = EnergyDriftWatchdog(name="t2")
    res2 = run_elastic(_elastic_cfg(tmp_path, max_steps=16,
                                    workdir=str(tmp_path / "clean"),
                                    virtual_step_time=True),
                       watchdog=wd2, log_fn=lambda *a, **k: None)
    assert not res2.aborted
    assert wd2.trips == []
