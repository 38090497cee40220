"""Runtime telemetry subsystem (paddle_tpu/observability): registry,
sinks, StepMonitor math, recompile sentinel, collective accounting,
preemption events.

Reference capability: PaddlePaddle's profiler/monitor stack (SURVEY
§5.5) — always-on runtime statistics.  Everything here runs on the CPU
backend; MFU uses the nominal 1e12 cpu peak from observability/mfu.py.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import warnings

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu.observability import _state as obs_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def telemetry(tmp_path):
    sink = obs.InMemorySink()
    # postmortem path pinned into tmp (the preemption test drains the
    # ring); crash hooks off — pytest owns excepthook/atexit
    tel = obs.enable(sinks=[sink], storm_threshold=2, storm_window_s=60.0,
                     postmortem_path=str(tmp_path / "t.postmortem"),
                     crash_hooks=False)
    yield tel, sink
    obs.disable()


@pytest.fixture(autouse=True)
def _always_disabled_after():
    yield
    obs.disable()


# -- registry ----------------------------------------------------------------

def test_registry_counter_gauge():
    reg = obs.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(41)
    reg.gauge("g").set(3.5)
    assert reg.counter("c").value == 42
    assert reg.gauge("g").value == 3.5
    assert reg.snapshot()["c"] == 42


def test_registry_kind_collision_raises():
    reg = obs.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_rolling_percentiles():
    reg = obs.MetricsRegistry()
    h = reg.histogram("h", window=1000)
    for v in range(1, 101):   # 1..100
        h.observe(v)
    # nearest-rank: p50 = 50th smallest, p95 = 95th smallest
    assert h.percentile(50) == 50
    assert h.percentile(95) == 95
    snap = reg.snapshot()["h"]
    assert snap["count"] == 100 and snap["p50"] == 50 and snap["p95"] == 95
    # rolling: a small window only sees the latest observations
    h2 = obs.Histogram("h2", window=10)
    for v in range(1, 101):
        h2.observe(v)
    assert h2.percentile(50) == 95  # window holds 91..100


def test_registry_thread_safety():
    reg = obs.MetricsRegistry()

    def work():
        for _ in range(2000):
            reg.counter("n").inc()
            reg.histogram("hh").observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("n").value == 16000
    assert reg.histogram("hh").count == 16000


# -- sinks -------------------------------------------------------------------

def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    arr = jnp.float32(2.5)   # before enable: its jit is not an event
    tel = obs.enable(jsonl_path=path)
    tel.emit({"event": "custom", "n": 1, "arr": arr})
    obs.disable()   # metrics snapshot + close
    lines = [json.loads(l) for l in open(path)]
    custom = next(l for l in lines if l["event"] == "custom")
    assert custom["n"] == 1 and custom["arr"] == 2.5 and "ts" in custom
    assert lines[-1]["event"] == "metrics"


def test_disabled_by_default_and_hooks_clear():
    assert not obs.enabled()
    assert obs_state.MONITOR[0] is None
    assert obs_state.COLLECTIVE[0] is None
    assert obs_state.EMIT[0] is None
    assert obs_state.SPAN[0] is None
    assert obs_state.RECORDER[0] is None
    assert obs_state.POSTMORTEM[0] is None
    obs.emit_event("nothing")  # no-op, must not raise
    tel = obs.enable(crash_hooks=False)
    assert obs.enabled() and obs_state.MONITOR[0] is tel.monitor
    assert obs_state.RECORDER[0] is tel.recorder
    assert obs_state.SPAN[0] is not None
    obs.disable()
    assert not obs.enabled() and obs_state.MONITOR[0] is None
    assert obs_state.SPAN[0] is None and obs_state.RECORDER[0] is None
    assert obs_state.POSTMORTEM[0] is None


# -- StepMonitor -------------------------------------------------------------

def _tiny_trainstep():
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import TrainStep
    model = nn.Linear(8, 8)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    loss = lambda m, b: ((m(b["x"]) - b["y"]) ** 2).mean()
    step = TrainStep(model, loss, opt)
    state = step.init_state()
    batch = {"x": jnp.ones((4, 8)), "y": jnp.zeros((4, 8))}
    return step, state, batch


def test_step_monitor_emits_step_events(telemetry):
    tel, sink = telemetry
    step, state, batch = _tiny_trainstep()
    for _ in range(5):
        state, _ = step(state, batch)
    events = sink.events("step")
    assert len(events) == 5
    for ev in events:
        assert ev["site"] == "TrainStep(Linear)"
        assert ev["wall_ms"] > 0 and ev["interval_ms"] > 0
        assert ev["tokens"] == 32                    # 4 x 8 batch
        assert "tokens_per_sec" in ev and "mfu" in ev
    assert events[0]["warmup"] is True               # compile step
    assert events[-1]["warmup"] is False
    # registry mirrors: count + rolling interval histogram
    reg = tel.registry
    assert reg.counter("step[TrainStep(Linear)].count").value == 5
    assert reg.histogram("step[TrainStep(Linear)].interval_ms").count == 4


def test_step_monitor_mfu_matches_bench_math(telemetry):
    """Runtime MFU and bench.py's MFU use the same formula by
    construction: recompute the event's mfu from its own tokens_per_sec
    and the shared flops-per-token function."""
    tel, sink = telemetry
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import causal_lm_loss, llama
    from paddle_tpu.observability.mfu import (causal_lm_flops_per_token,
                                              peak_flops)
    pt.seed(0)
    model = llama("tiny", max_position_embeddings=16)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = TrainStep(model, causal_lm_loss, opt)
    state = step.init_state(seed=0)
    ids = jax.random.randint(jax.random.key(0), (2, 16), 0,
                             model.cfg.vocab_size)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    for _ in range(5):
        state, _ = step(state, batch)
    events = sink.events("step")
    assert len(events) >= 5    # the 5-step llama smoke contract
    assert all("tokens_per_sec" in e and "mfu" in e for e in events)
    ev = events[-1]
    assert ev["tokens"] == 32                        # 2 x 16
    fpt = causal_lm_flops_per_token(model.cfg.num_params(),
                                    model.cfg.num_hidden_layers,
                                    model.cfg.hidden_size, 16)
    expect = ev["tokens_per_sec"] * fpt / peak_flops()
    assert ev["mfu"] == pytest.approx(expect, rel=1e-3, abs=1e-4)


def test_hapi_model_feeds_monitor(telemetry):
    tel, sink = telemetry
    from paddle_tpu import nn, optimizer
    net = nn.Linear(4, 2)
    model = pt.Model(net)
    model.prepare(optimizer.SGD(learning_rate=0.1,
                                parameters=net.parameters()),
                  loss=lambda pred, label: ((pred - label) ** 2).mean())
    x = jnp.ones((4, 4))
    y = jnp.zeros((4, 2))
    for _ in range(3):
        model.train_batch([x], [y])
    events = [e for e in sink.events("step")
              if e["site"] == "hapi.Model(Linear)"]
    assert len(events) == 3
    assert events[-1]["tokens"] == 16                # 4 x 4 input


def test_engine_fit_emits_steps_and_epochs(telemetry):
    tel, sink = telemetry
    import paddle_tpu.distributed as dist
    from paddle_tpu import nn, optimizer
    model = nn.Linear(8, 8)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    loss = lambda m, b: ((m(b["x"]) - b["y"]) ** 2).mean()
    engine = dist.Engine(model, loss=loss, optimizer=opt)
    data = [{"x": jnp.ones((2, 8)), "y": jnp.zeros((2, 8))}] * 3
    engine.fit(data, epochs=2)
    steps = sink.events("step")
    epochs = sink.events("epoch")
    assert len(steps) == 6 and len(epochs) == 2
    assert epochs[0]["steps"] == 3 and "loss" in epochs[0]


# -- recompile sentinel ------------------------------------------------------

def test_recompile_sentinel_counts_shape_change(telemetry):
    tel, sink = telemetry
    before = tel.sentinel.compiles()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones((3,)))
    f(jnp.ones((3,)))        # cache hit: no compile
    f(jnp.ones((5,)))        # shape change: recompile
    assert tel.sentinel.compiles() - before >= 2
    compiles = sink.events("compile")
    assert len(compiles) >= 2
    assert all(c["duration_ms"] >= 0 for c in compiles)
    assert tel.registry.counter("compile.count").value >= 2


def test_recompile_storm_warning(telemetry):
    """The classic shape-churn failure: one jit site compiling on every
    call trips the loud warning (threshold 2 in the fixture)."""
    tel, sink = telemetry
    f = jax.jit(lambda x: x + 1)
    # inputs built OUTSIDE the scope: jnp.ones itself compiles per shape
    # and those compiles must not be attributed to the churny site
    xs = [jnp.ones((n,)) for n in (3, 5, 7, 9, 11)]
    with pytest.warns(obs.RecompileStormWarning, match="recompile storm"):
        with tel.sentinel.site("churny-step"):
            for x in xs:
                f(x)
    storms = sink.events("recompile_storm")
    assert storms and storms[0]["site"] == "churny-step"
    assert storms[0]["compiles_after_warmup"] >= 2
    assert tel.sentinel.compiles("churny-step") == 5


def test_trainstep_shape_churn_attributed(telemetry):
    """Shape churn THROUGH TrainStep is attributed to its site and
    trips the storm warning without any manual site scope."""
    tel, sink = telemetry
    step, state, _ = _tiny_trainstep()
    with pytest.warns(obs.RecompileStormWarning):
        for b in (2, 3, 4, 5):   # batch-size churn: recompile per step
            batch = {"x": jnp.ones((b, 8)), "y": jnp.zeros((b, 8))}
            state, _ = step(state, batch)
    sites = {c["site"] for c in sink.events("compile")}
    assert "TrainStep(Linear)" in sites
    storms = sink.events("recompile_storm")
    assert any(s["site"] == "TrainStep(Linear)" for s in storms)


def test_unattributed_compiles_do_not_storm(telemetry):
    tel, sink = telemetry
    f = jax.jit(lambda x: x - 1)
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.RecompileStormWarning)
        for n in (2, 3, 4, 5, 6):   # no site scope: counted, never warns
            f(jnp.ones((n,)))
    assert tel.sentinel.compiles() >= 5
    assert not sink.events("recompile_storm")


# -- collective accounting ---------------------------------------------------

def test_collective_byte_counters(telemetry):
    tel, sink = telemetry
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": jax.device_count()}
    fleet.init(strategy=strategy)
    try:
        x = jnp.ones((4, 4), jnp.float32)
        dist.all_reduce(x)
        dist.all_reduce(x)
        reg = tel.registry
        assert reg.counter("collective.all_reduce.calls").value == 2
        assert reg.counter("collective.all_reduce.bytes").value == 2 * 64
        # paddle-style list signature: the payload is the SECOND arg (the
        # first is the empty output list) — bytes must still be counted
        out = []
        dist.all_gather(out, x)
        assert reg.counter("collective.all_gather.bytes").value == 64
    finally:
        fleet._reset()
    obs.disable()
    # snapshot carried into the final metrics event
    snap = [e for e in sink.events("metrics")][-1]["metrics"]
    assert snap["collective.all_reduce.bytes"] == 128


# -- preemption events -------------------------------------------------------

def test_preemption_event(telemetry):
    tel, sink = telemetry
    from paddle_tpu.launch.preempt import PreemptionGuard
    saved = []
    guard = PreemptionGuard(save_fn=lambda: saved.append(1))
    with guard:
        signal.raise_signal(signal.SIGTERM)
        signal.raise_signal(signal.SIGTERM)   # repeat signal: one event
    assert guard.preempted and saved == [1]
    events = sink.events("preemption")
    assert len(events) == 1
    assert events[0]["reason"] == "SIGTERM"
    assert "ts" in events[0] and "step" in events[0]


def test_preemption_drains_postmortem(telemetry, tmp_path):
    """The first SIGTERM drains the flight ring to the .postmortem file
    from inside the signal handler — a preempted run is never blind even
    if the SIGKILL follow-up lands before the grace window ends."""
    tel, sink = telemetry
    from paddle_tpu.launch.preempt import PreemptionGuard
    tel.emit({"event": "custom", "marker": 17})
    with PreemptionGuard():
        signal.raise_signal(signal.SIGTERM)
    pm_path = tmp_path / "t.postmortem"   # fixture-pinned path
    assert pm_path.exists()
    lines = [json.loads(l) for l in open(pm_path)]
    assert lines[0]["event"] == "postmortem"
    assert lines[0]["reason"] == "preemption:SIGTERM"
    kinds = [l["event"] for l in lines]
    assert "thread_stack" in kinds and "metrics" in kinds
    assert any(l.get("marker") == 17 for l in lines)   # ring drained
    # the preemption event itself was emitted first, so it is in the ring
    assert any(l.get("event") == "preemption" for l in lines)


# -- flight recorder ---------------------------------------------------------

def test_flight_recorder_ring_bounded():
    rec = obs.FlightRecorder(capacity=8)
    for i in range(50):
        rec.record("beat", i=i)
    assert len(rec) == 8 and rec.total == 50
    events = rec.snapshot()
    assert [e["i"] for e in events] == list(range(42, 50))
    assert rec.age_s() < 5.0


def test_flight_recorder_sees_events_and_breadcrumbs(telemetry):
    """Every emitted event lands in the ring, and the step span leaves
    begin breadcrumbs even though the step event carries the numbers."""
    tel, sink = telemetry
    step, state, batch = _tiny_trainstep()
    for _ in range(2):
        state, _ = step(state, batch)
    rec = obs.get_flight_recorder()
    assert rec is tel.recorder and rec is not None
    kinds = [e["event"] for e in rec.snapshot()]
    assert "step" in kinds           # emitted event recorded
    assert "span_begin" in kinds     # breadcrumb BEFORE the step ran
    begins = [e for e in rec.snapshot() if e["event"] == "span_begin"]
    assert any(e["name"] == "TrainStep(Linear)" for e in begins)


# -- trace spans -------------------------------------------------------------

def test_span_disabled_is_noop():
    assert obs_state.SPAN[0] is None
    with obs.span("nothing"):
        pass                          # no telemetry, no profiler: no-op


def test_span_event_registry_breadcrumb(telemetry):
    tel, sink = telemetry
    with obs.span("my.op", tag="x"):
        pass
    ev = sink.events("span")
    assert len(ev) == 1
    assert ev[0]["name"] == "my.op" and ev[0]["tag"] == "x"
    assert ev[0]["ms"] >= 0
    assert tel.registry.histogram("span[my.op].ms").count == 1
    kinds = [e["event"] for e in tel.recorder.snapshot()]
    assert "span_begin" in kinds
    # emitted span event is in the ring once (no duplicate span_end)
    assert kinds.count("span") == 1 and "span_end" not in kinds


def test_span_feeds_profiler_chrome_trace(tmp_path):
    """The profiler bridge works WITHOUT telemetry: a span inside a
    recording Profiler lands on the host timeline under the same name —
    one vocabulary for JSONL and the deep-dive trace."""
    from paddle_tpu import profiler
    assert not obs.enabled()
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    assert profiler.is_recording()
    with obs.span("bridge.op"):
        pass
    rows = {r[0] for r in prof.aggregate()}
    assert "bridge.op" in rows
    path = str(tmp_path / "trace.json")
    prof.export(path)
    prof.stop()
    names = {e["name"] for e in profiler.load_profiler_result(path)["traceEvents"]}
    assert "bridge.op" in names


def test_ckpt_and_collective_spans(telemetry, tmp_path):
    tel, sink = telemetry
    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    path = str(tmp_path / "obj.pd")
    pt.save({"w": jnp.ones((3,))}, path)
    pt.load(path)
    names = [e["name"] for e in sink.events("span")]
    assert "ckpt.save" in names and "ckpt.load" in names
    # eager collective span: begin breadcrumb lands before the op blocks
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": jax.device_count()}
    fleet.init(strategy=strategy)
    try:
        dist.all_reduce(jnp.ones((2, 2)))
    finally:
        fleet._reset()
    names = [e["name"] for e in sink.events("span")]
    assert "collective.all_reduce" in names
    begins = [e["name"] for e in tel.recorder.snapshot()
              if e["event"] == "span_begin"]
    assert "collective.all_reduce" in begins


def test_engine_epoch_span(telemetry):
    tel, sink = telemetry
    import paddle_tpu.distributed as dist
    from paddle_tpu import nn, optimizer
    model = nn.Linear(8, 8)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    loss = lambda m, b: ((m(b["x"]) - b["y"]) ** 2).mean()
    engine = dist.Engine(model, loss=loss, optimizer=opt)
    data = [{"x": jnp.ones((2, 8)), "y": jnp.zeros((2, 8))}] * 2
    engine.fit(data, epochs=2)
    spans = [e for e in sink.events("span")
             if e["name"] == "Engine.fit.epoch"]
    assert len(spans) == 2 and spans[1]["epoch"] == 1


# -- hang watchdog -----------------------------------------------------------

def test_watchdog_fires_on_wedged_step(telemetry, tmp_path):
    """Acceptance: a wedged fake step trips the watchdog within its
    deadline and the post-mortem holds thread stacks, the last-N flight
    events, and a registry snapshot."""
    tel, sink = telemetry
    import time
    pm = str(tmp_path / "hang.postmortem")
    wd = obs.HangWatchdog(deadline_s=0.3, recorder=tel.recorder,
                          registry=tel.registry, emit=tel.emit,
                          postmortem_path=pm)
    wd.start()
    try:
        tel.registry.counter("sentinel.metric").inc(5)

        def wedged():
            time.sleep(1.0)       # > deadline: the step enters, then hangs
            return None, {}

        tel.monitor.timed_step("TrainStep(Wedged)", None,
                               {"x": jnp.ones((2, 4))}, wedged)
    finally:
        wd.stop()
    assert wd.fired == 1          # one dump per stall episode
    assert wd.last_dump == pm and os.path.exists(pm)
    lines = [json.loads(l) for l in open(pm)]
    head = lines[0]
    assert head["event"] == "postmortem" and "hang" in head["reason"]
    stacks = [l for l in lines if l["event"] == "thread_stack"]
    assert stacks
    # the wedged thread's stack shows WHERE it is stuck
    assert any("wedged" in "\n".join(s["frames"]) for s in stacks)
    # flight ring drained: the step's begin breadcrumb is the last beat
    begins = [l for l in lines if l.get("event") == "span_begin"]
    assert any(b["name"] == "TrainStep(Wedged)" for b in begins)
    # registry snapshot present
    metrics = [l for l in lines if l.get("event") == "metrics"]
    assert metrics and metrics[-1]["metrics"]["sentinel.metric"] == 5
    # the hang event reached the sinks too
    hangs = sink.events("hang")
    assert hangs and hangs[0]["postmortem"] == pm


def test_watchdog_enable_wiring_and_rearm(tmp_path):
    import time
    sink = obs.InMemorySink()
    pm = str(tmp_path / "wd.postmortem")
    hangs = []
    tel = obs.enable(sinks=[sink], crash_hooks=False, watchdog_s=0.25,
                     postmortem_path=pm, on_hang=hangs.append)
    try:
        assert tel.watchdog is not None and obs.get_watchdog() is tel.watchdog
        time.sleep(0.7)
        assert tel.watchdog.fired == 1     # stalled: exactly one dump
        assert hangs and hangs[0] is tel.watchdog
        with obs.span("progress"):          # beat: re-arms the watchdog
            pass
        time.sleep(0.6)
        assert tel.watchdog.fired == 2     # second stall, second dump
    finally:
        obs.disable()
    assert tel.watchdog._thread is None    # disable() stopped the thread
    assert os.path.exists(pm)


def test_enable_watchdog_requires_recorder_validates_first(telemetry):
    tel, sink = telemetry
    with pytest.raises(ValueError, match="flight recorder"):
        obs.enable(flight_recorder=False, watchdog_s=1.0)
    # validated BEFORE any side effect: the active session survives, no
    # extra compile listener / sink was created and leaked
    assert obs.get_telemetry() is tel


def test_watchdog_manual_beat_prevents_fire():
    import time
    wd = obs.HangWatchdog(deadline_s=0.3, poll_s=0.05,
                          recorder=obs.FlightRecorder())
    wd.start()
    try:
        for _ in range(10):
            time.sleep(0.1)
            wd.beat()
        assert wd.fired == 0
    finally:
        wd.stop()


# -- crash post-mortems ------------------------------------------------------

def test_write_postmortem_contents(tmp_path):
    from paddle_tpu.observability.flight_recorder import write_postmortem
    rec = obs.FlightRecorder(capacity=4)
    for i in range(6):
        rec.record("crumb", i=i)
    reg = obs.MetricsRegistry()
    reg.counter("c").inc(3)
    path = str(tmp_path / "pm.postmortem")
    out = write_postmortem(reason="test", path=path, recorder=rec,
                           registry_fn=reg.snapshot)
    assert out == path
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["reason"] == "test" and lines[0]["pid"] == os.getpid()
    meta = next(l for l in lines if l["event"] == "flight_recorder")
    assert meta["recorded"] == 4 and meta["total"] == 6
    crumbs = [l for l in lines if l["event"] == "crumb"]
    assert [c["i"] for c in crumbs] == [2, 3, 4, 5]   # last-N only
    assert lines[-1]["metrics"]["c"] == 3


_CRASH_SCRIPT = """
import sys
sys.path.insert(0, {repo!r})
import paddle_tpu.observability as obs
tel = obs.enable(jsonl_path={jsonl!r})
tel.emit({{"event": "custom", "marker": 23}})
{death}
"""


@pytest.mark.parametrize("death,reason,rc", [
    ("raise RuntimeError('boom')", "unhandled_exception", 1),
    ("sys.exit(7)", "atexit", 7),
])
def test_hard_exit_leaves_postmortem(tmp_path, death, reason, rc):
    """Acceptance: a run that dies mid-stream (unhandled exception, or a
    bare sys.exit) still leaves a readable .postmortem next to its JSONL
    — a killed run is never blind."""
    jsonl = str(tmp_path / "run.jsonl")
    r = subprocess.run(
        [sys.executable, "-c",
         _CRASH_SCRIPT.format(repo=REPO, jsonl=jsonl, death=death)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == rc, r.stderr
    pm = jsonl + ".postmortem"
    assert os.path.exists(pm)
    lines = [json.loads(l) for l in open(pm)]
    assert lines[0]["event"] == "postmortem"
    assert lines[0]["reason"] == reason
    if reason == "unhandled_exception":
        assert lines[0]["exception"]["message"] == "boom"
    kinds = [l["event"] for l in lines]
    assert "thread_stack" in kinds and "metrics" in kinds
    assert any(l.get("marker") == 23 for l in lines)
    # the post-mortem is itself a telemetry_report-readable stream
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         "--json", pm], capture_output=True, text=True, timeout=60)
    assert rep.returncode == 0, rep.stderr
    summary = json.loads(rep.stdout.strip().splitlines()[-1])
    assert summary["postmortems"] == [reason]
    assert summary["thread_stacks"] >= 1


def test_clean_disable_means_no_postmortem(tmp_path):
    """obs.disable() is the clean-shutdown signal: no dump on exit."""
    jsonl = str(tmp_path / "clean.jsonl")
    script = _CRASH_SCRIPT.format(repo=REPO, jsonl=jsonl,
                                  death="obs.disable()")
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    assert not os.path.exists(jsonl + ".postmortem")


# -- telemetry_report tool ---------------------------------------------------

def test_telemetry_report_folds_jsonl(tmp_path, telemetry):
    tel, sink = telemetry
    path = str(tmp_path / "run.jsonl")
    js = obs.JsonlSink(path)
    tel.sinks.append(js)
    step, state, batch = _tiny_trainstep()
    for _ in range(4):
        state, _ = step(state, batch)
    tel.flush()
    js.close()
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         path], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "| TrainStep(Linear) |" in r.stdout
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["sites"]["TrainStep(Linear)"]["steps"] == 4
    assert summary["compiles"]  # the TrainStep compile was attributed
    assert summary["malformed_lines"] == 0


def test_telemetry_report_truncated_and_malformed_lines(tmp_path):
    """A crash cuts the JSONL mid-line: the reporter must skip, COUNT,
    and report damaged lines — and still summarize what survived."""
    path = str(tmp_path / "cut.jsonl")
    good = {"event": "step", "site": "S", "step": 1, "wall_ms": 2.0,
            "interval_ms": 2.0, "warmup": False}
    with open(path, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write(json.dumps({"event": "span", "name": "ckpt.save",
                            "ms": 3.25}) + "\n")
        cut = json.dumps({**good, "step": 2})
        f.write(cut[:len(cut) // 2] + "\n")     # crash-truncated line
        f.write("not json at all\n")            # garbage
        f.write("1234\n")                       # parses, but not an event
        f.write("\n")                           # blank: NOT damage
        f.write(json.dumps({**good, "step": 3}) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         path], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr           # must not raise
    assert "unparseable line skipped" in r.stderr
    assert "3 malformed/truncated line(s) skipped" in r.stdout
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["malformed_lines"] == 3
    assert summary["sites"]["S"]["steps"] == 2   # survivors summarized
    assert summary["spans"]["ckpt.save"]["n"] == 1


def test_telemetry_report_folds_serving_events(tmp_path):
    """The serve_* vocabulary (docs/SERVING.md) folds into a serving
    table + `serving` summary block — no engine needed, the reporter is
    pure stdlib over the event schema."""
    path = str(tmp_path / "serve.jsonl")
    with open(path, "w") as f:
        for i, (n, ct) in enumerate([(5, 0), (23, 16), (9, 8)]):
            f.write(json.dumps({"event": "serve_request", "id": f"r{i}",
                                "prompt_len": n, "slot": i, "blocks": 2,
                                "cached_tokens": ct}) + "\n")
        for ms, tok, act, q, sp in [(4.0, 1, 1, 2, 9), (2.0, 3, 3, 0, 3),
                                    (2.5, 3, 3, 0, 3), (3.0, 2, 2, 0, 2)]:
            f.write(json.dumps({"event": "serve_step", "ms": ms,
                                "tokens": tok, "active": act, "queue": q,
                                "span_tokens": sp,
                                "kv_blocks_used": 2 * act}) + "\n")
        f.write(json.dumps({"event": "serve_finish", "id": "r0",
                            "reason": "length", "tokens": 4,
                            "ms": 11.0}) + "\n")
        f.write(json.dumps({"event": "serve_finish", "id": "r1",
                            "reason": "eos", "tokens": 2,
                            "ms": 8.0}) + "\n")
        f.write(json.dumps({"event": "metrics", "metrics": {
            "serve.prefix_hits": 3, "serve.prefix_misses": 1,
            "serve.cow_copies": 1, "serve.shared_blocks": 2,
            "serve.cached_blocks": 4,
            "serve.ragged_occupancy": {"count": 4, "sum": 1.06,
                                       "p50": 0.19, "p95": 0.56},
            "serve.prefill_rows": {"count": 5, "sum": 37.0, "p50": 4.0,
                                   "p95": 27.0, "max": 27.0},
            "serve.prefill_steps": {"count": 3, "sum": 6.0, "p50": 2.0,
                                    "p95": 3.0, "max": 3.0},
            "serve.mlp_live_tiles": {"count": 4, "sum": 7.0, "p50": 1.0,
                                     "p95": 4.0, "max": 4.0}}}) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         path], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "| Serving | |" in r.stdout
    assert "| requests (finished) | 3 (1 eos, 1 length) |" in r.stdout
    assert "| prefix pages hit / missed | 3 / 1 (0.750) |" in r.stdout
    assert "| prompt tokens from cache | 24 / 37 (0.649) |" in r.stdout
    assert "| CoW copies | 1 |" in r.stdout
    assert "| ragged occupancy p50 / p95 | 0.19 / 0.56 " \
           "(17 span tokens) |" in r.stdout
    assert "| prefill rows a step p50 / p95 / max | 4 / 27 / 27 |" \
        in r.stdout
    assert "| prefill steps to first token p50 / p95 / max | 2 / 3 / 3 |" \
        in r.stdout
    assert "| MLP token tiles a step p50 / p95 / max | 1 / 4 / 4 |" \
        in r.stdout
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    sv = summary["serving"]
    assert sv["prefill_rows_p95"] == 27.0 and sv["prefill_steps_p95"] == 3.0
    assert sv["requests"] == 3 and sv["steps"] == 4
    assert sv["tokens"] == 9
    assert sv["finished"] == {"eos": 1, "length": 1}
    assert sv["peak_active"] == 3 and sv["peak_queue"] == 2
    assert sv["peak_kv_blocks"] == 6
    assert sv["agg_tok_s"] == round(9 / (11.5 / 1e3), 1)
    assert sv["prefix_hits"] == 3 and sv["prefix_hit_rate"] == 0.75
    assert sv["cached_tokens"] == 24 and sv["span_tokens"] == 17
    assert sv["cow_copies"] == 1 and sv["shared_blocks"] == 2
    assert sv["cached_blocks"] == 4
    assert sv["ragged_occupancy_p95"] == 0.56


def test_telemetry_report_json_only_mode_counts_malformed(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write('{"event":"step","site":"S","wall_\n')
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         "--json", path], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["events"] == 0 and summary["malformed_lines"] == 1
