"""Request-lifecycle tracing, the /metrics surface, and SLO capture
(paddle_tpu.observability.trace + serving plumbing — ISSUE 12).

The load-bearing claims: (1) phase accounting is EXACT — a trace's
queue_ms + prefill_ms + decode_ms equals its wall_ms as reported,
including across preempt→restore cycles and replica-failure evacuation;
(2) the trace id survives every lifecycle detour (the tracer is keyed
by request id and the id rides Request.trace_id); (3) the operational
surfaces — Prometheus /metrics, GET /v1/requests, the Perfetto export —
render valid artifacts from the same producers.
"""

import json
import os
import re
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu import resilience as rs
from paddle_tpu import serving
from paddle_tpu.observability.sinks import (prom_name, prom_split,
                                            registry_to_prometheus)
from paddle_tpu.observability.trace import RequestTracer, SLOCapture
from paddle_tpu.serving.distributed import EngineReplicaSet

R = np.random.default_rng(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _prompt(n):
    return R.integers(0, 256, size=n).astype(np.int32)


@pytest.fixture(scope="module")
def tiny_llama():
    from paddle_tpu.models.llama import llama
    pt.seed(0)
    return llama("tiny")


@pytest.fixture
def tel():
    t = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
    try:
        yield t
    finally:
        obs.disable()


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return serving.Engine(model, **kw).warmup()


def _phases(tl):
    return [e["phase"] for e in tl["events"]]


def _assert_exact_sum(tl):
    s = tl["summary"]
    assert abs(s["queue_ms"] + s["prefill_ms"] + s["decode_ms"]
               - s["wall_ms"]) < 1e-9, s


# ---------------------------------------------------------------------------
# prometheus exposition (sinks.py)
# ---------------------------------------------------------------------------

class TestProm:
    def test_prom_split_grammar(self):
        assert prom_split("serve.replica[0].free_blocks") == \
            ("serve_replica_free_blocks", [("replica", "0")])
        assert prom_split("serve.tenant[acme].ttft_ms") == \
            ("serve_tenant_ttft_ms", [("tenant", "acme")])
        assert prom_split("span[ckpt.save].ms") == \
            ("span_ms", [("span", "ckpt.save")])
        assert prom_split("serve.tok_s") == ("serve_tok_s", [])
        # sanitation: prom name charset only
        name, _ = prom_split("weird-name.with+chars")
        assert re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", name)
        assert prom_name("9lives") == "_9lives"

    def test_registry_to_prometheus_valid_exposition(self):
        from paddle_tpu.observability.registry import MetricsRegistry
        reg = MetricsRegistry()
        reg.counter("serve.requests").inc(3)
        reg.gauge("serve.replica[0].free_blocks").set(12)
        reg.gauge("serve.replica[1].free_blocks").set(7)
        reg.gauge("serve.broken").set("not-a-number")   # must be skipped
        h = reg.histogram("serve.ttft_ms")
        for v in (10.0, 20.0, 30.0):
            h.observe(v)
        body = registry_to_prometheus(reg, extra={"serve.live": 1,
                                                  "serve.requests": 99})
        sample = re.compile(
            r"[a-zA-Z_:][a-zA-Z0-9_:]*"
            r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
            r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? -?[0-9.eE+-]+')
        typed = set()
        for line in body.strip().splitlines():
            if line.startswith("# TYPE "):
                typed.add(line.split()[2])
                continue
            assert sample.fullmatch(line), line
            # TYPE precedes samples of its series
            base = re.match(r"[a-zA-Z0-9_:]+", line).group(0)
            assert any(base.startswith(t) for t in typed), line
        assert 'serve_replica_free_blocks{replica="0"} 12' in body
        assert 'serve_ttft_ms{quantile="0.95"} 30.0' in body
        assert "serve_ttft_ms_count 3" in body
        assert "broken" not in body
        assert "serve_live 1" in body
        assert "serve_requests 3" in body       # registry wins over extra
        assert "99" not in body

    def test_prometheus_without_registry_renders_extra(self):
        body = registry_to_prometheus(None, extra={"serve.queue_depth": 2})
        assert "# TYPE serve_queue_depth gauge" in body
        assert "serve_queue_depth 2" in body


# ---------------------------------------------------------------------------
# tracer unit (deterministic fake clock)
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def tick(self, s):
        self.t += s

    def __call__(self):
        return self.t


class TestTracerUnit:
    def test_phases_sum_exactly_to_wall(self):
        clk = _Clock()
        tr = RequestTracer(clock=clk)
        tr.begin("r1", tenant="t")
        clk.tick(0.010)
        tr.transition("r1", "prefill", event="admit")
        clk.tick(0.020)
        tr.transition("r1", "decode", event="first_token")
        clk.tick(0.030)
        tr.retire("r1", reason="length", tokens=3)
        tl = tr.timeline("r1")
        s = tl["summary"]
        assert s["queue_ms"] == 10.0 and s["prefill_ms"] == 20.0
        assert s["decode_ms"] == 30.0 and s["wall_ms"] == 60.0
        assert s["done"] and s["reason"] == "length"
        _assert_exact_sum(tl)

    def test_preempt_episodes_accumulate(self):
        clk = _Clock()
        tr = RequestTracer(clock=clk)
        tr.begin("r1")
        clk.tick(0.005)
        tr.transition("r1", "decode", event="admit")
        clk.tick(0.010)
        tr.transition("r1", "queue", event="preempt")   # back to queue
        clk.tick(0.007)
        tr.transition("r1", "decode", event="admit")
        clk.tick(0.002)
        tr.retire("r1", tokens=1)
        s = tr.timeline("r1")["summary"]
        assert s["queue_ms"] == 12.0 and s["decode_ms"] == 12.0
        assert s["preempts"] == 1
        _assert_exact_sum(tr.timeline("r1"))

    def test_begin_is_get_or_create(self):
        tr = RequestTracer()
        a = tr.begin("r1", trace_id="outer")
        b = tr.begin("r1", trace_id="other")    # door→engine double begin
        assert a == b == "outer"
        assert _phases(tr.timeline("r1")).count("submit") == 1

    def test_trace_context_propagates(self):
        tr = RequestTracer()
        with obs.trace_context("ctx-id") as tid:
            assert tid == "ctx-id"
            assert tr.begin("r1") == "ctx-id"
        assert tr.begin("r2").startswith("tr-")   # generated outside

    def test_unknown_rid_is_noop(self):
        tr = RequestTracer()
        tr.point("ghost", "prefill_chunk")
        tr.transition("ghost", "decode")
        tr.retire("ghost")
        assert tr.timeline("ghost") is None

    def test_events_bounded_retire_forced(self):
        tr = RequestTracer(max_events=4)
        tr.begin("r1")
        for _ in range(10):
            tr.point("r1", "prefill_chunk", tokens=1)
        tr.retire("r1", reason="length", tokens=1)
        tl = tr.timeline("r1")
        assert len(tl["events"]) == 5               # 4 + forced retire
        assert tl["events"][-1]["phase"] == "retire"
        assert tl["summary"]["dropped_events"] == 7
        assert tl["summary"]["prefill_chunks"] == 10   # counted, not dropped

    def test_retention_bounded(self):
        tr = RequestTracer(capacity=3)
        for i in range(6):
            tr.begin(f"r{i}")
            tr.retire(f"r{i}")
        assert len(tr) == 3
        assert tr.timeline("r0") is None and tr.timeline("r5") is not None

    def test_retire_emits_serve_trace(self):
        events = []
        tr = RequestTracer(emit=events.append)
        tr.begin("r1", tenant="acme")
        tr.retire("r1", reason="eos", tokens=2)
        assert len(events) == 1
        ev = events[0]
        assert ev["event"] == "serve_trace" and ev["id"] == "r1"
        assert ev["tenant"] == "acme" and ev["summary"]["done"]
        json.dumps(ev)                              # JSONL-serializable

    def test_reused_request_id_starts_a_fresh_trace(self):
        """A request id legitimately reused (the engine's keep_finished
        window is smaller than trace_capacity) must not append onto the
        retired timeline — the second request gets its own trace and
        its own serve_trace event."""
        events = []
        tr = RequestTracer(emit=events.append)
        tr.begin("dup", trace_id="first")
        tr.retire("dup", reason="eos", tokens=1)
        tid2 = tr.begin("dup", trace_id="second")
        assert tid2 == "second"
        tr.transition("dup", "decode", event="admit")
        tr.retire("dup", reason="length", tokens=2)
        assert [e["trace_id"] for e in events] == ["first", "second"]
        tl = tr.timeline("dup")
        assert tl["trace_id"] == "second"
        assert _phases(tl).count("retire") == 1
        # late events for an already-retired trace are dropped, never
        # appended past its retire
        tr.point("dup", "prefill_chunk")
        tr.transition("dup", "queue")
        assert _phases(tr.timeline("dup"))[-1] == "retire"

    def test_find_by_trace_id(self):
        tr = RequestTracer()
        with obs.trace_context("batch-7"):
            tr.begin("a")
            tr.begin("b")
        assert {t.request_id for t in tr.find("batch-7")} == {"a", "b"}


# ---------------------------------------------------------------------------
# SLO-triggered capture
# ---------------------------------------------------------------------------

class _FakeProf:
    def __init__(self):
        self.steps = 0
        self.stopped = False

    def step(self):
        self.steps += 1

    def stop(self):
        self.stopped = True


class TestSLOCapture:
    def _seed_ttft(self, n=10, ms=100.0):
        reg = obs.get_registry()
        for _ in range(n):
            reg.histogram("serve.ttft_ms").observe(ms)

    def test_arms_after_consecutive_breaches(self, tel, tmp_path):
        profs = []

        def factory(d):
            p = _FakeProf()
            profs.append((d, p))
            return p

        cap = SLOCapture(50.0, str(tmp_path), window_steps=2, windows=2,
                         capture_steps=3, min_samples=4,
                         profiler_factory=factory)
        self._seed_ttft()
        for _ in range(3):
            cap.on_step()
        assert not cap.capturing            # only 1 breached window yet
        cap.on_step()                       # window 2 → armed
        assert cap.capturing and len(profs) == 1
        for _ in range(3):
            cap.on_step()                   # countdown
        assert not cap.capturing and profs[0][1].stopped
        assert profs[0][1].steps == 3
        assert cap.captures == [profs[0][0]]
        evs = tel.sinks[0].events("serve_slo_capture")
        assert [e["state"] for e in evs] == ["armed", "done"]
        assert evs[1]["trace_dir"] == profs[0][0]
        assert tel.registry.snapshot()["serve.slo_captures"] == 1

    def test_healthy_window_resets_and_max_captures(self, tel, tmp_path):
        made = []
        cap = SLOCapture(50.0, str(tmp_path), window_steps=1, windows=2,
                         capture_steps=1, max_captures=1, min_samples=2,
                         profiler_factory=lambda d: (made.append(d)
                                                     or _FakeProf()))
        self._seed_ttft(ms=100.0)
        cap.on_step()                       # breach 1
        self._seed_ttft(n=512, ms=1.0)      # flush the window healthy
        cap.on_step()                       # healthy → reset
        self._seed_ttft(n=512, ms=100.0)
        cap.on_step()                       # breach 1 again
        assert not cap.capturing
        cap.on_step()                       # breach 2 → armed
        cap.on_step()                       # capture step → done
        for _ in range(8):
            cap.on_step()                   # max_captures=1: never re-arms
        assert len(made) == 1 and len(cap.captures) == 1

    def test_no_signal_never_arms(self, tel, tmp_path):
        cap = SLOCapture(50.0, str(tmp_path), window_steps=1, windows=1,
                         min_samples=8,
                         profiler_factory=lambda d: _FakeProf())
        for _ in range(10):
            cap.on_step()                   # no ttft observations at all
        assert not cap.capturing and not cap.captures

    def test_engine_wiring(self, tiny_llama, tel, tmp_path):
        profs = []

        def factory(d):
            p = _FakeProf()
            profs.append(p)
            return p

        cap = SLOCapture(1e-9, str(tmp_path), window_steps=1, windows=1,
                         capture_steps=2, min_samples=1,
                         profiler_factory=factory)
        eng = _engine(tiny_llama, slo_capture=cap)
        eng.add_request(_prompt(12), max_new_tokens=6)
        eng.run()
        # any real TTFT breaches 1e-9 ms: the engine's step hook armed
        # the capture and counted it down through the compiled steps
        assert profs and profs[0].stopped and profs[0].steps == 2
        assert len(cap.captures) == 1

    def test_windowed_profiler_smoke(self, tmp_path):
        # the default factory's host half: starts, steps, stops cleanly
        # (timer_only-style use; the device trace itself is exercised by
        # the profiler suite)
        from paddle_tpu.profiler import windowed_profiler
        prof = windowed_profiler(str(tmp_path / "w"), steps=2)
        try:
            prof.step()
            prof.step()
        finally:
            prof.stop()
        assert os.path.isdir(str(tmp_path / "w"))


# ---------------------------------------------------------------------------
# engine lifecycle tracing (real tiny model)
# ---------------------------------------------------------------------------

class TestEngineTracing:
    def test_lifecycle_phases_exactly_once(self, tiny_llama, tel):
        eng = _engine(tiny_llama)
        rids = [eng.add_request(_prompt(20), max_new_tokens=4,
                                tenant="acme"),
                eng.add_request(_prompt(5), max_new_tokens=3)]
        outs = eng.run()
        tr = obs.get_request_tracer()
        assert tr is tel.tracer is not None
        for rid in rids:
            tl = tr.timeline(rid)
            phases = _phases(tl)
            for ph in ("submit", "admit", "first_token", "retire"):
                assert phases.count(ph) == 1, (rid, phases)
            assert phases.index("submit") < phases.index("admit") \
                < phases.index("first_token") < phases.index("retire")
            _assert_exact_sum(tl)
            s = tl["summary"]
            assert s["done"] and s["decode_tokens"] == len(outs[rid])
        # the 20-token prompt prefilled in 8-token chunks, one a step
        # (both slots hold a request, so no free row to fan out over)
        assert tr.timeline(rids[0])["summary"]["prefill_chunks"] == 3
        # phase histograms + per-tenant aggregates landed
        snap = tel.registry.snapshot()
        assert snap["serve.queue_ms"]["count"] >= 2
        assert snap["serve.prefill_ms"]["count"] == 2
        assert snap["serve.decode_ms_per_token"]["count"] == 2
        assert snap["serve.tenant[acme].ttft_ms"]["count"] == 1
        assert snap["serve.tenant[acme].queue_ms"]["count"] >= 1
        # one serve_trace event per retired request
        assert len(tel.sinks[0].events("serve_trace")) == 2

    def test_trace_id_from_context_and_request(self, tiny_llama, tel):
        eng = _engine(tiny_llama)
        with obs.trace_context("client-abc"):
            rid = eng.add_request(_prompt(6), max_new_tokens=2)
        eng.run()
        tr = obs.get_request_tracer()
        tl = tr.timeline(rid)
        assert tl["trace_id"] == "client-abc"
        # the id also rides the Request (survives state migration)
        assert eng._states[rid].request.trace_id == "client-abc"

    def test_preempt_restore_continuity(self, tiny_llama, tel):
        eng = _engine(tiny_llama)
        rid = eng.add_request(_prompt(12), max_new_tokens=8)
        eng.step()
        eng.step()          # prefill done, decoding
        tr = obs.get_request_tracer()
        tid_before = tr.timeline(rid)["trace_id"]
        assert eng.preempt(rid)
        outs = eng.run()
        assert len(outs[rid]) == 8
        tl = tr.timeline(rid)
        assert tl["trace_id"] == tid_before
        phases = _phases(tl)
        assert phases.count("preempt") == 1 \
            and phases.count("restore") == 1
        # re-admission: one admit per queue episode
        assert phases.count("admit") == 1 + tl["summary"]["preempts"]
        for ph in ("submit", "first_token", "retire"):
            assert phases.count(ph) == 1
        _assert_exact_sum(tl)
        # the preempt wait is queue time: two queue episodes observed
        assert tel.registry.snapshot()["serve.queue_ms"]["count"] == 2

    def test_isolated_failure_traced(self, tiny_llama, tel):
        eng = _engine(tiny_llama)
        rid = eng.add_request(_prompt(5), max_new_tokens=3)
        rs.install_faults("serve.step@0")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                outs = eng.run()
        finally:
            rs.clear_faults()
        assert len(outs[rid]) == 3
        tl = obs.get_request_tracer().timeline(rid)
        phases = _phases(tl)
        assert "isolated" in phases and phases.count("retire") == 1
        _assert_exact_sum(tl)

    def test_tracing_off_is_off(self, tiny_llama):
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False,
                         request_tracing=False)
        try:
            assert obs.get_request_tracer() is None
            eng = _engine(tiny_llama)
            rid = eng.add_request(_prompt(5), max_new_tokens=2)
            eng.run()
            assert eng._states[rid].request.trace_id is None
            assert not tel.sinks[0].events("serve_trace")
            assert "serve.queue_ms" not in tel.registry.snapshot()
        finally:
            obs.disable()


# ---------------------------------------------------------------------------
# front-door integration: per-tenant SLO + shed-path trace hygiene
# ---------------------------------------------------------------------------

class TestFrontDoorTracing:
    def test_per_tenant_slo_exemption_and_recovery(self, tiny_llama,
                                                   tel):
        """The global TTFT signal GATES the SLO shed; the submitting
        tenant's own aggregate refines it (healthy tenant exempt), and
        a shed tenant recovers when the global signal recovers — its
        frozen per-tenant window must not lock it out forever."""
        eng = _engine(tiny_llama)
        door = serving.FrontDoor(eng, policies={
            "lo": serving.TenantPolicy(priority=0),
            "ok": serving.TenantPolicy(priority=0)},
            slo_ttft_p95_ms=50.0)
        reg = tel.registry
        for _ in range(4):
            reg.histogram("serve.ttft_ms").observe(500.0)   # breached
            reg.histogram("serve.tenant[ok].ttft_ms").observe(1.0)
            reg.histogram("serve.tenant[lo].ttft_ms").observe(500.0)
        assert door.submit(_prompt(3), tenant="ok",
                           max_new_tokens=2).admitted      # own p95 ok
        a = door.submit(_prompt(3), tenant="lo", max_new_tokens=2)
        assert not a.admitted and a.reason == "slo_shed"
        b = door.submit(_prompt(3), tenant="new", max_new_tokens=2)
        assert not b.admitted                  # no history → global
        # recovery: the global window refreshes healthy; 'lo's frozen
        # per-tenant history no longer matters once the gate is open
        for _ in range(512):
            reg.histogram("serve.ttft_ms").observe(1.0)
        assert door.submit(_prompt(3), tenant="lo",
                           max_new_tokens=2).admitted
        door.run()

    def test_pump_shed_retires_trace(self, tiny_llama, tel):
        """A request answered admitted=True but shed at pump (the
        engine refused an already-vetted id) must not leak a live
        trace — tracer retention only reaps done traces."""
        from paddle_tpu.serving.errors import AdmissionError
        eng = _engine(tiny_llama)
        door = serving.FrontDoor(eng)
        orig = eng.add_request

        def boom(*a, **kw):
            eng.add_request = orig             # refuse exactly once
            raise AdmissionError("id raced into the retained set")

        eng.add_request = boom
        a = door.submit(_prompt(5), max_new_tokens=2)
        assert a.admitted                      # answered before pump
        t = obs.get_request_tracer().get(a.request_id)
        assert t is not None and t.done and t.finish_reason == "shed"
        assert tel.sinks[0].events("serve_shed")
        door.run()


# ---------------------------------------------------------------------------
# replica-failure evacuation keeps the trace
# ---------------------------------------------------------------------------

class TestReplicaEvacuationTracing:
    def _rset(self, model_fn):
        return EngineReplicaSet(
            [_engine(model_fn()) for _ in range(2)])

    def test_trace_survives_evacuation(self, tel):
        from paddle_tpu.models.llama import llama

        def build():
            pt.seed(0)
            return llama("tiny")

        rset = self._rset(build)
        prompts = [_prompt(n) for n in (9, 14, 6, 11)]
        rids = []
        rs.install_faults("serve.replica@4")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for p in prompts:
                    rids.append(rset.add_request(p, max_new_tokens=6))
                    rset.step()
                outs = rset.run()
        finally:
            rs.clear_faults()
        assert rset.failures == 1 and rset.requeued >= 1
        tr = obs.get_request_tracer()
        migrated = 0
        for rid in rids:
            assert len(outs[rid]) == 6
            tl = tr.timeline(rid)
            assert tl is not None and tl["summary"]["done"]
            phases = _phases(tl)
            assert phases.count("submit") == 1
            assert phases.count("retire") == 1
            assert phases.count("route") == 1
            _assert_exact_sum(tl)
            migrated += phases.count("migrate")
            # the trace id is intact on the (possibly migrated) state
            assert rset._states[rid].request.trace_id == tl["trace_id"]
        assert migrated == rset.requeued

    def test_hard_reset_keeps_first_token_exactly_once(self, tel):
        """When the failing replica cannot even swap out, the victim
        re-prefills from scratch on the survivor — the trace records
        the degraded path (reset_fresh + re_prefilled) while
        `first_token` stays exactly-once and sums stay exact."""
        from paddle_tpu.models.llama import llama

        def build():
            pt.seed(0)
            return llama("tiny")

        rset = self._rset(build)
        rids = []
        rs.install_faults("serve.replica@4,serve.swap@0x999")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for n in (9, 14, 6, 11):
                    rids.append(rset.add_request(_prompt(n),
                                                 max_new_tokens=6))
                    rset.step()
                outs = rset.run()
        finally:
            rs.clear_faults()
        assert rset.failures == 1
        tr = obs.get_request_tracer()
        resets = 0
        for rid in rids:
            assert len(outs[rid]) == 6
            tl = tr.timeline(rid)
            phases = _phases(tl)
            assert phases.count("first_token") == 1, (rid, phases)
            assert phases.count("retire") == 1
            resets += phases.count("reset_fresh")
            _assert_exact_sum(tl)
        assert resets >= 1, "no trace recorded the degraded reset path"


# ---------------------------------------------------------------------------
# HTTP surfaces
# ---------------------------------------------------------------------------

class TestServerEndpoints:
    @pytest.fixture
    def server(self, tiny_llama, tel):
        eng = _engine(tiny_llama, max_batch=2)
        srv = serving.ServingServer(eng, poll_s=0.001)
        host, port = srv.start()
        import http.client
        conn = http.client.HTTPConnection(host, port, timeout=30)
        try:
            yield srv, conn
        finally:
            conn.close()
            srv.close()

    def _post(self, conn, body, headers=None):
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json", **(headers or {})})
        r = conn.getresponse()
        return r.status, json.loads(r.read())

    def test_metrics_and_timeline_endpoints(self, server):
        srv, conn = server
        status, out = self._post(
            conn, {"prompt": [3, 5, 7, 9], "max_tokens": 3},
            headers={"X-Trace-Id": "edge-42"})
        assert status == 200
        rid = out["id"]
        assert len(out["choices"][0]["token_ids"]) == 3

        conn.request("GET", f"/v1/requests/{rid}")
        r = conn.getresponse()
        tl = json.loads(r.read())
        assert r.status == 200
        assert tl["trace_id"] == "edge-42"
        phases = [e["phase"] for e in tl["events"]]
        for ph in ("submit", "admit", "first_token", "retire"):
            assert phases.count(ph) == 1
        _assert_exact_sum(tl)

        conn.request("GET", "/v1/requests/no-such")
        r = conn.getresponse()
        assert r.status == 404
        r.read()

        conn.request("GET", "/metrics")
        r = conn.getresponse()
        body = r.read().decode()
        assert r.status == 200
        assert "text/plain" in r.getheader("Content-Type")
        assert "# TYPE serve_ttft_ms summary" in body
        assert "serve_requests 1" in body
        assert re.search(r"serve_queue_ms_count \d+", body)

    def test_metrics_without_telemetry(self, tiny_llama):
        # no obs.enable(): the endpoint still renders engine-local
        # gauges, and /v1/requests answers the typed 503
        eng = _engine(tiny_llama)
        srv = serving.ServingServer(eng, poll_s=0.001)
        host, port = srv.start()
        import http.client
        conn = http.client.HTTPConnection(host, port, timeout=30)
        try:
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            body = r.read().decode()
            assert r.status == 200 and "serve_queue_depth 0" in body
            conn.request("GET", "/v1/requests/x")
            r = conn.getresponse()
            assert r.status == 503
            assert "tracing_disabled" in r.read().decode()
        finally:
            conn.close()
            srv.close()


# ---------------------------------------------------------------------------
# tools: trace_export + telemetry_report folding
# ---------------------------------------------------------------------------

class TestTraceTools:
    @pytest.fixture
    def jsonl(self, tiny_llama, tmp_path):
        path = str(tmp_path / "run.jsonl")
        obs.enable(jsonl_path=path, crash_hooks=False)
        try:
            eng = _engine(tiny_llama)
            for n, t in ((12, "acme"), (5, "bob")):
                eng.add_request(_prompt(n), max_new_tokens=3, tenant=t)
                eng.step()
            eng.run()
        finally:
            obs.disable()
        return path

    def test_trace_export_chrome_json(self, jsonl, tmp_path):
        out = str(tmp_path / "trace.json")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "trace_export.py"),
             jsonl, "-o", out],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        summary = json.loads(r.stdout.strip().splitlines()[-1])
        assert summary["requests"] == 2 and summary["out"] == out
        with open(out) as f:
            trace = json.load(f)
        evs = trace["traceEvents"]
        # every request has a named track, phase slices, and markers
        names = {e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert len(names) == 2
        slices = [e for e in evs if e["ph"] == "X"]
        assert {"queue", "prefill", "decode"} <= {e["name"]
                                                 for e in slices}
        for e in slices:
            assert e["dur"] >= 0 and {"pid", "tid", "ts"} <= set(e)
        assert any(e["ph"] == "i" and e["name"] == "prefill_chunk"
                   for e in evs)

    def test_export_pid_follows_migration(self):
        """An evacuated request's post-migration slices must render
        under the SURVIVOR replica's process, not the dead one's."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import trace_export
        ev = {"event": "serve_trace", "id": "r1", "trace_id": "t",
              "t0": 1.0, "events": [
                  {"phase": "submit", "t_ms": 0.0},
                  {"phase": "route", "t_ms": 0.1, "replica": 0},
                  {"phase": "admit", "t_ms": 0.2, "closed": "queue",
                   "ms": 0.2},
                  {"phase": "preempt", "t_ms": 1.0, "closed": "prefill",
                   "ms": 0.8},
                  {"phase": "migrate", "t_ms": 1.1, "from_replica": 0,
                   "to_replica": 1},
                  {"phase": "retire", "t_ms": 2.0, "closed": "decode",
                   "ms": 0.5}],
              "summary": {}}
        trace, n, stitched = trace_export.chrome_trace([ev])
        assert n == 1 and stitched == 0
        by_name = {e["name"]: e for e in trace["traceEvents"]
                   if e["ph"] == "X"}
        assert by_name["queue"]["pid"] == 0
        assert by_name["prefill"]["pid"] == 0      # work the dead one did
        assert by_name["decode"]["pid"] == 1       # survivor's work
        # both replicas carry the request's track metadata
        meta_pids = {e["pid"] for e in trace["traceEvents"]
                     if e["ph"] == "M" and e["name"] == "thread_name"}
        assert meta_pids == {0, 1}

    def test_telemetry_report_folds_traces(self, jsonl, capsys):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import telemetry_report
        assert telemetry_report.main([jsonl, "--json"]) == 0
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        ph = summary["trace_phases"]
        for k in ("queue_ms", "prefill_ms", "decode_ms",
                  "decode_ms_per_token", "wall_ms"):
            assert ph[k]["n"] == 2 and ph[k]["p50"] is not None
        tenants = summary["trace_tenants"]
        assert set(tenants) == {"acme", "bob"}
        assert tenants["acme"]["traces"] == 1
        # per-tenant ttft parsed from the registry snapshot through the
        # SAME prom grammar the /metrics exporter uses
        assert tenants["acme"]["ttft_p95"] is not None

    def test_report_renders_tables(self, jsonl, capsys):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import telemetry_report
        telemetry_report.main([jsonl])
        out = capsys.readouterr().out
        assert "Request phase" in out and "| Tenant |" in out
