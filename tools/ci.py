#!/usr/bin/env python
"""Standing CI gates — the single entry point the test suite invokes
(tests/test_ci_gates.py), so a public-API removal or a sharding-memory
regression fails ``pytest`` instead of waiting for a user (or a real pod
OOM) to notice.

Reference: the reference repo's CI stack (SURVEY §2.8 — API-approval diff
job, model memory checks; its op-benchmark job has no gate here: kernel
numbers come from the chip, benchmark/metrics/*_roofline.py) — here
collapsed into in-repo gates over artifacts committed alongside the code:

  api-compat      tools/check_api_compat.py vs tools/api_spec.txt
  memproof-lite   cheap re-check of the 13B hybrid sharding from
                  docs/memproof.json: rebuild the abstract train state on
                  the deviceless v5e:8x8 topology and recompute per-chip
                  ARGUMENT bytes from the shardings alone (no compile —
                  the full compiler proof is tools/memproof.py).  Catches
                  a sharding spec or amp-dtype regression that would
                  re-break the proven memory fit.

  telemetry-overhead  the disabled-observability train-step path stays
                  zero-overhead (one falsy check — see
                  paddle_tpu/observability/_state.py): registry/sink/
                  request-tracer calls are poisoned and the dispatch
                  cost is bounded (the fault-injection hook rides the
                  same contract); the /metrics + /v1/requests HTTP
                  surface renders on a no-jax stub engine within a
                  time budget

  chaos           the resilience subsystem actually recovers: a tiny
                  deterministic train run, supervised by
                  resilience.run_resilient, must finish with final
                  params BITWISE-equal to the fault-free run while a
                  fault is injected at every registered site (step,
                  collective, ckpt.save, ckpt.load, store.get/set);
                  and with the newest checkpoint deliberately
                  corrupted, resume must fall back to the previous
                  valid one and still reproduce the same params

  serving-smoke   the continuous-batching engine's standing contracts
                  (docs/SERVING.md): after warmup, mixed-length requests
                  joining/leaving the running batch trigger ZERO
                  recompiles (recompile sentinel + jit cache sizes), and
                  every KV block is reclaimed at drain

  lint            pdtpu-lint (paddle_tpu/analysis, docs/ANALYSIS.md):
                  the framework-invariant static analyzer — donation
                  safety, compat discipline, zero-overhead guards,
                  retrace hazards, fault-site consistency, lock
                  discipline — runs clean over the whole tree, jax-free
                  and in seconds; any non-baselined finding fails

  chaos-serving   the resilience machinery applied to the serving path:
                  a PDTPU_FAULTS plan firing at every serving site
                  (serve.admit/prefill/step/cow/swap) during a mixed
                  churn run with preemption + CoW → zero step
                  recompiles, all KV blocks reclaimed at drain, and
                  greedy outputs token-identical to the fault-free run

  serving-dist    sharded serving on a forced 8-device CPU mesh: a TP=2
                  engine (head-sharded paged pools) serves greedy
                  outputs token-identical to the single-chip engine
                  with zero compiles after warmup, and a 2-replica DP
                  set behind the FrontDoor survives an injected
                  serve.replica fault — every in-flight request
                  re-queued through preempt→restore and completed,
                  all blocks reclaimed on every replica

Run all:  python tools/ci.py            (exit 0 = all gates pass)
One:      python tools/ci.py --only api-compat|memproof-lite|telemetry-overhead|chaos|serving-smoke|chaos-serving|serving-dist|lint
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

# memproof-lite tolerance: abstract-state accounting vs the recorded
# compiled argument bytes.  The two differ only by compiler-internal
# padding; 5% flags a real change (an unsharded moment tensor alone would
# be +25%) without tripping on layout noise.
MEMPROOF_TOL = 0.05
# one sentinel per BASELINE workload class (VERDICT r4 #7: breaking ANY
# config's sharding must fail pytest in seconds, not just the 13B row):
# 7B ZeRO-3, 13B TP+PP, 70B hybrid, SDXL, MoE EP, 32k-ring long-context
MEMPROOF_CASES = [
    "7b-sh8-zero3-v5e8",
    "13b-mp8pp4dp2-v5e64",
    "70b-mp8pp4sh4-v5p128",
    "sdxl-dp8-v5e8",
    "moe-8x7b-ep8sh8-v5e64",
    "7b-sep8-sh16-seq32k-v5p128",
]


def gate_api_compat() -> int:
    sys.argv = ["check_api_compat.py"]
    import check_api_compat
    return check_api_compat.main()


def _shard_bytes(leaf) -> int:
    """Per-chip bytes of one abstract array under its NamedSharding."""
    import numpy as np
    shape = leaf.shape
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            shape = sharding.shard_shape(shape)
        except Exception:
            pass
    return int(np.prod(shape, dtype=np.int64)) * leaf.dtype.itemsize


def gate_memproof_lite() -> int:
    # deviceless gate: never initialize the TPU plugin — a concurrent
    # TPU-holding process makes plugin init fail on the libtpu lockfile
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

    import memproof

    with open(os.path.join(REPO, "docs", "memproof.json")) as f:
        recorded_all = {r["name"]: r for r in json.load(f)}

    failures = []
    for name in MEMPROOF_CASES:
        case = next((c for c in memproof.CASES if c.name == name), None)
        recorded = recorded_all.get(name)
        if case is None or recorded is None:
            # the gate's own failure message, not a StopIteration — a
            # renamed/removed sentinel IS a layout-config change
            failures.append(
                f"{name}: missing from "
                f"{'memproof.CASES' if case is None else 'docs/memproof.json'}"
                " — update MEMPROOF_CASES or restore the case")
            continue
        step, astate, batch, _ = memproof.build_case(case)
        leaves = (jax.tree_util.tree_leaves(astate)
                  + jax.tree_util.tree_leaves(batch))
        est = sum(_shard_bytes(l) for l in leaves)
        ref = recorded["argument_bytes"]
        drift = abs(est - ref) / ref
        print(f"memproof-lite: {name} abstract argument bytes "
              f"{est:,} vs recorded {ref:,} (drift {drift:.2%}, "
              f"tol {MEMPROOF_TOL:.0%})")
        if drift > MEMPROOF_TOL:
            failures.append(f"{name}: drift {drift:.2%}")
        # the recorded full proof must still say the config fits
        if not recorded.get("fits"):
            failures.append(f"{name}: recorded proof says it does not fit")
    if failures:
        print("memproof-lite gate FAILED — a sharded memory layout "
              "changed; re-run tools/memproof.py for the full compiler "
              "proof and update docs/memproof.json:")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print(f"memproof-lite gate OK ({len(MEMPROOF_CASES)} configs)")
    return 0


def gate_telemetry_overhead(iters: int = 100_000,
                            budget_us: float = 10.0,
                            ring_budget_us: float = 5.0) -> int:
    """The disabled-telemetry train-step path must stay zero-overhead,
    and the enabled flight-recorder ring append must stay O(µs).

    Four checks, all deterministic:

    1. POISON: with telemetry disabled (the default), a TrainStep call
       must never touch the metrics registry or emit an event — the
       registry methods and Telemetry.emit are monkeypatched to raise,
       and a dispatch-only TrainStep (compiled fn stubbed out) is driven
       through ``__call__``.  Accidentally hot-pathing the registry
       fails loudly regardless of timing noise.
    2. TIMING: the same dispatch-only ``__call__`` must average under
       ``budget_us`` per call (measured ~1 µs; the contract is ONE falsy
       hook-container check — see observability/_state.py).  A stray
       per-step file write or lock acquisition blows the budget.
    3. RING: the enabled-recorder cost is one dict build + one deque
       append — ``FlightRecorder.record`` must average under
       ``ring_budget_us`` per call and the ring must stay bounded at
       its capacity (a lock, a copy, or an unbounded buffer blows it).
    4. RE-CHECK: after a full ``enable(flight_recorder=True, watchdog)``
       /``disable`` cycle, every hook container is None again and the
       poisoned dispatch probe still passes — enabling the recorder once
       must not leave residue on the disabled path.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import time

    import paddle_tpu.observability as obs
    from paddle_tpu.observability import _state as obs_state
    from paddle_tpu.jit import TrainStep

    if obs.enabled():
        print("telemetry-overhead gate FAILED: telemetry is enabled by "
              "default — it must be opt-in")
        return 1

    # dispatch-only TrainStep: real __call__ code path, no XLA
    step = TrainStep.__new__(TrainStep)
    step.model = type("M", (), {"_grad_sync": True})()
    step._accum = False
    step.mesh = None
    step._site = "TrainStep(M)"
    step._compiled = lambda s, b, a: (s, {})

    def boom(self, *a, **kw):
        raise AssertionError(
            "disabled-telemetry path touched the metrics registry / sinks")

    saved = {}
    # the request tracer rides the same contract: with tracing off every
    # serving site is ONE falsy check on _state.TRACE[0], so a poisoned
    # tracer method must never fire during the disabled-path probes
    # the fleet aggregation layer (observability/aggregate.py) rides the
    # same contract: with telemetry disabled no sketch is observed or
    # merged, no registry is folded to the wire, no segments stitched
    from paddle_tpu.observability import aggregate as obs_agg
    # the compiled-artifact ledger rides the contract too: with
    # telemetry disabled no row is recorded or read, no roofline is
    # evaluated, no HBM snapshot is taken (its compile-path capture is
    # a method wrap that only exists while enabled — zero checks, not
    # even one)
    from paddle_tpu.observability import compiled as obs_compiled
    poisoned = [(obs.MetricsRegistry, n) for n in
                ("counter", "gauge", "histogram")] + \
               [(obs.Telemetry, "emit")] + \
               [(obs.RequestTracer, n) for n in
                ("begin", "point", "transition", "retire")] + \
               [(obs_agg.HistogramSketch, n) for n in
                ("observe", "merge")] + \
               [(obs_agg, n) for n in
                ("registry_to_wire", "fleet_fold",
                 "stitch_trace_segments")] + \
               [(obs.CompiledArtifactLedger, n) for n in
                ("record_executable", "snapshot", "min_ms_for",
                 "rows_for", "set_hbm")] + \
               [(obs_compiled, n) for n in ("roofline", "chip_spec")]
    for cls, name in poisoned:
        saved[(cls, name)] = getattr(cls, name)
        setattr(cls, name, boom)
    try:
        state, batch = {"step": 0}, {"x": None}
        step(state, batch)  # poison probe: one call is enough to detonate
        t0 = time.perf_counter()
        for _ in range(iters):
            step(state, batch)
        per_call_us = (time.perf_counter() - t0) / iters * 1e6
    finally:
        for (cls, name), fn in saved.items():
            setattr(cls, name, fn)
    print(f"telemetry-overhead: disabled-path TrainStep dispatch "
          f"{per_call_us:.2f} us/call (budget {budget_us:.0f} us)")
    if per_call_us > budget_us:
        print("telemetry-overhead gate FAILED: the disabled path grew a "
              "measurable per-step cost — keep it to one falsy check "
              "(observability/_state.py)")
        return 1

    # 3. enabled-recorder ring append: one dict build + one deque append
    rec = obs.FlightRecorder(capacity=512)
    ring_iters = max(iters, 1024)
    t0 = time.perf_counter()
    for _ in range(ring_iters):
        rec.record("beat", site="gate")
    ring_us = (time.perf_counter() - t0) / ring_iters * 1e6
    print(f"telemetry-overhead: enabled-recorder ring append "
          f"{ring_us:.2f} us/record (budget {ring_budget_us:.0f} us)")
    if ring_us > ring_budget_us:
        print("telemetry-overhead gate FAILED: FlightRecorder.record grew "
              "beyond one append — no locks, no copies, no I/O on the "
              "breadcrumb path (observability/flight_recorder.py)")
        return 1
    if len(rec) != 512 or rec.total != ring_iters:
        print(f"telemetry-overhead gate FAILED: ring not bounded at its "
              f"capacity (len {len(rec)}, capacity 512, total {rec.total})")
        return 1

    # 3b. serving fault sites + front-door decisions ride the same
    # contract: the serve.* sites are registered (a PDTPU_FAULTS plan
    # naming them parses), and with telemetry disabled a FrontDoor
    # submit — admitted or shed — touches neither registry nor sinks
    # (poison probe) and costs O(µs) per decision.
    import numpy as np

    from paddle_tpu.resilience import faults as rs_faults
    serve_sites = ("serve.admit", "serve.prefill", "serve.step",
                   "serve.cow", "serve.swap", "serve.gateway",
                   "cluster.journal", "cluster.takeover")
    missing = [s for s in serve_sites if s not in rs_faults.SITES]
    if missing:
        print(f"telemetry-overhead gate FAILED: serving fault sites "
              f"not registered: {missing}")
        return 1
    rs_faults.parse_faults(",".join(f"{s}@0" for s in serve_sites))

    from paddle_tpu.serving.frontdoor import FrontDoor, TenantPolicy

    class _Alloc:
        used_blocks = 0

        def can_allocate(self, n):
            return True

    class _KV:
        num_blocks = 64
        allocator = _Alloc()

    class _Sched:
        waiting = ()

        def queue_depth(self):
            return 0

        def blocks_for(self, n):
            return 1

        def active(self):
            return []

    class _Eng:
        """The attribute surface FrontDoor reads — no jax, no model."""
        max_batch = 4
        max_seq_len = 128
        kv = _KV()
        kv_blocks_used = 0

        def __init__(self):
            self.scheduler = _Sched()
            self._states = {}

        def add_request(self, *a, **kw):
            return kw.get("request_id")

        def has_work(self):
            return False

    door = FrontDoor(_Eng(), policies={
        "t": TenantPolicy(rate_tokens_per_s=1.0, burst_tokens=8.0)})
    prompt = np.arange(4, dtype=np.int32)
    for cls, name in poisoned:
        setattr(cls, name, boom)
    try:
        first = door.submit(prompt, tenant="t", max_new_tokens=4)
        second = door.submit(prompt, tenant="t", max_new_tokens=4)
        shed_iters = 2000
        t0 = time.perf_counter()
        for _ in range(shed_iters):
            door.submit(prompt, tenant="t", max_new_tokens=4)
        shed_us = (time.perf_counter() - t0) / shed_iters * 1e6
    except AssertionError:
        print("telemetry-overhead gate FAILED: the disabled-telemetry "
              "front door touched the metrics registry / sinks "
              "(serving/frontdoor.py must guard every emit)")
        return 1
    finally:
        for (cls, name), fn in saved.items():
            setattr(cls, name, fn)
    if not first.admitted or second.admitted \
            or second.reason != "rate_limited":
        print(f"telemetry-overhead gate FAILED: front-door stub "
              f"decisions wrong ({first}, {second})")
        return 1
    print(f"telemetry-overhead: disabled-path FrontDoor shed decision "
          f"{shed_us:.2f} us/call (budget 50 us)")
    if shed_us > 50.0:
        print("telemetry-overhead gate FAILED: the front door's shed "
              "path grew a measurable cost — sheds happen thousands of "
              "times per second under overload")
        return 1

    # 3c. the live operational surface renders on the SAME no-jax stub
    # engine, telemetry off, registry/tracer methods still poisoned:
    # GET /metrics must fall back to valid prom text from engine-local
    # gauges (never 500, never empty) and GET /v1/requests must answer
    # its typed tracing-disabled 503 — each within a small time budget
    # (an operator's scrape loop must not perturb the engine loop).
    import http.client

    from paddle_tpu.serving.server import ServingServer

    for cls, name in poisoned:
        setattr(cls, name, boom)
    srv = ServingServer(door)
    try:
        host, port = srv.start()
        conn = http.client.HTTPConnection(host, port, timeout=10)
        conn.request("GET", "/metrics")   # first call pays thread spin-up
        conn.getresponse().read()
        t0 = time.perf_counter()
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        body = r.read().decode()
        metrics_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        conn.request("GET", "/v1/requests/no-such-request")
        r2 = conn.getresponse()
        body2 = r2.read().decode()
        req_ms = (time.perf_counter() - t0) * 1e3
        conn.close()
    except (OSError, http.client.HTTPException):
        # a poisoned registry/tracer method fires in the HANDLER thread:
        # http.server swallows the AssertionError and drops the
        # connection, which the client sees as RemoteDisconnected (an
        # HTTPException) or ConnectionReset (an OSError) — that IS the
        # poison-probe failure signal
        print("telemetry-overhead gate FAILED: the disabled-telemetry "
              "/metrics //v1/requests surface dropped the connection — "
              "a handler touched the poisoned registry / tracer "
              "(serving/server.py must ride the guarded getters)")
        return 1
    finally:
        for (cls, name), fn in saved.items():
            setattr(cls, name, fn)
        srv.close()
    if r.status != 200 or "text/plain" not in (r.getheader(
            "Content-Type") or "") or "serve_queue_depth 0" not in body:
        print(f"telemetry-overhead gate FAILED: GET /metrics on the "
              f"stub engine answered {r.status} with body "
              f"{body[:200]!r} — expected prom text exposition with "
              "the engine-local fallback gauges")
        return 1
    if r2.status != 503 or "tracing_disabled" not in body2:
        print(f"telemetry-overhead gate FAILED: GET /v1/requests with "
              f"tracing off answered {r2.status} {body2[:200]!r} — "
              "expected the typed tracing_disabled 503")
        return 1
    print(f"telemetry-overhead: stub-engine /metrics {metrics_ms:.1f} ms"
          f" / /v1/requests {req_ms:.1f} ms (budget 250 ms each)")
    if metrics_ms > 250.0 or req_ms > 250.0:
        print("telemetry-overhead gate FAILED: the operational HTTP "
              "surface blew its render budget on an IDLE stub engine")
        return 1

    # 3d. the fleet observability plane rides the same contract: with
    # telemetry disabled, a worker's telemetry/trace/clock publishers
    # and a controller pump touch neither the registry/tracer (poison)
    # nor the store's telemetry keys (write audit) — and each disabled
    # publisher call stays O(µs).
    from paddle_tpu.serving import cluster as cluster_mod
    from paddle_tpu.serving import gateway as gateway_mod
    from paddle_tpu.serving import worker as worker_mod

    class _DictStore:
        """Minimal in-memory store; records every key written."""

        def __init__(self):
            self.kv = {}
            self.writes = []

        def set(self, k, v):
            self.writes.append(k)
            self.kv[k] = v

        def get(self, k):
            return self.kv.get(k)

        def add(self, k, n):
            cur = int(self.kv.get(k, b"0")) + n
            self.kv[k] = str(cur).encode()
            return cur

        def delete(self, k):
            return self.kv.pop(k, None) is not None

        def compare_set(self, k, expected, new):
            if self.kv.get(k) == expected or (
                    expected in (b"", None) and k not in self.kv):
                self.kv[k] = new
                return True
            return False

        def keys(self, pfx):
            return [k for k in self.kv if k.startswith(pfx)]

    class _CSched:
        def queue_depth(self):
            return 0

        def active(self):
            return []

    class _CAlloc:
        free_blocks = 8

    class _CKV:
        num_blocks = 8
        allocator = _CAlloc()

    class _CEng:
        role = "both"
        handoffs = 0
        scheduler = _CSched()
        kv = _CKV()

    fleet_poisoned = poisoned + \
        [(worker_mod, "registry_to_wire")] + \
        [(cluster_mod, n) for n in
         ("registry_to_wire", "fleet_fold", "stitch_trace_segments")]
    dstore = _DictStore()
    fw = worker_mod.ServingWorker(_CEng(), dstore, worker_id="gate-w",
                                  status_interval_s=0.0)
    for cls, name in fleet_poisoned:
        saved[(cls, name)] = getattr(cls, name)
        setattr(cls, name, boom)
    try:
        fw.register()
        fw.publish_status()
        ctl = cluster_mod.ClusterController(dstore, autoscale=True)
        ctl.pump()
        # the gateway's admission path rides the contract too: with
        # telemetry disabled an admit (through the controller's durable
        # journal) and a typed policy shed touch neither registry nor
        # sinks (serving/gateway.py guards every emit)
        fgw = gateway_mod.ClusterGateway(ctl, max_live=1)
        gw_admit = fgw.submit_request([1, 2, 3], max_new_tokens=2,
                                      idempotency_key="gate-k")
        gw_shed = fgw.submit_request([1, 2, 3], max_new_tokens=2)
        pub_iters = 20_000
        t0 = time.perf_counter()
        for _ in range(pub_iters):
            fw.publish_telemetry()
            fw._sync_clock()
            fw._publish_trace_segment("gate-r0")
        pub_us = (time.perf_counter() - t0) / pub_iters * 1e6
    except AssertionError:
        print("telemetry-overhead gate FAILED: the disabled-telemetry "
              "fleet plane (worker publish / controller pump / gateway "
              "admission) touched the registry / tracer / aggregation "
              "layer — every site must be one falsy check "
              "(serving/worker.py, serving/cluster.py, "
              "serving/gateway.py)")
        return 1
    finally:
        for (cls, name), fn in saved.items():
            setattr(cls, name, fn)
    if not gw_admit.admitted or gw_shed.admitted \
            or gw_shed.reason != "queue_full":
        print(f"telemetry-overhead gate FAILED: gateway stub decisions "
              f"wrong ({gw_admit}, {gw_shed})")
        return 1
    leaked = [k for k in dstore.writes
              if "/telemetry/" in k or "/trace/" in k
              or k.endswith("/clock")]
    if leaked:
        print(f"telemetry-overhead gate FAILED: disabled-telemetry "
              f"fleet plane still wrote observability store keys: "
              f"{leaked[:4]} — the publishers must return before any "
              "store traffic")
        return 1
    print(f"telemetry-overhead: disabled-path fleet publishers "
          f"{pub_us:.2f} us/cycle (budget {budget_us:.0f} us)")
    if pub_us > budget_us:
        print("telemetry-overhead gate FAILED: the disabled fleet "
              "publishers grew a measurable per-cycle cost")
        return 1

    # 4. an enable/disable cycle (recorder + watchdog + spans on) leaves
    # the disabled path exactly as it was: all hooks None, poison-clean.
    # The fault-injection hook rides the same contract: an
    # install/clear cycle must leave FAULTS None too.
    from paddle_tpu import resilience as rs
    from paddle_tpu.resilience import _state as rs_state
    tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False,
                     watchdog_s=3600.0)
    rs.install_faults("step@999999999")   # installed but never firing
    step(state, batch)
    rs.clear_faults()
    obs.disable()
    hooks = {"MONITOR": obs_state.MONITOR[0],
             "COLLECTIVE": obs_state.COLLECTIVE[0],
             "EMIT": obs_state.EMIT[0],
             "SPAN": obs_state.SPAN[0],
             "RECORDER": obs_state.RECORDER[0],
             "POSTMORTEM": obs_state.POSTMORTEM[0],
             "TRACE": obs_state.TRACE[0],
             "LEDGER": obs_state.LEDGER[0],
             "FAULTS": rs_state.FAULTS[0]}
    stale = [k for k, v in hooks.items() if v is not None]
    if stale:
        print(f"telemetry-overhead gate FAILED: disable() left hook "
              f"containers set: {stale}")
        return 1
    # the ledger's compile wrap must not outlive the session either:
    # disable() restores pxla.MeshComputation.compile verbatim
    try:
        from jax._src.interpreters import pxla
        if pxla.MeshComputation.compile.__name__ == "_ledger_compile":
            print("telemetry-overhead gate FAILED: disable() left the "
                  "compiled-artifact ledger's compile wrap installed "
                  "(observability/compiled.py uninstall)")
            return 1
    except ImportError:
        pass
    if tel.watchdog is None or tel.watchdog._thread is not None:
        print("telemetry-overhead gate FAILED: disable() left the hang "
              "watchdog thread running")
        return 1
    for cls, name in poisoned:
        saved[(cls, name)] = getattr(cls, name)
        setattr(cls, name, boom)
    try:
        step(state, batch)   # re-poison probe after the cycle
    finally:
        for (cls, name), fn in saved.items():
            setattr(cls, name, fn)
    print("telemetry-overhead gate OK")
    return 0


def gate_chaos(num_steps: int = 6, save_every: int = 2) -> int:
    """Chaos gate: the resilience subsystem must turn injected faults
    into retries/restarts that reproduce the fault-free run EXACTLY.

    Five checks, all deterministic (docs/RESILIENCE.md):

    1. BASELINE: a tiny supervised train run (Linear(4,4) + AdamW,
       batches derived from the step index) with no faults.
    2. PER-SITE FAULTS: the same run with a fault injected at each
       registered train-path site (step, collective, ckpt.save,
       ckpt.load — the load fires because the supervisor restores-first
       on every start) must complete and end with params bitwise-equal
       to the baseline.
    3. ALL-AT-ONCE: one run with faults at every one of those sites.
    4. STORE: TCPStore set/get survive injected store.set/store.get
       faults under a RetryPolicy (and raise without one).
    5. FALLBACK: with the newest checkpoint's shard bytes flipped,
       ``latest_checkpoint(valid_only=True)`` lands on the previous
       valid directory, and a resumed supervised run still reproduces
       the baseline params bitwise.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import ckpt, distributed as dist, nn, optimizer
    from paddle_tpu import resilience as rs
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.launch import TCPStore
    from paddle_tpu.launch.store import free_port

    # NO persistent compile cache here, deliberately: the gate's whole
    # contract is bitwise reproducibility, and mixing cache-hit
    # executables from older sessions with fresh compiles has been
    # observed to break it.  The programs are tiny; compiling them
    # fresh keeps every run of this gate self-contained.

    def make_step():
        pt.seed(0)
        m = nn.Linear(4, 4)
        opt = optimizer.AdamW(learning_rate=1e-2,
                              parameters=m.parameters())
        return TrainStep(
            m, lambda mm, b: ((mm(b["x"]) - b["y"]) ** 2).mean(), opt)

    def batch_of(i):
        r = np.random.default_rng(i)   # batch = f(step index): replayable
        return {"x": jnp.asarray(r.normal(size=(4, 4)), jnp.float32),
                "y": jnp.asarray(r.normal(size=(4, 4)), jnp.float32)}

    def params_bytes(state):
        return b"".join(np.asarray(l).tobytes()
                        for l in jax.tree_util.tree_leaves(state["params"]))

    policy = rs.RetryPolicy(max_attempts=4, backoff_s=0.0, jitter=0.0,
                            sleep=lambda _s: None)

    def run(ckpt_dir, faults=None):
        rs.clear_faults()
        if faults:
            rs.install_faults(faults)
        try:
            step = make_step()

            def step_fn(state, i):
                st, _metrics = step(state, batch_of(i))
                # eager collective on the no-op world group: exercises
                # the "collective" fault site without a multi-host run
                dist.all_reduce(jnp.zeros(()))
                return st

            final = rs.run_resilient(step_fn, state=step.init_state(),
                                     num_steps=num_steps, ckpt_dir=ckpt_dir,
                                     policy=policy, save_every=save_every)
            return params_bytes(final)
        finally:
            rs.clear_faults()

    failures = []
    with tempfile.TemporaryDirectory() as root:
        base_dir = os.path.join(root, "baseline")
        p0 = run(base_dir)

        site_faults = {
            "step": "step@3",
            "collective": "collective@4",
            "ckpt.save": "ckpt.save@1",
            "ckpt.load": "ckpt.load@0",
        }
        for site, spec in site_faults.items():
            p = run(os.path.join(root, site.replace(".", "_")), spec)
            ok = p == p0
            print(f"chaos: fault at {site:10s} ({spec}): params "
                  f"{'bitwise-equal' if ok else 'DIVERGED'}")
            if not ok:
                failures.append(f"{site}: params diverged from fault-free run")
        p = run(os.path.join(root, "all_sites"),
                ",".join(site_faults.values()))
        if p != p0:
            failures.append("all-sites run: params diverged")
        else:
            print("chaos: all sites at once: params bitwise-equal")

        # store.set / store.get: retried under a policy, raise without one
        rs.install_faults("store.set@0,store.get@0")
        s = TCPStore(f"127.0.0.1:{free_port()}", is_master=True,
                     retry=policy)
        try:
            s.set("chaos", b"ok")
            got = s.get("chaos")
            inj = rs.active_injector()
            if got != b"ok" or {f[0] for f in inj.fired} != {"store.set",
                                                            "store.get"}:
                failures.append(
                    f"store faults not absorbed by retry (got {got!r}, "
                    f"fired {inj.fired})")
            else:
                print("chaos: store.set/store.get faults absorbed by retry")
        finally:
            s.close()
            rs.clear_faults()

        # fallback: corrupt the newest checkpoint of the baseline dir,
        # then resume — must land on the previous valid one and still
        # reproduce the baseline params
        newest = ckpt.latest_checkpoint(base_dir)
        shard = next(f for f in sorted(os.listdir(newest))
                     if f.endswith(".npy"))
        fpath = os.path.join(newest, shard)
        raw = bytearray(open(fpath, "rb").read())
        raw[-1] ^= 0xFF
        open(fpath, "wb").write(bytes(raw))
        fallback = ckpt.latest_checkpoint(base_dir, valid_only=True)
        want = os.path.join(base_dir, f"step_{num_steps - save_every}")
        if fallback != want:
            failures.append(
                f"corrupted newest: valid_only fallback returned "
                f"{fallback}, wanted {want}")
        else:
            print(f"chaos: corrupt newest skipped, fallback to "
                  f"{os.path.basename(want)}")
            if run(base_dir) != p0:
                failures.append(
                    "resume from fallback checkpoint diverged from baseline")
            else:
                print("chaos: resume from fallback reproduces baseline "
                      "params bitwise")

    if failures:
        print("chaos gate FAILED — resilience does not reproduce the "
              "fault-free run (docs/RESILIENCE.md):")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("chaos gate OK")
    return 0


def gate_serving_smoke(max_batch: int = 4, n_requests: int = 10) -> int:
    """Serving smoke: the continuous-batching engine's standing
    contracts (docs/SERVING.md), end to end on a tiny model:

    1. ZERO RECOMPILES UNDER CHURN: after ``Engine.warmup()`` — ONE
       compile for the unified ragged step plus one for the CoW page
       copy — requests of varying lengths joining and leaving the
       running batch, prefilling in chunks interleaved with decode,
       must not trigger a single further compile.  Checked two ways:
       the recompile sentinel's backend-compile count stays at its
       warmup level, and the jit caches of the step/CoW callables hold
       exactly one executable each at drain (the second check also
       catches re-TRACES that the persistent XLA compile cache would
       hide from the sentinel).
    2. FULL RECLAIM AT DRAIN: when the queue and every slot are empty,
       ``used_blocks == 0`` — every refcount back to zero, shared and
       private blocks alike; prefix-cached pages linger only as
       EVICTABLE capacity (still allocatable).
    3. PREFIX CACHING IS AN OPTIMIZATION, NOT A TRADE: with shared
       prompt prefixes and chunked prefill, greedy outputs stay
       token-identical to ``model.generate()``, cache hits are > 0 on
       the re-serve, and the fully-cached page-aligned prompt exercises
       copy-on-write.

    Plus the correctness floor: every request produced exactly its
    ``max_new_tokens`` greedy tokens (EOS unset), token-identical
    across a re-serve of the same prompts on the churned engine.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    failures = []
    tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
    try:
        pt.seed(0)
        model = llama("tiny")
        # prefill_chunk below the longest prompt → chunked prefill is
        # actually exercised (40-token prompts take 5 ragged steps)
        eng = serving.Engine(model, max_batch=max_batch, max_seq_len=64,
                             page_size=8, prefill_chunk=8).warmup()
        compiles_at_warmup = tel.sentinel.compiles()

        rng = np.random.default_rng(0)
        lens = [3, 17, 9, 33, 5, 26, 12, 40, 7, 21][:n_requests]
        prompts = [rng.integers(0, model.cfg.vocab_size,
                                size=n).astype(np.int32) for n in lens]
        budgets = [3 + (i % 5) for i in range(len(prompts))]

        def serve_all():
            rids = []
            for p, m in zip(prompts, budgets):
                rids.append(eng.add_request(p, max_new_tokens=m))
                # staggered admission: step between submits so requests
                # join a RUNNING batch (and finished ones leave it)
                eng.step()
            outs = eng.run()
            # run()'s contract: every request finished since the last
            # run() is in the dict, INCLUDING ones that finished during
            # the staggered step()s above
            return [outs[r] for r in rids]

        first = serve_all()
        again = serve_all()   # re-serve on the churned engine

        churn_compiles = tel.sentinel.compiles() - compiles_at_warmup
        if churn_compiles:
            failures.append(
                f"{churn_compiles} backend compile(s) AFTER warmup — "
                "the fixed-slot shape contract is broken "
                "(serving/scheduler.py)")
        else:
            print(f"serving-smoke: {2 * len(prompts)} requests "
                  f"(lens {min(lens)}..{max(lens)}, chunked prefill) "
                  "joined/left the batch: 0 compiles after warmup")
        sizes = []
        for fn, want, name in ((eng._step_fn, 1, "step"),
                               (eng._unpack_fn, 1, "unpack"),
                               (eng._cow_fn, 1, "cow")):
            n = getattr(fn, "_cache_size", lambda: None)()
            sizes.append(f"{name}={n}")
            if n is not None and n > want:
                failures.append(
                    f"{name} jit cache holds {n} entries, expected "
                    f"{want} — a retrace slipped past the sentinel")
        print(f"serving-smoke: jit cache sizes at drain: "
              f"{', '.join(sizes)} "
              f"(chunk={eng.prefill_chunk})")

        if eng.kv_blocks_used != 0:
            failures.append(
                f"{eng.kv_blocks_used} KV block(s) still referenced at "
                "drain — reclaim/refcount leak "
                "(serving/block_allocator.py)")
        else:
            alloc = eng.kv.allocator
            print(f"serving-smoke: all KV blocks reclaimed at drain "
                  f"(refcounts 0; {alloc.cached_blocks} prefix-cached "
                  f"pages evictable, {alloc.free_blocks} allocatable "
                  f"of {alloc.num_blocks})")
            if alloc.free_blocks != alloc.num_blocks:
                failures.append(
                    f"only {alloc.free_blocks}/{alloc.num_blocks} blocks "
                    "allocatable at drain — cached pages must stay "
                    "evictable capacity")

        for i, (a, b, m) in enumerate(zip(first, again, budgets)):
            if len(a) != m:
                failures.append(
                    f"request {i}: {len(a)} tokens, budget {m}")
            if a != b:
                failures.append(
                    f"request {i}: re-serve on the churned engine "
                    "diverged — slot state leaked between requests")
        if not any("request" in f for f in failures):
            print("serving-smoke: greedy outputs stable across re-serve")

        # 3. prefix caching: shared prefixes + a fully-cached prompt,
        # outputs token-identical to generate(), hits and CoW observed
        import jax.numpy as jnp
        common = rng.integers(0, model.cfg.vocab_size,
                              size=16).astype(np.int32)   # 2 full pages
        shared_prompts = [np.concatenate(
            [common, rng.integers(0, model.cfg.vocab_size,
                                  size=t).astype(np.int32)])
            for t in (6, 11, 4)] + [common]   # last: fully cached → CoW
        served = []
        for p, m in zip(shared_prompts, (5, 4, 6, 5)):
            rid = eng.add_request(p, max_new_tokens=m)
            outs = eng.run()
            served.append((p, m, outs[rid]))
        churn_compiles = tel.sentinel.compiles() - compiles_at_warmup
        # the generate() references below compile their own programs —
        # check the engine's zero-compile contract BEFORE running them
        for p, m, got in served:
            ref = np.asarray(model.generate(
                jnp.asarray(p)[None], max_new_tokens=m,
                temperature=0.0))[0, len(p):]
            if not np.array_equal(ref, np.asarray(got)):
                failures.append(
                    f"prefix-cached request (prompt {len(p)}) diverged "
                    "from model.generate() — sharing corrupted the KV")
        stats = eng.prefix_stats()
        if stats["hits"] == 0:
            failures.append("no prefix-cache hits across shared-prefix "
                            "requests — the cache never engaged")
        if stats["cow_copies"] == 0:
            failures.append("fully-cached prompt did not trigger "
                            "copy-on-write")
        if eng.kv_blocks_used != 0:
            failures.append(
                f"{eng.kv_blocks_used} KV block(s) still referenced "
                "after the prefix-cache runs")
        if churn_compiles:
            failures.append(
                f"{churn_compiles} compile(s) after warmup once prefix "
                "caching + CoW engaged")
        if not any("prefix" in f or "cached" in f for f in failures):
            print(f"serving-smoke: prefix caching token-identical to "
                  f"generate() (hit rate {stats['hit_rate']:.0%}, "
                  f"{stats['cow_copies']} CoW cop"
                  f"{'y' if stats['cow_copies'] == 1 else 'ies'}, "
                  "0 compiles)")

        # 4. FUSED DECODE PATH (docs/KERNELS.md): the same contracts
        # hold with the fused-kernel entry points forced on and the
        # decode weight path quantized — one warmup compile set, zero
        # compiles under churn, greedy outputs token-identical to
        # model.generate() on the same (quantized, fused) model.
        pt.seed(0)
        fmodel = llama("tiny", fused_ops="on")
        feng = serving.Engine(fmodel, max_batch=max_batch,
                              max_seq_len=64, page_size=8,
                              prefill_chunk=8,
                              weight_quant="int8").warmup()
        fused_warmup = tel.sentinel.compiles()
        fprompts = [rng.integers(0, fmodel.cfg.vocab_size,
                                 size=n).astype(np.int32)
                    for n in (3, 17, 9, 26)]
        served = []
        for p in fprompts:
            rid = feng.add_request(p, max_new_tokens=5)
            feng.step()     # staggered: join a running batch
            outs = feng.run()
            served.append((p, outs[rid]))
        fused_churn = tel.sentinel.compiles() - fused_warmup
        if fused_churn:
            failures.append(
                f"{fused_churn} compile(s) after warmup with the fused "
                "decode path on — a fused entry point re-traces under "
                "churn (ops/tuning must resolve before warmup)")
        for fn, name in ((feng._step_fn, "fused step"),
                         (feng._cow_fn, "fused cow")):
            n = getattr(fn, "_cache_size", lambda: None)()
            if n is not None and n > 1:
                failures.append(
                    f"{name} jit cache holds {n} entries, expected 1")
        for p, got in served:
            ref = np.asarray(fmodel.generate(
                jnp.asarray(p)[None], max_new_tokens=5,
                temperature=0.0))[0, len(p):]
            if not np.array_equal(ref, np.asarray(got)):
                failures.append(
                    f"fused+int8 request (prompt {len(p)}) diverged "
                    "from model.generate() — the fused decode path "
                    "changed greedy outputs")
        if not any("fused" in f for f in failures):
            print(f"serving-smoke: fused decode path (fused_ops=on + "
                  f"int8 weights): {len(fprompts)} requests "
                  "token-identical to generate(), 0 compiles after "
                  "warmup")

        # 5. SPECULATIVE DECODING (docs/SERVING.md "Speculative
        # decoding"): n-gram self-drafting through the one compiled
        # verify step.  Same standing contracts — one warmup compile
        # set, ZERO compiles under draft-HIT churn (looping prompts,
        # verify spans > 1) interleaved with draft-MISS churn (random
        # prompts, draft_len=0 rides the same program), jit caches at
        # one entry, full reclaim — and greedy outputs token-identical
        # to model.generate() (speculation is a perf lever, never a
        # quality trade).
        seng = serving.Engine(model, max_batch=max_batch,
                              max_seq_len=64, page_size=8,
                              prefill_chunk=8, spec_decode=True,
                              draft_depth=4).warmup()
        spec_warmup = tel.sentinel.compiles()
        motif = rng.integers(0, model.cfg.vocab_size,
                             size=5).astype(np.int32)
        sprompts = [np.tile(motif, 3)] + \
            [rng.integers(0, model.cfg.vocab_size,
                          size=n).astype(np.int32)
             for n in (3, 17, 9)] + [np.tile(motif, 3)]
        served = []
        for p in sprompts:
            rid = seng.add_request(p, max_new_tokens=12)
            seng.step()     # staggered: join a running batch
            outs = seng.run()
            served.append((p, outs[rid]))
        spec_churn = tel.sentinel.compiles() - spec_warmup
        if spec_churn:
            failures.append(
                f"{spec_churn} compile(s) after warmup with "
                "speculative decoding on — draft-hit/miss churn must "
                "ride the one compiled (B, C) step as span-length "
                "data, never a new shape")
        for fn, name in ((seng._step_fn, "spec step"),
                         (seng._cow_fn, "spec cow")):
            n = getattr(fn, "_cache_size", lambda: None)()
            if n is not None and n > 1:
                failures.append(
                    f"{name} jit cache holds {n} entries, expected 1")
        for p, got in served:
            ref = np.asarray(model.generate(
                jnp.asarray(p)[None], max_new_tokens=12,
                temperature=0.0))[0, len(p):]
            if not np.array_equal(ref, np.asarray(got)):
                failures.append(
                    f"speculative request (prompt {len(p)}) diverged "
                    "from model.generate() — accept/rollback "
                    "bookkeeping corrupted the stream")
        sstats = seng.spec_stats()
        if sstats["proposed"] == 0:
            failures.append(
                "speculative engine never proposed a draft — the "
                "n-gram proposer lost its looping-prompt coverage")
        if sstats["accepted"] == 0:
            failures.append(
                "no draft token was ever accepted on the looping "
                "prompts — speculative verification or acceptance is "
                "broken")
        if seng.kv_blocks_used != 0:
            failures.append(
                f"{seng.kv_blocks_used} KV block(s) still referenced "
                "after the speculative runs")
        if not any("spec" in f for f in failures):
            print(f"serving-smoke: speculative decoding "
                  f"({sstats['proposed']} drafted, "
                  f"{sstats['accept_rate']:.0%} accepted) "
                  "token-identical to generate(), 0 compiles after "
                  "warmup")

        # 6. BATCHED MULTI-LORA (docs/SERVING.md "Multi-LoRA"): many
        # adapters + the base model churning through ONE engine.  The
        # standing contracts, extended to adapter churn: loading /
        # hot-loading / evicting adapters and mixing adapter ids within
        # a batch are VALUE edits (0 compiles after warmup, jit caches
        # unchanged at 1), and each adapter's greedy outputs are
        # token-identical to a merged-weight (W + B_k A_k) reference
        # model while base requests stay identical to generate() on the
        # unmerged model.
        pt.seed(0)
        lomodel = llama("tiny")
        pool = serving.LoRAPool(lomodel, max_adapters=3, rank=8)
        lrng = np.random.default_rng(7)
        adapter_w = {name: serving.random_adapter(
            lomodel, rank=8, rng=lrng, scale=0.05)
            for name in ("ad-a", "ad-b", "ad-c")}
        pool.load("ad-a", adapter_w["ad-a"])
        pool.load("ad-b", adapter_w["ad-b"])    # ad-c hot-loads below
        leng = serving.Engine(lomodel, max_batch=max_batch,
                              max_seq_len=64, page_size=8,
                              prefill_chunk=8, lora=pool).warmup()
        lora_warmup = tel.sentinel.compiles()
        lprompts = [lrng.integers(0, lomodel.cfg.vocab_size,
                                  size=n).astype(np.int32)
                    for n in (5, 17, 9, 26, 12, 7)]
        mix = [None, "ad-a", "ad-b", "ad-a", "ad-c", "ad-c"]
        served = []
        for i, (p, ad) in enumerate(zip(lprompts, mix)):
            if i == 4:
                # hot-load mid-churn: a buffer write into the stacked
                # pool while requests are in flight — never a retrace
                pool.load("ad-c", adapter_w["ad-c"])
            rid = leng.add_request(p, max_new_tokens=6, adapter=ad)
            leng.step()     # staggered: join a running batch
            served.append((p, ad, rid))
        louts = leng.run()
        leng.add_request(lprompts[0], max_new_tokens=4, adapter="ad-b")
        pool.evict("ad-a")              # idle: evictable mid-serve
        louts.update(leng.run())
        lora_churn = tel.sentinel.compiles() - lora_warmup
        if lora_churn:
            failures.append(
                f"{lora_churn} compile(s) after warmup under multi-LoRA "
                "churn — adapter load/evict/mixed batches must be value "
                "edits into the stacked pool, never a retrace")
        for fn, name in ((leng._step_fn, "lora step"),
                         (leng._cow_fn, "lora cow")):
            n = getattr(fn, "_cache_size", lambda: None)()
            if n is not None and n > 1:
                failures.append(
                    f"{name} jit cache holds {n} entries, expected 1")
        if leng.kv_blocks_used != 0:
            failures.append(
                f"{leng.kv_blocks_used} KV block(s) still referenced "
                "after the multi-LoRA runs")
        merged_models = {}
        for name, w in adapter_w.items():
            pt.seed(0)
            m_ = llama("tiny")
            serving.merge_adapter(m_, w)
            merged_models[name] = m_
        for p, ad, rid in served:
            refm = lomodel if ad is None else merged_models[ad]
            ref = np.asarray(refm.generate(
                jnp.asarray(p)[None], max_new_tokens=6,
                temperature=0.0))[0, len(p):]
            if not np.array_equal(ref, np.asarray(louts[rid])):
                failures.append(
                    f"multi-LoRA request (adapter {ad!r}, prompt "
                    f"{len(p)}) diverged from its "
                    f"{'base' if ad is None else 'merged-weight'} "
                    "reference — the grouped BGMV or slot routing is "
                    "wrong")
        if not any("LoRA" in f or "lora" in f for f in failures):
            print(f"serving-smoke: multi-LoRA ({pool.loads} loads incl. "
                  "1 hot-load mid-churn, 1 evict, mixed "
                  "base+3-adapter batches) token-identical to "
                  "merged-weight references, 0 compiles after warmup")
    finally:
        obs.disable()

    if failures:
        print("serving-smoke gate FAILED (docs/SERVING.md):")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("serving-smoke gate OK")
    return 0


def gate_chaos_serving(max_batch: int = 4) -> int:
    """Chaos-serving gate: the PR-3 resilience machinery applied to the
    serving path (docs/RESILIENCE.md "Serving sites").

    One mixed churn scenario — staggered multi-tenant admission through
    a FrontDoor, chunked prefill, a fully-cached duplicate prompt
    (prefix share + CoW), and a mid-flight preemption (host swap +
    restore) — runs twice on fresh engines: fault-free, then with a
    ``PDTPU_FAULTS`` plan firing at EVERY serving site
    (serve.admit/prefill/step/cow/swap).  The contract:

    1. ZERO step recompiles in both runs: the sentinel's backend-compile
       count stays at its warmup level and the step/CoW/swap jit caches
       hold exactly one executable each — faults are confined to host
       bookkeeping, the compiled programs are never torn down.
    2. FULL RECLAIM at drain: ``used_blocks == 0``, every block
       allocatable — isolation/preempt/restore leaks nothing.
    3. TOKEN IDENTITY: every request's greedy output in the faulted run
       equals the fault-free run — isolation rewinds + swap round-trips
       are byte-exact, and injected swap faults are absorbed by the
       RetryPolicy.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import warnings

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu import resilience as rs
    from paddle_tpu import serving

    SPEC = ("serve.admit@1,serve.prefill@1,serve.step@2,"
            "serve.cow@0,serve.swap@0:OSError")
    serve_sites = ("serve.admit", "serve.prefill", "serve.step",
                   "serve.cow", "serve.swap")
    failures = []
    tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
    try:
        from paddle_tpu.models.llama import llama
        pt.seed(0)
        model = llama("tiny")
        rng = np.random.default_rng(0)
        lens = [3, 17, 9, 33, 5, 26, 12, 21]
        prompts = [rng.integers(0, model.cfg.vocab_size,
                                size=n).astype(np.int32) for n in lens]
        budgets = [3 + (i % 4) for i in range(len(prompts))]
        # page-aligned 2-page prompt, served twice: the second serve is
        # fully cached → borrows both pages and copy-on-writes the last
        shared = rng.integers(0, model.cfg.vocab_size,
                              size=16).astype(np.int32)

        def scenario(spec, tag):
            rs.clear_faults()
            inj = None
            if spec:
                os.environ["PDTPU_FAULTS"] = spec
                inj = rs.install_faults_from_env()
            try:
                eng = serving.Engine(
                    model, max_batch=max_batch, max_seq_len=64,
                    page_size=8, prefill_chunk=8,
                    retry=rs.RetryPolicy(max_attempts=4, backoff_s=0.0,
                                         jitter=0.0,
                                         sleep=lambda _s: None)).warmup()
                c0 = tel.sentinel.compiles()
                door = serving.FrontDoor(eng, policies={
                    "lo": serving.TenantPolicy(priority=0),
                    "hi": serving.TenantPolicy(priority=1)},
                    max_queue_depth=64)
                rids = []
                preempted = False
                with warnings.catch_warnings():
                    # isolation warns per injected fault by design
                    warnings.simplefilter("ignore", RuntimeWarning)
                    for i, (p, m) in enumerate(zip(prompts, budgets)):
                        a = door.submit(
                            p, tenant="hi" if i % 3 == 0 else "lo",
                            max_new_tokens=m)
                        rids.append(a.request_id)
                        door.step()    # staggered: join a RUNNING batch
                    a = door.submit(shared, tenant="lo", max_new_tokens=4)
                    rids.append(a.request_id)
                    door.run()         # registers the shared pages
                    a = door.submit(shared, tenant="lo", max_new_tokens=4)
                    rids.append(a.request_id)
                    door.step()        # fully-cached admission + CoW
                    for _ in range(200):
                        if not preempted:
                            act = eng.scheduler.active()
                            if act:
                                preempted = eng.preempt(
                                    act[0][1].request.request_id)
                        if not door.has_work():
                            break
                        door.step()
                    door.run()
                churn = tel.sentinel.compiles() - c0
                if churn:
                    failures.append(
                        f"{tag}: {churn} backend compile(s) after warmup "
                        "— a fault tore into the compiled path")
                if not preempted:
                    failures.append(f"{tag}: preemption never engaged")
                if eng.kv_blocks_used != 0:
                    failures.append(
                        f"{tag}: {eng.kv_blocks_used} KV block(s) still "
                        "referenced at drain")
                alloc = eng.kv.allocator
                if alloc.free_blocks != alloc.num_blocks:
                    failures.append(
                        f"{tag}: only {alloc.free_blocks}/"
                        f"{alloc.num_blocks} blocks allocatable at drain")
                for fn, name in ((eng._step_fn, "step"),
                                 (eng._cow_fn, "cow"),
                                 (eng._swap._gather, "swap_out"),
                                 (eng._swap._scatter, "swap_in")):
                    n = getattr(fn, "_cache_size", lambda: None)()
                    if n is not None and n > 1:
                        failures.append(
                            f"{tag}: {name} jit cache holds {n} entries "
                            "— a retrace slipped past the sentinel")
                if eng.prefix_stats()["cow_copies"] == 0 and not spec:
                    failures.append(
                        f"{tag}: the duplicate prompt never exercised "
                        "copy-on-write — the scenario lost its cow "
                        "coverage")
                # request-lifecycle tracing rode the whole chaos run
                # (zero compiles above PROVES trace reads stay host-
                # side): every request must carry a complete timeline
                # with the lifecycle phases exactly once, and the
                # preempted request a preempt/restore pair
                tracer = obs.get_request_tracer()
                if tracer is None:
                    failures.append(
                        f"{tag}: request tracing was not active — the "
                        "gate must run with tracing enabled")
                else:
                    saw_preempt = False
                    for r in rids:
                        tl = tracer.timeline(r)
                        if tl is None or not tl["summary"]["done"]:
                            failures.append(
                                f"{tag}: request {r} has no complete "
                                "trace at drain")
                            continue
                        phases = [e["phase"] for e in tl["events"]]
                        once = [ph for ph in ("submit", "first_token",
                                              "retire")
                                if phases.count(ph) != 1]
                        if once or "admit" not in phases:
                            failures.append(
                                f"{tag}: request {r} lifecycle phases "
                                f"malformed ({once or 'no admit'}; "
                                f"{phases})")
                        if "preempt" in phases:
                            saw_preempt = "restore" in phases \
                                or "reset_fresh" in phases or saw_preempt
                    if not saw_preempt:
                        failures.append(
                            f"{tag}: no trace carries the preempt→"
                            "restore pair the scenario forces")
                return [eng.output_ids(r) for r in rids], inj
            finally:
                rs.clear_faults()
                os.environ.pop("PDTPU_FAULTS", None)

        base, _ = scenario(None, "baseline")
        if not failures:
            print(f"chaos-serving: baseline churn ({len(base)} requests, "
                  "preempt+restore, CoW) clean: 0 compiles after warmup, "
                  "all blocks reclaimed")
        faulted, inj = scenario(SPEC, "faulted")
        fired = {site for site, _idx in inj.fired}
        missing = [s for s in serve_sites if s not in fired]
        if missing:
            failures.append(
                f"faulted: plan never fired at {missing} — the scenario "
                "lost coverage of those sites")
        diverged = [i for i, (a, b) in enumerate(zip(base, faulted))
                    if a != b]
        if diverged:
            failures.append(
                f"faulted: requests {diverged} diverged from the "
                "fault-free run — isolation/restore is not "
                "token-preserving")
        elif not missing:
            print(f"chaos-serving: faults at all {len(serve_sites)} "
                  "serving sites absorbed: outputs token-identical to "
                  "the fault-free run, 0 compiles, all blocks reclaimed")

        # SPECULATIVE DECODING under chaos (docs/SERVING.md
        # "Speculative decoding"): the same run with verify spans in
        # flight.  serve.step is the per-decode-slot bookkeeping site,
        # so with drafts attached it fires MID-VERIFY — the rollback
        # must rewind the pre-span snapshot (kv_len only ever covered
        # accepted tokens, so the speculative tail needs no undo);
        # serve.spec degrades one slot's drafting to draft_len=0; an
        # injected swap fault plus a manual mid-decode preemption ride
        # the preempt→restore path with speculation live.  Greedy
        # outputs must stay token-identical to the fault-free
        # speculative run, with zero compiles and full reclaim.
        SSPEC = "serve.spec@1,serve.step@3x2,serve.swap@0:OSError"
        spec_sites = ("serve.spec", "serve.step", "serve.swap")
        motif = rng.integers(0, model.cfg.vocab_size,
                             size=5).astype(np.int32)
        spec_prompts = [np.tile(motif, 3),
                        rng.integers(0, model.cfg.vocab_size,
                                     size=9).astype(np.int32),
                        np.tile(rng.integers(0, model.cfg.vocab_size,
                                             size=4).astype(np.int32), 4),
                        rng.integers(0, model.cfg.vocab_size,
                                     size=17).astype(np.int32)]
        # long enough for a random tiny model's greedy output to fall into
        # a cycle: the drafts it accepts are of its own loop, the motifs
        # owe it none (at 5-10 tokens nothing was ever accepted)
        spec_budgets = (16, 16, 16, 16)

        def spec_scenario(spec, tag):
            rs.clear_faults()
            inj = None
            if spec:
                os.environ["PDTPU_FAULTS"] = spec
                inj = rs.install_faults_from_env()
            try:
                eng = serving.Engine(
                    model, max_batch=max_batch, max_seq_len=64,
                    page_size=8, prefill_chunk=8, spec_decode=True,
                    draft_depth=3,
                    retry=rs.RetryPolicy(max_attempts=4, backoff_s=0.0,
                                         jitter=0.0,
                                         sleep=lambda _s: None)).warmup()
                c0 = tel.sentinel.compiles()
                rids = []
                preempted = False
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", RuntimeWarning)
                    for p, m_ in zip(spec_prompts, spec_budgets):
                        rids.append(eng.add_request(p, max_new_tokens=m_))
                        eng.step()
                    for _ in range(200):
                        if not preempted:
                            # victim a DECODING slot so the preemption
                            # lands mid-speculation: kv_len covers only
                            # accepted tokens, so the swap/restore must
                            # round-trip exactly that prefix
                            for _slot, st in eng.scheduler.active():
                                if not st.prefilling:
                                    preempted = eng.preempt(
                                        st.request.request_id)
                                    break
                        if not eng.has_work():
                            break
                        eng.step()
                    eng.run()
                churn = tel.sentinel.compiles() - c0
                if churn:
                    failures.append(
                        f"{tag}: {churn} compile(s) after warmup on "
                        "the speculative engine")
                if not preempted:
                    failures.append(
                        f"{tag}: mid-decode preemption never engaged "
                        "on the speculative engine")
                if eng.kv_blocks_used != 0:
                    failures.append(
                        f"{tag}: {eng.kv_blocks_used} KV block(s) "
                        "leaked on the speculative engine")
                if eng.spec_stats()["accepted"] == 0:
                    failures.append(
                        f"{tag}: no draft token accepted — the "
                        "scenario lost its speculative coverage")
                return [eng.output_ids(r) for r in rids], inj
            finally:
                rs.clear_faults()
                os.environ.pop("PDTPU_FAULTS", None)

        sbase, _ = spec_scenario(None, "spec-baseline")
        sfault, sinj = spec_scenario(SSPEC, "spec-faulted")
        sfired = {site for site, _idx in sinj.fired}
        smissing = [s for s in spec_sites if s not in sfired]
        if smissing:
            failures.append(
                f"spec-faulted: plan never fired at {smissing} — the "
                "scenario lost coverage of those sites")
        sdiverged = [i for i, (a, b) in enumerate(zip(sbase, sfault))
                     if a != b]
        if sdiverged:
            failures.append(
                f"spec-faulted: requests {sdiverged} diverged from the "
                "fault-free speculative run — mid-verify rollback or "
                "preempt→restore is not token-preserving")
        elif not smissing:
            print("chaos-serving: mid-verify + draft-proposer faults "
                  "and a mid-decode preemption absorbed on the "
                  "speculative engine: outputs token-identical, "
                  "0 compiles, all blocks reclaimed")
    finally:
        obs.disable()

    if failures:
        print("chaos-serving gate FAILED (docs/RESILIENCE.md):")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("chaos-serving gate OK")
    return 0


def gate_serving_dist(max_batch: int = 4) -> int:
    """Serving-dist gate: sharded serving keeps every single-chip
    contract (docs/SERVING.md "Sharded serving"), on a forced 8-device
    CPU host platform (the gate re-execs itself in a subprocess when
    the already-initialized backend has fewer devices):

    1. TP IDENTITY: a TP=2 engine (params sharded by their partition
       specs, paged KV pools head-sharded over ``mp``) serves a mixed
       churn workload with prefix-cache hits and produces greedy
       outputs TOKEN-IDENTICAL to the single-chip engine — with zero
       compiles after warmup (sentinel + step/CoW jit-cache sizes) and
       the pools verifiably mp-sharded.
    2. DP REPLICA ROUTING: two TP=2 replicas (disjoint submeshes)
       behind the existing FrontDoor, multi-tenant staggered churn with
       a duplicated prompt (prefix-affinity routing), and ONE injected
       ``serve.replica`` fault mid-churn.  The failed replica must be
       evacuated through preempt→swap→restore onto the survivor, every
       request must complete token-identical to the single-chip run —
       nothing dropped, nothing recompiled, and every KV block
       reclaimed on EVERY replica (the dead one included).
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    if len(jax.devices()) < 8:
        # an 8-device virtual mesh needs XLA_FLAGS before backend init —
        # too late in this process, so run the gate in a child
        pp = os.environ.get("PYTHONPATH")
        flags = " ".join(
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": REPO + (os.pathsep + pp if pp else ""),
               "XLA_FLAGS": (flags +
                             " --xla_force_host_platform_device_count=8"
                             ).strip()}
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--only",
             "serving-dist"],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=1500)
        sys.stdout.write(r.stdout)
        sys.stderr.write(r.stderr)
        return r.returncode

    import warnings

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu import resilience as rs
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    # Persistent compile cache (the same dir tests/conftest.py uses):
    # this gate compiles four engines' worth of sharded programs, the
    # suite's wall-clock budget is tight, and the contract here is
    # WITHIN-RUN token equality across different programs — a cache-hit
    # executable cannot skew that (unlike the chaos gate's
    # bitwise-across-runs contract, which deliberately avoids the cache).
    # Where JAX_COMPILATION_CACHE_DIR is set jax reads it and no other
    # directory is set in code.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            REPO, ".pytest_cache", "xla_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    failures = []
    tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
    try:
        rng = np.random.default_rng(0)
        lens = [3, 17, 9, 33, 5, 26, 12, 21]
        prompts = [rng.integers(0, 256, size=n).astype(np.int32)
                   for n in lens]
        budgets = [3 + (i % 4) for i in range(len(prompts))]
        # page-aligned 2-page prompt served twice: prefix hits on the
        # re-serve, and (in the DP phase) affinity pins the repeat to
        # the replica already holding the pages
        shared = rng.integers(0, 256, size=16).astype(np.int32)

        def build_model():
            pt.seed(0)
            return llama("tiny")

        def churn(target, submit, step, drain, rid_sink=None):
            """The one workload every phase runs: staggered admission,
            then the duplicated shared prompt twice (hits + CoW)."""
            rids = []
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for p, m in zip(prompts, budgets):
                    rids.append(submit(p, m))
                    step()
                rids.append(submit(shared, 4))
                outs = drain()
                rids.append(submit(shared, 4))
                outs.update(drain())
            if rid_sink is not None:
                rid_sink.extend(rids)
            return [outs[r] for r in rids]

        def engine_churn(eng):
            return churn(eng,
                         lambda p, m: eng.add_request(p, max_new_tokens=m),
                         eng.step, eng.run)

        # single-chip reference
        ref_eng = serving.Engine(build_model(), max_batch=max_batch,
                                 max_seq_len=64, page_size=8,
                                 prefill_chunk=8).warmup()
        ref = engine_churn(ref_eng)

        # 1. TP=2: identical outputs, zero compiles, sharded pools
        mesh = serving.serving_mesh(tp=2)
        eng = serving.Engine(build_model(), max_batch=max_batch,
                             max_seq_len=64, page_size=8,
                             prefill_chunk=8, mesh=mesh).warmup()
        c0 = tel.sentinel.compiles()
        got = engine_churn(eng)
        churn_compiles = tel.sentinel.compiles() - c0
        spec = tuple(eng.kv.caches[0][0].sharding.spec)
        if len(spec) < 3 or spec[2] != "mp":
            failures.append(
                f"TP pools not head-sharded over mp: spec {spec}")
        if got != ref:
            bad = [i for i, (a, b) in enumerate(zip(got, ref)) if a != b]
            failures.append(
                f"TP=2 outputs diverged from single-chip at requests "
                f"{bad} — GSPMD partitioning changed the decode")
        if churn_compiles:
            failures.append(
                f"TP=2: {churn_compiles} compile(s) after warmup")
        for fn, name in ((eng._step_fn, "step"),
                         (eng._unpack_fn, "unpack"), (eng._cow_fn, "cow")):
            n = getattr(fn, "_cache_size", lambda: None)()
            if n is not None and n > 1:
                failures.append(
                    f"TP=2: {name} jit cache holds {n} entries — the "
                    "sharded dispatch re-traced")
        if eng.kv_blocks_used != 0:
            failures.append(
                f"TP=2: {eng.kv_blocks_used} KV block(s) leaked")
        if not failures:
            print(f"serving-dist: TP=2 engine token-identical to "
                  f"single-chip over {len(ref)} requests "
                  f"(pools {spec}, 0 compiles after warmup)")

        # 2. DP: 2 TP=2 replicas behind the FrontDoor, one injected
        # replica fault mid-churn
        rs.clear_faults()
        meshes = serving.replica_meshes(2, tp=2)
        reps = [serving.Engine(build_model(), max_batch=max_batch,
                               max_seq_len=64, page_size=8,
                               prefill_chunk=8, mesh=m) for m in meshes]
        rset = serving.EngineReplicaSet(reps).warmup()
        door = serving.FrontDoor(rset, policies={
            "lo": serving.TenantPolicy(priority=0),
            "hi": serving.TenantPolicy(priority=1)}, max_queue_depth=64)
        c0 = tel.sentinel.compiles()
        inj = rs.install_faults("serve.replica@6")
        try:
            i_box = [0]

            def submit(p, m):
                i_box[0] += 1
                a = door.submit(
                    p, tenant="hi" if i_box[0] % 3 == 0 else "lo",
                    max_new_tokens=m)
                return a.request_id

            dp_rids = []
            got = churn(door, submit, door.step, door.run,
                        rid_sink=dp_rids)
        finally:
            rs.clear_faults()
        churn_compiles = tel.sentinel.compiles() - c0
        if not inj.fired:
            failures.append("DP: the serve.replica fault never fired — "
                            "the scenario lost its failure coverage")
        # pdtpu-lint: disable=lock-discipline — single-threaded gate driver
        health = list(rset._health)
        if rset.failures != 1 or all(health):
            failures.append(
                f"DP: expected exactly one failed replica, got "
                f"failures={rset.failures}, health={health}")
        if got != ref:
            bad = [i for i, (a, b) in enumerate(zip(got, ref)) if a != b]
            failures.append(
                f"DP: requests {bad} diverged from the single-chip run "
                "— evacuation/restore is not token-preserving")
        if churn_compiles:
            failures.append(
                f"DP: {churn_compiles} compile(s) after warmup")
        for i, rep in enumerate(reps):
            if rep.kv_blocks_used != 0:
                failures.append(
                    f"DP: replica {i} holds {rep.kv_blocks_used} KV "
                    "block(s) at drain (evacuation leaked)")
            alloc = rep.kv.allocator
            if alloc.free_blocks != alloc.num_blocks:
                failures.append(
                    f"DP: replica {i} has only {alloc.free_blocks}/"
                    f"{alloc.num_blocks} blocks allocatable at drain")
            for fn, name in ((rep._step_fn, "step"), (rep._cow_fn, "cow")):
                n = getattr(fn, "_cache_size", lambda: None)()
                if n is not None and n > 1:
                    failures.append(
                        f"DP: replica {i} {name} jit cache holds {n} "
                        "entries")
        hits = rset.prefix_stats()["hits"]
        if hits == 0:
            failures.append("DP: no prefix-cache hits — affinity "
                            "routing never engaged the duplicate prompt")
        # trace continuity across the injected replica failure (the
        # zero-compiles check above already proved tracing stayed
        # host-side): every DP request keeps ONE complete timeline with
        # a route decision, and the evacuation shows up as migrate (or
        # degraded reset_fresh) events on the survivors' traces
        tracer = obs.get_request_tracer()
        if tracer is None:
            failures.append("DP: request tracing was not active")
        else:
            migrated = 0
            for r in dp_rids:
                tl = tracer.timeline(r)
                if tl is None or not tl["summary"]["done"] \
                        or not tl["trace_id"]:
                    failures.append(
                        f"DP: request {r} lost its trace across the "
                        "replica failure")
                    continue
                phases = [e["phase"] for e in tl["events"]]
                if phases.count("retire") != 1 \
                        or phases.count("submit") != 1:
                    failures.append(
                        f"DP: request {r} lifecycle phases malformed "
                        f"({phases})")
                if "route" not in phases:
                    failures.append(
                        f"DP: request {r} trace carries no routing "
                        "decision")
                migrated += sum(1 for ph in phases
                                if ph in ("migrate", "reset_fresh"))
            if rset.requeued and migrated == 0:
                failures.append(
                    "DP: replicas evacuated requests but no trace "
                    "carries a migrate event")
        if not any(f.startswith("DP") for f in failures):
            print(f"serving-dist: DP 2x(TP=2) replicas survived an "
                  f"injected replica fault ({rset.requeued} request(s) "
                  f"requeued) — all {len(ref)} outputs token-identical, "
                  f"0 compiles, all blocks reclaimed, "
                  f"{hits} prefix hit(s)")
    finally:
        obs.disable()

    if failures:
        print("serving-dist gate FAILED (docs/SERVING.md \"Sharded "
              "serving\"):")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("serving-dist gate OK")
    return 0


def gate_serving_disagg(max_batch: int = 4) -> int:
    """Serving-disagg gate: the prefill/decode split keeps every
    colocated contract (docs/SERVING.md "Disaggregated serving"):

    mixed churn (staggered admissions + a duplicated page-aligned
    prompt for prefix hits on the prefill tier, int8 pools) runs
    through 2 prefill + 2 decode replicas whose KV pages stream over a
    StoreTransport on a real in-process TCPStore, with injected
    ``serve.xfer.put``/``serve.xfer.get`` faults (two transient — the
    transport's RetryPolicy absorbs them — and one burst long enough
    to exhaust retries, forcing the hard-failure fresh-re-prefill
    fallback) and ONE decode-replica kill mid-churn (its in-flight
    requests re-enter the handoff queue).  Demands: greedy outputs
    TOKEN-IDENTICAL to a colocated engine's run, zero compiles after
    warmup on every replica, every KV block reclaimed on every replica
    (the dead one included), and every request's trace timeline
    complete — exactly one submit and one retire, an ``xfer`` segment,
    and queue+prefill+xfer+decode summing exactly to wall.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import warnings

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu import resilience as rs
    from paddle_tpu import serving
    from paddle_tpu.launch.store import TCPStore
    from paddle_tpu.models.llama import llama

    failures = []
    tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
    store = TCPStore("127.0.0.1:0", is_master=True)
    try:
        rng = np.random.default_rng(0)
        lens = [3, 17, 9, 33, 5, 26, 12, 21]
        prompts = [rng.integers(0, 256, size=n).astype(np.int32)
                   for n in lens]
        budgets = [3 + (i % 4) for i in range(len(prompts))]
        # page-aligned 2-page prompt served twice: prefix hits land on
        # the PREFILL tier (the decode tier never prefills a hit)
        shared = rng.integers(0, 256, size=16).astype(np.int32)

        def build_engine(role):
            pt.seed(0)
            return serving.Engine(
                llama("tiny"), max_batch=max_batch, max_seq_len=64,
                page_size=8, prefill_chunk=8, kv_cache_dtype="int8",
                role=role)

        def churn(submit, step, drain, rid_sink=None):
            rids = []
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for p, m in zip(prompts, budgets):
                    rids.append(submit(p, m))
                    step()
                rids.append(submit(shared, 4))
                outs = drain()
                rids.append(submit(shared, 4))
                outs.update(drain())
            if rid_sink is not None:
                rid_sink.extend(rids)
            return [outs[r] for r in rids]

        # colocated reference (same int8 pools, same workload)
        ref_eng = build_engine("both").warmup()
        ref = churn(lambda p, m: ref_eng.add_request(p, max_new_tokens=m),
                    ref_eng.step, ref_eng.run)

        transport = serving.StoreTransport(store, op_timeout_s=20.0)
        pre = [build_engine("prefill").warmup(),
               build_engine("prefill").warmup()]
        dec = [build_engine("decode").warmup(),
               build_engine("decode").warmup()]
        dset = serving.DisaggReplicaSet(pre, dec, transport=transport)
        c0 = tel.sentinel.compiles()
        # two transient xfer faults (absorbed by the retry policy) plus
        # a 12-call burst that exhausts the 3-attempt policy — the hard
        # transfer failure the fresh-re-prefill fallback covers
        inj = rs.install_faults(
            "serve.xfer.put@2:ConnectionError,"
            "serve.xfer.get@5:ConnectionError,serve.xfer.put@9x12")
        killed = [False]
        steps = [0]

        def step():
            steps[0] += 1
            dset.step()
            if steps[0] == 6 and not killed[0]:
                killed[0] = True
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", RuntimeWarning)
                    dset._fail_replica(
                        dset._decode_idx[0],
                        RuntimeError("injected decode-replica kill"))

        try:
            ds_rids = []
            got = churn(
                lambda p, m: dset.add_request(p, max_new_tokens=m),
                step, dset.run, rid_sink=ds_rids)
        finally:
            rs.clear_faults()
        churn_compiles = tel.sentinel.compiles() - c0

        if len(inj.fired) < 3:
            failures.append(
                f"xfer faults under-fired ({inj.fired}) — the scenario "
                "lost its transfer-fault coverage")
        if not killed[0]:
            failures.append("the decode-replica kill never happened")
        # pdtpu-lint: disable=lock-discipline — single-threaded gate
        health = list(dset._health)
        if dset.failures != 1 or health[dset._decode_idx[0]]:
            failures.append(
                f"expected exactly the killed decode replica dead, got "
                f"failures={dset.failures}, health={health}")
        st = dset.disagg_stats()
        if st["handoffs"] == 0 or st["xfers"] == 0:
            failures.append(
                f"no KV-page handoffs happened ({st}) — the set ran "
                "colocated and proved nothing")
        if st["xfer_failures"] == 0:
            failures.append(
                "the hard xfer-fault burst never exhausted the retries "
                "— the fresh-re-prefill fallback went unexercised")
        if got != ref:
            bad = [i for i, (a, b) in enumerate(zip(got, ref)) if a != b]
            failures.append(
                f"disagg outputs diverged from the colocated run at "
                f"requests {bad} — the handoff is not token-preserving")
        if churn_compiles:
            failures.append(
                f"{churn_compiles} compile(s) after warmup — the "
                "transfer path retraced something")
        for i, rep in enumerate(dset.replicas):
            if rep.kv_blocks_used != 0:
                failures.append(
                    f"replica {i} ({rep.role}) holds "
                    f"{rep.kv_blocks_used} KV block(s) at drain")
            alloc = rep.kv.allocator
            if alloc.free_blocks != alloc.num_blocks:
                failures.append(
                    f"replica {i} has only {alloc.free_blocks}/"
                    f"{alloc.num_blocks} blocks allocatable at drain")
            for fn, name in ((rep._step_fn, "step"),
                             (rep._cow_fn, "cow")):
                n = getattr(fn, "_cache_size", lambda: None)()
                if n is not None and n > 1:
                    failures.append(
                        f"replica {i} {name} jit cache holds {n} "
                        "entries — something re-traced")
        hits = sum(pre[i].prefix_stats()["hits"] for i in range(len(pre)))
        if hits == 0:
            failures.append(
                "no prefix-cache hits on the prefill tier — the "
                "duplicate prompt re-prefilled from scratch")
        # trace completeness across handoff + kill + fallback: one
        # timeline per request, exactly one submit/retire, an xfer
        # segment, and the four-phase sum exact as printed
        tracer = obs.get_request_tracer()
        if tracer is None:
            failures.append("request tracing was not active")
        else:
            for r in ds_rids:
                tl = tracer.timeline(r)
                if tl is None or not tl["summary"]["done"]:
                    failures.append(
                        f"request {r} lost its trace across the handoff")
                    continue
                phases = [e["phase"] for e in tl["events"]]
                if phases.count("submit") != 1 \
                        or phases.count("retire") != 1:
                    failures.append(
                        f"request {r} lifecycle phases malformed "
                        f"({phases})")
                if not any(e.get("closed") == "xfer"
                           for e in tl["events"]):
                    failures.append(
                        f"request {r} timeline has no xfer segment — "
                        "the handoff left the trace")
                s = tl["summary"]
                if abs(s["queue_ms"] + s["prefill_ms"] + s["xfer_ms"]
                       + s["decode_ms"] - s["wall_ms"]) > 1e-9:
                    failures.append(
                        f"request {r} phase sum != wall ({s})")
        if not failures:
            print(f"serving-disagg: 2 prefill + 2 decode replicas over "
                  f"a TCPStore transport survived {len(inj.fired)} "
                  f"injected xfer fault(s) ({st['xfer_failures']} hard, "
                  f"degraded to re-prefill) and a decode-replica kill — "
                  f"all {len(ref)} outputs token-identical to the "
                  f"colocated run, {st['xfers']} transfer(s) / "
                  f"{st['xfer_bytes']} bytes shipped, 0 compiles, all "
                  f"blocks reclaimed, {hits} prefix hit(s), every "
                  f"timeline complete with an xfer segment")
    finally:
        obs.disable()
        store.close()

    if failures:
        print("serving-disagg gate FAILED (docs/SERVING.md "
              "\"Disaggregated serving\"):")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("serving-disagg gate OK")
    return 0


def gate_serving_cluster(n_prefill: int = 2, n_decode: int = 2) -> int:
    """Serving-cluster gate: the control plane keeps every colocated
    contract across real OS processes (docs/SERVING.md "Cluster
    serving"):

    2 prefill + 2 decode ``python -m paddle_tpu.serving.worker``
    processes register with a real TCPStore under epoch-fenced leases,
    with ``cluster.register``/``cluster.lease``/``cluster.command``
    faults injected in EVERY worker via ``PDTPU_FAULTS`` (transient —
    the worker's RetryPolicy and command-requeue absorb them without a
    lease loss).  Mid-churn a decode worker is SIGKILLed the moment it
    owns an uncollected assignment (lease-expiry evacuation) and a
    prefill worker is force-``role_flip``ped to decode.  Demands:
    every wave greedy TOKEN-IDENTICAL to a colocated engine, the flip
    acked with the membership record showing the new role, and every
    surviving worker's exit report showing 0 compiles after warmup,
    every KV block reclaimed, 0 lease losses, and the injected faults
    actually fired.

    Fleet observability demands (docs/OBSERVABILITY.md "Fleet
    observability"), scraped from the controller's own HTTP surface
    MID-CHURN (right after the SIGKILL): ``GET /metrics`` is valid
    prom exposition carrying per-worker-labelled rows AND merged fleet
    rollups with fleet tokens advancing between scrapes; and after the
    waves drain, EVERY request has one stitched cross-host timeline —
    ≥ 2 hosts, per-segment exact-sum phase accounting, a positive xfer
    phase, monotonic after clock-skew correction.

    Phase B kills the CONTROLLER: an active controller subprocess
    (tests/cluster_controller.py, 3s ``ControllerLease``, transient
    ``cluster.journal`` fault in its submit path) journals keyed
    submissions and is SIGKILLed mid-churn; an in-gate standby
    follower takes over off the stale lease (first attempt aborted by
    an injected ``cluster.takeover`` fault), replays the journal, and
    every re-submitted ``Idempotency-Key`` resolves to the SAME rid —
    token-identical, zero duplicate admissions, ctl epoch bumped past
    the corpse.  A ``ClusterGateway`` smoke over the winner then
    demands: SSE stream off the fenced record token-identical to the
    colocated refs, a duplicate Idempotency-Key POST replaying the
    same rid, and a draining gateway shedding the typed 503 +
    Retry-After.  Worker drain + exit-report audits (0 compiles, all
    blocks reclaimed) run through the takeover winner."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import http.client
    import re as _re
    import time

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.launch.store import TCPStore, free_port
    from paddle_tpu.models.llama import llama
    from paddle_tpu.observability import aggregate as obs_agg

    failures = []
    rng = np.random.default_rng(0)
    lens = [5, 17, 9, 26]
    prompts = [rng.integers(0, 256, size=n).astype(np.int32)
               for n in lens]

    def build_engine():
        pt.seed(0)
        return serving.Engine(llama("tiny"), max_batch=2,
                              max_seq_len=64, page_size=8,
                              prefill_chunk=8)

    ref_eng = build_engine().warmup()
    refs = {}
    for budget in (8, 24):
        rids = [ref_eng.add_request(p, max_new_tokens=budget)
                for p in prompts]
        outs = ref_eng.run()
        refs[budget] = [outs[r] for r in rids]

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(REPO, ".pytest_cache", "xla_cache")
    env = {**os.environ,
           "PDTPU_REPO": REPO,
           "PYTHONPATH": REPO,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_COMPILATION_CACHE_DIR": cache,
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
           "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
           # the workers' stderr is a pipe nobody reads until they exit;
           # on a warm compile cache every hit logs ~4 KB of
           # cpu_aot_loader lines and a full pipe (64 KiB) stops a worker
           "TF_CPP_MIN_LOG_LEVEL": "3",
           # transient control-plane faults in EVERY worker: a retried
           # register, a retried lease renew, a requeued first command
           "PDTPU_FAULTS": ("cluster.register@1;"
                            "cluster.lease@1:ConnectionError;"
                            "cluster.command@0")}
    store = TCPStore(f"127.0.0.1:{free_port()}", is_master=True)
    factory = os.path.join(REPO, "tests", "cluster_worker.py") \
        + ":make_serving_engine"
    roles = ["prefill"] * n_prefill + ["decode"] * n_decode
    procs = {}
    reports = {}
    ctl_proc = None
    gw = None
    try:
        for i, role in enumerate(roles):
            wid = f"cw{i}-{role}"
            procs[wid] = subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.serving.worker",
                 "--store", store.endpoint, "--role", role,
                 "--factory", factory, "--worker-id", wid,
                 "--lease-deadline-s", "6",
                 "--status-interval-s", "0.05",
                 "--steps-per-poll", "2", "--seed", "0"],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
        ctl = serving.ClusterController(store, lease_deadline_s=6.0)
        http_host, http_port = ctl.serve_http()

        def scrape():
            conn = http.client.HTTPConnection(http_host, http_port,
                                              timeout=30)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            body = r.read().decode()
            conn.close()
            if r.status != 200 or "text/plain" not in (
                    r.getheader("Content-Type") or ""):
                failures.append(
                    f"GET /metrics answered {r.status} "
                    f"{r.getheader('Content-Type')!r}")
            sample = _re.compile(
                r"^[A-Za-z_:][A-Za-z0-9_:]*(\{[^{}]*\})? \S+$")
            bad = [ln for ln in body.splitlines()
                   if ln and not ln.startswith("# ")
                   and not sample.match(ln)]
            if bad:
                failures.append(
                    f"/metrics is not valid prom exposition: {bad[:3]}")

            def fleet_counter(name):
                tot = 0.0
                for ln in body.splitlines():
                    if ln.startswith(f"{name} "):
                        tot += float(ln.split()[-1])
                return tot
            return body, fleet_counter

        def alive_or_fail(may_exit=()):
            for wid, p in procs.items():
                if wid not in may_exit and p.poll() is not None:
                    out, err = p.communicate(timeout=10)
                    raise RuntimeError(
                        f"{wid} died rc={p.returncode}\n{out}\n{err}")

        deadline = time.time() + 300
        while True:
            alive_or_fail()
            try:
                ctl.wait_for_workers(len(roles), timeout_s=2.0)
                break
            except TimeoutError:
                if time.time() > deadline:
                    raise

        def pump_until(rids, *, timeout_s=240.0, may_exit=(), c=None):
            c = ctl if c is None else c
            end = time.time() + timeout_s
            while time.time() < end:
                c.pump()
                if all(r in c.outputs for r in rids):
                    return
                alive_or_fail(may_exit)
                time.sleep(0.01)
            missing = [r for r in rids if r not in c.outputs]
            raise RuntimeError(f"undelivered: {missing}")

        # wave 1: plain disagg churn across the fleet
        w1 = [ctl.submit(p, max_new_tokens=8) for p in prompts]
        pump_until(w1)
        got = [ctl.outputs[r]["tokens"] for r in w1]
        if got != refs[8]:
            failures.append(
                "wave-1 outputs diverged from the colocated run — "
                "the fleet is not token-preserving")
        body1, fleet1 = scrape()
        toks1 = fleet1("serve_tokens")
        if toks1 <= 0:
            failures.append(
                f"post-wave-1 /metrics fleet serve_tokens = {toks1} — "
                "the fold dropped the workers' counters")
        for wid in procs:
            if f'worker="{wid}"' not in body1:
                failures.append(
                    f"/metrics carries no per-worker rows for {wid}")
        if 'quantile="0.95"' not in body1 \
                or "serve_ttft_ms_count" not in body1:
            failures.append(
                "/metrics fleet rollup has no merged-sketch ttft "
                "summary (serve_ttft_ms quantile rows)")

        # wave 2 under load: SIGKILL a decode worker that owns an
        # uncollected assignment, and force-flip a prefill worker
        victim, w2 = None, []
        flipped = f"cw{n_prefill - 1}-prefill"
        cid = ctl.role_flip(flipped, "decode")
        end = time.time() + 120
        while victim is None and time.time() < end:
            w2 += [ctl.submit(p, max_new_tokens=24) for p in prompts]
            wave_end = time.time() + 5
            while victim is None and time.time() < wave_end:
                ctl.pump()
                for r in w2:
                    a = ctl._assigned.get(r)
                    if r not in ctl.outputs and a \
                            and a["wid"].endswith("decode") \
                            and a["wid"] != flipped:
                        victim = a["wid"]
                        break
        if victim is None:
            failures.append("no decode worker ever owned an "
                            "assignment — nothing was killed")
        else:
            procs[victim].kill()
            # MID-CHURN scrape: a dead worker and an in-flight role
            # flip must not break the exposition, and fleet tokens
            # must keep advancing.  Snapshots land at status cadence,
            # so poll — every iteration still demands a valid scrape
            # (grammar + per-worker rows) with the victim dead.
            end2 = time.time() + 60
            body2, fleet2 = scrape()
            while fleet2("serve_tokens") <= toks1 \
                    and time.time() < end2:
                ctl.pump()
                time.sleep(0.2)
                body2, fleet2 = scrape()
            if fleet2("serve_tokens") <= toks1:
                failures.append(
                    f"mid-churn fleet serve_tokens stuck at {toks1} "
                    "— the fold stopped advancing under churn")
            pump_until(w2, may_exit=(victim,))
            for i, r in enumerate(w2):
                if ctl.outputs[r]["tokens"] != refs[24][i % len(lens)]:
                    failures.append(
                        f"wave-2 request {r} diverged after the kill/"
                        "flip — evacuation is not token-preserving")
                    break
            if ctl.members()[victim].get("state") != "dead":
                failures.append(
                    f"killed worker {victim} never marked dead")
        ack = ctl.command_ack(cid)
        if not ack or not ack.get("ok"):
            failures.append(f"role_flip never acked ok ({ack})")
        if ctl.members().get(flipped, {}).get("role") != "decode":
            failures.append(
                f"{flipped} membership record still shows "
                f"{ctl.members().get(flipped, {}).get('role')!r} "
                "after the flip")

        # every delivered request must stitch into ONE cross-host
        # timeline: prefill on one host, decode on another, the
        # inter-host gap attributed to xfer, each segment keeping its
        # exact-sum phase accounting, ordering monotonic after the
        # workers' clock-skew correction
        n_fail0 = len(failures)
        for rid in w1 + w2:
            tl = ctl.request_timeline(rid)
            if tl is None:
                failures.append(f"{rid}: no stitched timeline "
                                "(workers published no trace segments)")
                continue
            if len(tl["hosts"]) < 2:
                failures.append(
                    f"{rid}: timeline covers hosts {tl['hosts']} — a "
                    "disagg request must cross prefill → decode")
            if not tl["monotonic"]:
                failures.append(
                    f"{rid}: segments out of order after skew "
                    f"correction ({[s['worker'] for s in tl['segments']]})")
            if not tl["xfer_ms"] > 0:
                failures.append(
                    f"{rid}: no xfer phase in the stitched timeline "
                    f"({tl['xfer_ms']} ms)")
            if tl["decode_tokens"] is None or tl["decode_tokens"] <= 0:
                failures.append(
                    f"{rid}: stitched timeline lost the decode tokens")
            for seg in tl["segments"]:
                s = seg["summary"]
                parts = sum(s.get(k) or 0.0 for k in
                            ("queue_ms", "prefill_ms", "xfer_ms",
                             "decode_ms"))
                if abs(parts - (s.get("wall_ms") or 0.0)) > 0.005:
                    failures.append(
                        f"{rid}: segment on {seg['worker']} broke the "
                        f"exact-sum invariant ({parts} vs "
                        f"{s.get('wall_ms')})")
            if len(failures) > n_fail0:
                break                # one broken timeline is enough

        # ---- phase B: the controller is as killable as the workers
        # (docs/SERVING.md "Cluster serving" failure matrix).  An
        # ACTIVE controller subprocess under a 3s ControllerLease —
        # with a transient cluster.journal fault injected into its
        # submit path — journals keyed submissions pushed through the
        # store-backed gate/req queue and acks each key's rid AFTER
        # the durable journal write.  It is SIGKILLed mid-churn; the
        # in-gate standby follower must take over off the stale lease
        # (first attempt aborted by an injected cluster.takeover
        # fault), replay the journal, and answer EVERY re-submitted
        # idempotency key with the SAME rid it acked — token-identical
        # outputs, zero duplicate admissions, zero recompiles.
        from paddle_tpu import resilience as rs
        env_ctl = {**env, "PDTPU_FAULTS": "cluster.journal@1"}
        ctl_proc = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "tests", "cluster_controller.py"),
             "--store", store.endpoint, "--lease-deadline-s", "3",
             "--worker-lease-deadline-s", "6"],
            env=env_ctl, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)

        def ctl_proc_alive_or_fail():
            if ctl_proc.poll() is not None:
                out_, err_ = ctl_proc.communicate(timeout=10)
                raise RuntimeError(
                    f"controller subprocess died early "
                    f"rc={ctl_proc.returncode}\n{out_}\n{err_}")

        end = time.time() + 300
        while store.get("cluster/ctl/lease") is None:
            ctl_proc_alive_or_fail()
            if time.time() > end:
                raise RuntimeError(
                    "controller subprocess never acquired the lease")
            time.sleep(0.05)
        standby = serving.ClusterController(
            store, follower=True, lease_deadline_s=6.0,
            lease=serving.ControllerLease(store, holder="standby",
                                          deadline_s=3.0))
        req_q = serving.StoreQueue(store, "cluster/gate/req")
        bkeys = [f"bk-{i}" for i in range(2 * len(lens))]
        for i, key in enumerate(bkeys):
            req_q.push({"prompt": prompts[i % len(lens)].tolist(),
                        "max_new_tokens": 8, "key": key})
        end = time.time() + 300
        while sum(store.get(f"cluster/gate/ack/{k}") is not None
                  for k in bkeys) < 2:
            ctl_proc_alive_or_fail()
            if time.time() > end:
                raise RuntimeError(
                    "controller subprocess never acked a submission")
            time.sleep(0.02)
        ctl_proc.kill()
        killed_at = time.time()
        acked = {}
        for k in bkeys:
            raw = store.get(f"cluster/gate/ack/{k}")
            if raw is not None:
                acked[k] = raw.decode()

        inj = rs.install_faults("cluster.takeover@0")
        try:
            end = time.time() + 120
            while standby.follower and time.time() < end:
                standby.pump()
                time.sleep(0.02)
            took = time.time() - killed_at
            if standby.follower:
                raise RuntimeError(
                    "standby never took over the stale controller lease")
        finally:
            rs.clear_faults()
        if ("cluster.takeover", 0) not in inj.fired:
            failures.append(
                "the injected cluster.takeover fault never fired — the "
                "takeover-abort path went unexercised")
        if took > 8.0:
            failures.append(
                f"standby takeover took {took:.1f}s after the "
                "controller SIGKILL — the 3s lease staleness window "
                "was missed by more than the allowed slack")
        if standby.ctl_epoch < 3:
            failures.append(
                f"standby ctl epoch {standby.ctl_epoch} was not bumped "
                "past the killed controller's — zombie writes unfenced")

        # re-submit EVERY key through the standby: acked keys must
        # resolve to the SAME rid (journal dedupe across controllers);
        # unacked keys land in the crash window (journaled-but-unacked
        # dedupes too; never-submitted admits fresh) — either way one
        # rid per key, one jkey index entry, no duplicate output
        rids_b = {}
        for i, key in enumerate(bkeys):
            rids_b[key] = standby.submit(
                prompts[i % len(lens)], max_new_tokens=8,
                idempotency_key=key)
        for key, rid in acked.items():
            if rids_b[key] != rid:
                failures.append(
                    f"idempotency key {key} re-submitted through the "
                    f"standby got rid {rids_b[key]} but the killed "
                    f"controller acked {rid} — duplicate admission")
        if len(set(rids_b.values())) != len(bkeys):
            failures.append(
                f"{len(bkeys)} idempotency keys mapped onto "
                f"{len(set(rids_b.values()))} rids")
        pump_until(list(rids_b.values()), may_exit=(victim,), c=standby)
        for i, key in enumerate(bkeys):
            if standby.outputs[rids_b[key]]["tokens"] \
                    != refs[8][i % len(lens)]:
                failures.append(
                    f"phase-B request {key} diverged after the "
                    "controller failover — journal replay is not "
                    "token-preserving")
                break
        for key in bkeys:
            raw = store.get(f"cluster/jkey/{key}")
            if raw is None or raw.decode() != rids_b[key]:
                failures.append(
                    f"jkey index for {key} is {raw!r}, expected "
                    f"{rids_b[key]} — lost or duplicated journal index")
                break

        # ---- gateway smoke over the takeover winner: POST → SSE off
        # the fenced output record, a duplicate Idempotency-Key POST
        # replays the SAME rid, and a draining gateway sheds a typed
        # 503 + Retry-After.  The gateway's pump loop owns the
        # controller from here until close().
        gw = serving.ClusterGateway(standby, poll_s=0.005)
        gw_host, gw_port = gw.start()

        def gpost(body, headers=None):
            conn = http.client.HTTPConnection(gw_host, gw_port,
                                              timeout=240)
            conn.request("POST", "/v1/completions",
                         body=json.dumps(body),
                         headers={"Content-Type": "application/json",
                                  **(headers or {})})
            r = conn.getresponse()
            data = r.read().decode()
            hdrs = {k.lower(): v for k, v in r.getheaders()}
            conn.close()
            return r.status, data, hdrs

        st, data, _h = gpost(
            {"prompt": prompts[0].tolist(), "max_tokens": 8,
             "stream": True},
            {"Idempotency-Key": "gw-0"})
        sse_toks, gw_rid, fin = [], None, None
        for ln in data.splitlines():
            if not ln.startswith("data: ") or ln == "data: [DONE]":
                continue
            ev = json.loads(ln[len("data: "):])
            gw_rid = ev.get("id", gw_rid)
            for ch in ev.get("choices", []):
                if "token_id" in ch:
                    sse_toks.append(ch["token_id"])
                fin = ch.get("finish_reason") or fin
        if st != 200 or sse_toks != list(refs[8][0]) or fin is None \
                or "data: [DONE]" not in data:
            failures.append(
                f"gateway SSE stream answered {st} with tokens "
                f"{sse_toks} (finish {fin!r}) — expected the colocated "
                "reference stream")
        st2, data2, _h2 = gpost(
            {"prompt": prompts[0].tolist(), "max_tokens": 8},
            {"Idempotency-Key": "gw-0"})
        rep2 = json.loads(data2)
        if st2 != 200 or rep2.get("id") != gw_rid \
                or rep2["choices"][0]["token_ids"] != list(refs[8][0]):
            failures.append(
                f"duplicate Idempotency-Key POST answered {st2} id "
                f"{rep2.get('id')!r} — expected the SAME rid "
                f"({gw_rid!r}) and stream, never a second admission")
        gw.begin_drain(reason="gate")
        st3, data3, h3 = gpost(
            {"prompt": prompts[0].tolist(), "max_tokens": 8})
        err3 = json.loads(data3).get("error", {})
        if st3 != 503 or err3.get("type") != "draining" \
                or "retry-after" not in h3:
            failures.append(
                f"draining gateway answered {st3} {err3!r} "
                f"(Retry-After: {h3.get('retry-after')!r}) — expected "
                "the typed 503 with a retry hint")
        if not gw.wait_drained(timeout=60):
            failures.append("gateway never drained its live requests")
        gw.close()
        gw = None

        # drain the survivors and audit their exit reports — through
        # the takeover winner: its bumped ctl epoch must still command
        # the fleet
        for wid in procs:
            if wid != victim:
                standby.drain_worker(wid)
        for wid, p in procs.items():
            if wid == victim:
                continue
            out, err = p.communicate(timeout=120)
            if p.returncode != 0:
                failures.append(f"{wid} exited rc={p.returncode}: {err}")
                continue
            lines = [ln for ln in out.splitlines() if ln.strip()]
            reports[wid] = json.loads(lines[-1])
        for wid, rep in reports.items():
            if rep["compiles_after_warmup"] != 0:
                failures.append(
                    f"{wid}: {rep['compiles_after_warmup']} compile(s) "
                    "after warmup — membership churn retraced something")
            if rep["free_blocks"] != rep["num_blocks"]:
                failures.append(
                    f"{wid} holds {rep['num_blocks'] - rep['free_blocks']}"
                    " KV block(s) at drain")
            if rep["lease_losses"] != 0:
                failures.append(
                    f"{wid} lost its lease {rep['lease_losses']}x — the "
                    "injected transients were not absorbed")
            fired = {f[0] for f in rep["fired"]}
            if "cluster.lease" not in fired \
                    or "cluster.command" not in fired:
                failures.append(
                    f"{wid} fired only {sorted(fired)} — the cluster.* "
                    "fault plans went unexercised")
            # final mergeable snapshot: the exit report must carry the
            # worker's registry in wire form (every worker registers,
            # so cluster.registers is always present even for a worker
            # the router never handed work)
            wire = rep.get("telemetry")
            regs = (wire or {}).get("cluster.registers")
            if not wire or not isinstance(regs, dict) \
                    or not regs.get("value"):
                failures.append(
                    f"{wid} exit report has no mergeable telemetry "
                    f"snapshot (cluster.registers: {regs!r})")
        # post-mortem fleet accounting from the reports ALONE (no
        # store): merging the survivors' step sketches must recover a
        # fleet step distribution — p95 from merged counts, never from
        # averaging per-worker p95s
        fleet_step = obs_agg.HistogramSketch()
        for rep in reports.values():
            sw = (rep.get("telemetry") or {}).get("serve.step_ms")
            if isinstance(sw, dict) and sw.get("kind") == "sketch":
                fleet_step.merge(obs_agg.HistogramSketch.from_dict(sw))
        if reports and (not fleet_step.snapshot()["count"]
                        or not (fleet_step.percentile(95) or 0) > 0):
            failures.append(
                "survivor exit reports merged into an empty fleet "
                f"serve.step_ms sketch ({fleet_step.snapshot()!r})")
        flip_rep = reports.get(flipped)
        if flip_rep and flip_rep["role"] != "decode":
            failures.append(
                f"{flipped} exit report still says {flip_rep['role']!r}")
        if flip_rep and "cluster.register" not in \
                {f[0] for f in flip_rep["fired"]}:
            failures.append(
                f"{flipped} re-register never hit cluster.register")

        if not failures:
            print(f"serving-cluster: {n_prefill} prefill + {n_decode} "
                  f"decode worker processes survived a SIGKILL "
                  f"({victim}), a forced role flip ({flipped}) and "
                  f"injected cluster.* faults in every worker — all "
                  f"{len(w1) + len(w2)} outputs token-identical to the "
                  f"colocated run, 0 compiles after warmup, all blocks "
                  f"reclaimed, 0 lease losses on the survivors; "
                  f"/metrics scraped valid per-worker + fleet rollups "
                  f"mid-churn and every request stitched into one "
                  f"cross-host timeline; controller SIGKILL mid-churn "
                  f"→ standby controller takeover in {took:.1f}s "
                  f"(epoch {standby.ctl_epoch}), journal replayed, all "
                  f"{len(bkeys)} re-submitted idempotency keys "
                  f"answered with the same rid — zero duplicates; "
                  f"gateway smoke: SSE stream token-identical, "
                  f"duplicate Idempotency-Key POST replayed the same "
                  f"rid, drain answered the typed 503")
    finally:
        try:
            ctl.close_http()
        except Exception:  # noqa: BLE001 — ctl may not exist
            pass
        if gw is not None:
            try:
                gw.close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        if ctl_proc is not None and ctl_proc.poll() is None:
            ctl_proc.kill()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        store.close()

    if failures:
        print("serving-cluster gate FAILED (docs/SERVING.md "
              "\"Cluster serving\"):")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("serving-cluster gate OK")
    return 0


def gate_bench_regression(timeout_s: float = 120.0) -> int:
    """bench-regression gate: the perf-regression ledger's check mode
    (tools/bench_compare.py --check vs tools/bench_baseline.json) must
    PASS on the committed seed numbers and FAIL on an injected 2×
    CPU-plumbing slowdown — both enforced end-to-end through the CLI's
    exit code, so the gate catches a broken comparator as loudly as a
    broken bench.  When the driver provides a real fresh run
    (``PDTPU_BENCH_FRESH=<bench stdout JSON>``) that run is gated too.
    """
    import tempfile

    baseline_path = os.path.join(HERE, "bench_baseline.json")
    try:
        with open(baseline_path) as f:
            rows = json.load(f).get("rows") or {}
    except (OSError, ValueError) as e:
        print(f"bench-regression gate FAILED: unreadable baseline "
              f"{baseline_path}: {e}")
        return 1
    gated = {k: s for k, s in rows.items()
             if isinstance(s.get("value"), (int, float))
             and s.get("better") in ("higher", "lower")}
    if not gated:
        print("bench-regression gate FAILED: baseline carries no "
              "gateable rows (tools/bench_baseline.json)")
        return 1

    def _payload(vals: dict) -> dict:
        extra = {k: v for k, v in vals.items()
                 if k != "llama_train_mfu"}
        return {"metric": "llama_train_mfu",
                "value": vals.get("llama_train_mfu", 0.0),
                "unit": "mfu_fraction", "extra": extra}

    seed_vals = {k: s["value"] for k, s in gated.items()}
    slowed = dict(seed_vals)
    # inject a 2× slowdown into the first CPU-plumbing throughput row:
    # halved tok/s (or doubled ms) is exactly the regression the
    # acceptance contract names
    victim = sorted(gated)[0]
    if gated[victim]["better"] == "higher":
        slowed[victim] = seed_vals[victim] / 2.0
    else:
        slowed[victim] = seed_vals[victim] * 2.0

    compare = os.path.join(HERE, "bench_compare.py")
    with tempfile.TemporaryDirectory() as td:
        cases = [("seed", _payload(seed_vals), 0),
                 ("slowed-2x", _payload(slowed), 1)]
        for name, payload, want_rc in cases:
            p = os.path.join(td, f"{name}.json")
            with open(p, "w") as f:
                json.dump(payload, f)
            r = subprocess.run(
                [sys.executable, compare, "--check", "--fresh", p,
                 "--baseline", baseline_path],
                capture_output=True, text=True, timeout=timeout_s)
            ok = (r.returncode == 0) == (want_rc == 0)
            print(f"bench-regression: {name} run → rc={r.returncode} "
                  f"(want {'0' if want_rc == 0 else 'nonzero'})")
            if not ok:
                sys.stdout.write(r.stdout)
                sys.stderr.write(r.stderr)
                print(f"bench-regression gate FAILED: --check "
                      f"{'passed' if r.returncode == 0 else 'failed'} "
                      f"on the {name} numbers "
                      f"(injected victim row: {victim})")
                return 1

    fresh = os.environ.get("PDTPU_BENCH_FRESH")
    if fresh:
        r = subprocess.run(
            [sys.executable, compare, "--check", "--fresh", fresh,
             "--baseline", baseline_path],
            capture_output=True, text=True, timeout=timeout_s)
        sys.stdout.write(r.stdout)
        if r.returncode != 0:
            print(f"bench-regression gate FAILED: fresh run {fresh} "
                  "regressed vs tools/bench_baseline.json")
            return 1
    print("bench-regression gate OK")
    return 0


def gate_lint(timeout_s: float = 120.0) -> int:
    """Lint gate: pdtpu-lint runs clean over the whole tree with NO jax
    import (subprocess, bare env — the analyzer must work on a jax-less
    box; the CLI itself hard-fails if jax sneaks into sys.modules) and
    well inside the 30 s budget.  Stale suppressions / baseline entries
    print as warnings in the CLI output but do not fail — the baseline
    only shrinks (docs/ANALYSIS.md)."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "pdtpu_lint.py")],
        cwd=REPO, capture_output=True, text=True, timeout=timeout_s)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        print("lint gate FAILED — fix the finding or suppress it inline "
              "with a reason (# pdtpu-lint: disable=<rule> — <why>); "
              "see docs/ANALYSIS.md")
        return 1
    if "(jax imported: False)" not in r.stdout:
        print("lint gate FAILED — the analyzer imported jax (or did not "
              "report); it must stay importable on a jax-less box")
        return 1
    print("lint gate OK")
    return 0


GATES = {
    "api-compat": gate_api_compat,
    "lint": gate_lint,
    "memproof-lite": gate_memproof_lite,
    "telemetry-overhead": gate_telemetry_overhead,
    "chaos": gate_chaos,
    "serving-smoke": gate_serving_smoke,
    "chaos-serving": gate_chaos_serving,
    "serving-dist": gate_serving_dist,
    "serving-disagg": gate_serving_disagg,
    "serving-cluster": gate_serving_cluster,
    "bench-regression": gate_bench_regression,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(GATES))
    args = ap.parse_args()
    names = [args.only] if args.only else list(GATES)
    rc = 0
    for n in names:
        print(f"== gate: {n} ==")
        rc |= GATES[n]()
    return rc


if __name__ == "__main__":
    sys.exit(main())
