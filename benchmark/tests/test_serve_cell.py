"""The serving runner's phases at tiny size end to end through HTTP and
the load generator's child process, and the fault a serving cell can have:
a token altered where it is produced."""

from conftest import tiny_ctx

from benchmark import harness
from benchmark import run as brun
from benchmark import trace_reduce
from benchmark.runners import serve
from benchmark.work import regions

CELL = "mistral-7b.serve-chat"


def test_run_is_correct():
    res = serve.run(tiny_ctx(CELL, 2**31 + 41, seconds=3.0))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_a_traced_run_reads_what_the_program_writes(monkeypatch):
    """Under the profiler the loop's leaf phases are read on one thread
    and no second of a gap is credited twice; the compiled step's text
    gives the model's regions a leaf each; the registry entries the cell's
    metric files name reach their readers."""
    seen = {}
    real = harness.result_line

    def keep(ctx, **kw):
        seen.update(kw["rctx"])
        return real(ctx, **kw)

    monkeypatch.setattr(harness, "result_line", keep)
    ctx = tiny_ctx(CELL, 2**31 + 44, seconds=3.0,
                   trace={"after_s": 0.3, "seconds": 1.5})
    bench = brun.load_json("..", "BENCHMARK.json")
    ctx.update(trace=True, per_layer={
        m["name"]: m["unit"]
        for m in brun.cell_metrics(bench, CELL, "per_layer")})
    res = serve.run(ctx)
    assert res["correct"] is True, res["checks"]

    host = seen["trace"]["host"]
    leaves = {"pdtpu.serve.step." + p for p in
              ("admit", "plan", "dispatch", "sync", "emit", "account")}
    threads = trace_reduce.by_thread(host)
    loops = [t for t, spans in threads.items()
             if leaves & {e[0] for e in spans}]
    assert len(loops) == 1
    assert leaves <= {e[0] for e in threads[loops[0]]}
    # one gap over all the traced seconds: its owners sum to its length
    t0, t1 = trace_reduce.span_of(host)
    thread, rows = trace_reduce.gap_owners([(t0, t1)], host)
    assert thread == loops[0]
    (_, row), = rows
    assert abs(sum(row.values()) - (t1 - t0)) < 1e-9
    assert "pdtpu.serve.stream.write" not in row
    # the parents own what their leaves leave (credited by overlap they
    # would own as much as the leaves together)
    parents = row.get("pdtpu.serve.step", 0) \
        + row.get("pdtpu.serve.step.finish", 0)
    assert parents < 0.25 * sum(v for k, v in row.items() if k in leaves)

    found = {regions.region_of(path) for path in seen["scopes"].values()}
    assert {"attn_proj", "attn_core", "mlp"} <= found

    window = seen["counters"]["window"]
    assert set(window) == {"serve.ragged_occupancy", "serve.queue_ms",
                           "serve.prefill_steps", "serve.mlp_live_tiles"}
    assert all(e["count"] > 0 for e in window.values())
    for name in ("ragged_occupancy_pct", "queue_wait_mean_ms",
                 "prefill_steps_mean", "mlp_live_tiles_mean"):
        assert res["metrics"][name]["value"] > 0
    # no device plane on the CPU: the readers of the trace read nothing
    assert "host_ms_per_step.dispatch" not in res["metrics"]


def test_fault_token_altered(monkeypatch):
    """Every emitted token id shifted by one where the engine emits it."""
    from paddle_tpu import serving

    real = serving.Engine.step_finish

    def altered(self, pending):
        return [ev._replace(token_id=(ev.token_id + 1) % 256)
                for ev in real(self, pending)]

    monkeypatch.setattr(serving.Engine, "step_finish", altered)
    res = serve.run(tiny_ctx(CELL, 2**31 + 42, seconds=3.0))
    assert res["correct"] is False
    c = res["checks"]["served_logit_gap"]
    assert c["value"] > c["limit"]


def test_failed_requests_are_not_correct(monkeypatch):
    """A request the server sheds is a failure and a miss."""
    ctx = tiny_ctx(CELL, 2**31 + 43, seconds=3.0)
    ctx["cell"]["traffic"]["output_tokens"] = {
        "median": 200, "sigma": 0.1, "min": 150, "max": 250}   # > max_seq_len
    res = serve.run(ctx)
    assert res["failed"] > 0 and res["correct"] is False


def test_control_is_not_correct():
    """At the positions the program served, the token that float8 puts
    first lies further below the reference's best than the cell's limit
    allows: through the run's own judge ``correct`` comes out false of the
    control and true of the program, on three seeds."""
    seeds = [2**31 + 51, 2**31 + 52, 2**31 + 53]
    ctx = tiny_ctx(CELL, seeds[0], seconds=2.0, limits_window_s=2.0)
    rows = list(serve.limit_readings(ctx, seeds, set(seeds)))
    prog = [r["correct"] for r in rows if r["side"] == "program"]
    ctl = [r["correct"] for r in rows if r["side"] == "control_fp8"]
    assert prog == [True] * 3 and ctl == [False] * 3, rows
