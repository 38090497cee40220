"""The serving runner's phases at tiny size end to end through HTTP and
the load generator's child process, and the fault a serving cell can have:
a token altered where it is produced."""

from conftest import tiny_ctx

from benchmark.runners import serve

CELL = "mistral-7b.serve-chat"


def test_run_is_correct():
    res = serve.run(tiny_ctx(CELL, 2**31 + 41, seconds=3.0))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


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
