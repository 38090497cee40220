"""A serving cell: the program's own ``ServingServer`` -> ``FrontDoor`` ->
``Engine`` on a localhost port, with weights from the seed, under open-loop
traffic from ``benchmark/loadgen.py`` (a child process that never imports
jax) at the rate fixed in the cell's file.

The same traffic runs for ``lead_in_s`` seconds before the window, as part
of set-up, so that the window starts loaded.  Requests due in the window
are the sample; once the window has closed the run waits for them (up to
``grace_s``), reads the peak memory, frees the engine, and runs the plain
reference over a sample of the finished requests, drawn from the seed with
the longest in it: the widest gap by which a served token's logit lies
below the reference's best.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmark import harness, hlo, traffic, weights
from benchmark.reference import common as ref_common

HERE = os.path.dirname(os.path.abspath(__file__))
LOADGEN = os.path.join(os.path.dirname(HERE), "loadgen.py")


class Program:
    """The system under test: model, engine, server, as the program's
    documentation builds them; every engine argument the cell's file does
    not give stays at the program's default."""

    def __init__(self, ctx: dict):
        from paddle_tpu import nn, serving
        from paddle_tpu.nn.layer import raw_params

        cell, config = ctx["cell"], ctx["config"]
        builder = importlib.import_module(
            "benchmark.builders." + config["builder"])
        eng_args = dict(cell["engine"])
        with nn.meta_init():
            model = builder.build_model(
                config, cell["num_hidden_layers"],
                config["max_position_embeddings"], dtype="bfloat16")
        model.astype("bfloat16")
        self.shapes = {k: tuple(v.shape)
                       for k, v in raw_params(model).items()}
        for name, leaf in weights.make_weights(self.shapes,
                                               ctx["seed"]).items():
            model._assign_by_path(name, leaf)
        model.eval()
        self.model = model
        self.engine = serving.Engine(model, **eng_args)
        self.engine.warmup()
        self.server = serving.ServingServer(self.engine, port=0)
        self.host, self.port = self.server.start()

    def compiled(self):
        """(text, memory analysis) of the compiled step program at the
        shapes ``Engine.warmup`` compiled it for: a cache hit."""
        import jax.numpy as jnp

        eng = self.engine
        b, mb, c = eng.max_batch, eng.max_blocks_per_seq, eng.prefill_chunk
        zeros = jnp.zeros((b,), jnp.int32)
        compiled = eng._step_fn.lower(
            eng.params, eng.kv.caches, jnp.zeros((b, c), jnp.int32),
            jnp.full((b, mb), eng.kv.oob_block, jnp.int32), zeros, zeros,
            jnp.zeros((b,), jnp.float32), eng._key, zeros, zeros,
            eng._lora_stacks(), zeros, eng._device_aux([])).compile()
        return compiled.as_text(), compiled.memory_analysis()

    def stop(self) -> None:
        self.server.begin_drain()
        self.server.wait_drained(timeout=30)
        self.server.close()

    def free(self) -> None:
        self.server = self.engine = self.model = None
        harness.release()


class LoadGen:
    """The child process and its files (under TMPDIR, deleted after)."""

    def __init__(self, schedule: list, host: str, port: int, t0: float,
                 grace_s: float):
        self.dir = tempfile.mkdtemp(prefix="bench_load_")
        self.out = os.path.join(self.dir, "results.json")
        sched = os.path.join(self.dir, "schedule.json")
        with open(sched, "w") as f:
            json.dump(schedule, f)
        self.proc = subprocess.Popen(
            [sys.executable, LOADGEN, "--schedule", sched, "--host", host,
             "--port", str(port), "--t0", repr(t0), "--grace-s",
             str(grace_s), "--out", self.out],
            stdout=subprocess.DEVNULL)

    def results(self, timeout: float) -> list:
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            raise
        try:
            with open(self.out) as f:
                return json.load(f)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def percentile(values, p: float) -> float:
    """The smallest value with at least ``p`` % of the sample at or below
    it (nearest rank)."""
    data = sorted(values)
    rank = max(1, int(np.ceil(p / 100.0 * len(data))))
    return float(data[min(rank, len(data)) - 1])


def make_schedule(ctx: dict, rate_rps: float = None, seconds: float = None,
                  seed: int = None):
    """Requests due over ``[-lead_in_s, seconds)``, times relative to the
    window's start.  The lead-in and the window are drawn apart, so the
    window holds ``round(rate * seconds)`` requests for every seed."""
    cell, config = ctx["cell"], ctx["config"]
    t = cell["traffic"]
    seconds = ctx["seconds"] if seconds is None else seconds
    seed = ctx["seed"] if seed is None else seed
    lead = float(t["lead_in_s"])
    window = traffic.open_loop_schedule(t, config["vocab_size"], seed,
                                        seconds, rate_rps)
    warm = traffic.open_loop_schedule(t, config["vocab_size"], seed + 1,
                                      lead, rate_rps)
    out = []
    for r in warm:
        out.append(dict(r, index=len(out), due_s=r["due_s"] - lead,
                        sample=False))
    for r in window:
        out.append(dict(r, index=len(out), sample=True))
    return out


def drive(ctx: dict, prog: Program, schedule: list, seconds: float,
          tracer=None, registry=None, names=()) -> dict:
    """Lead-in, window and grace.  Returns the child's records and what the
    main thread read meanwhile: of the registry, the entries ``names`` at
    the window's start and end and around the traced seconds."""
    cell = ctx["cell"]
    t = cell["traffic"]
    lead, grace = float(t["lead_in_s"]), float(t["grace_s"])
    t0 = time.monotonic() + 1.0 + lead          # the window's start
    gen = LoadGen(schedule, prog.host, prog.port, t0, grace)
    info = {"t0": t0, "polls": []}
    try:
        time.sleep(max(0.0, t0 - time.monotonic()))
        info["setup_s"] = time.perf_counter() - ctx["t_start"]
        with harness.sentinel() as sent:
            c0 = sent.compiles()
            snap0 = _registry_snapshot(registry, names)
            if tracer is not None:
                time.sleep(cell["trace"]["after_s"])
                tr0 = _registry_snapshot(registry, names)
                tracer.start()
                tw0 = time.monotonic() - t0
                t_end = time.monotonic() + cell["trace"]["seconds"]
                while time.monotonic() < t_end:
                    info["polls"].append(prog.engine.kv_blocks_used)
                    time.sleep(0.05)
                tracer.stop()
                info["trace_window"] = (tw0, time.monotonic() - t0)
                info["trace_counters"] = _registry_delta(
                    tr0, _registry_snapshot(registry, names))
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            info["compiles"] = sent.compiles() - c0
            info["window_counters"] = _registry_delta(
                snap0, _registry_snapshot(registry, names))
        info["device"] = ctx["device_report"]()
        info["records"] = gen.results(timeout=grace + 120.0)
    finally:
        gen.kill()
    return info


def _registry_snapshot(registry, names=()):
    """name -> ``{"sum", "count"}`` of a histogram, ``{"value"}`` of a
    counter, for the entries of ``names`` that exist by now."""
    if registry is None:
        return None
    out = {}
    for name in names:
        entry = registry.get(name)
        if hasattr(entry, "sum") and hasattr(entry, "count"):
            out[name] = {"sum": entry.sum, "count": entry.count}
        elif isinstance(getattr(entry, "value", None), (int, float)):
            out[name] = {"value": entry.value}
    return out


def _registry_delta(a, b):
    """What was added between two snapshots, entry by entry; an entry the
    program made in between started from nought."""
    if a is None or b is None:
        return {}
    return {name: {k: v - a.get(name, {}).get(k, 0) for k, v in now.items()}
            for name, now in b.items()}


def client_metrics(records: list, schedule: list, seconds: float,
                   miss_ms: float) -> dict:
    """The end-to-end metrics from the client's side.  A request that
    failed or never answered counts as a miss: its time to first token is
    ``miss_ms``."""
    sample = {r["index"] for r in schedule if r["sample"]}
    want = {r["index"]: r["max_tokens"] for r in schedule}
    ttft, gaps, late, failed = [], [], [], 0
    for rec in records:
        if rec["index"] not in sample:
            continue
        ok = rec["done"] and not rec["error"] \
            and len(rec["tokens"]) == want[rec["index"]]
        if not ok:
            failed += 1
        if rec["token_s"]:
            ttft.append(1e3 * (rec["token_s"][0] - rec["due_s"]))
        else:
            ttft.append(miss_ms)
        if rec["sent_s"] is not None:
            late.append(1e3 * (rec["sent_s"] - rec["due_s"]))
        ts = rec["token_s"]
        gaps.extend(1e3 * (b - a) for a, b in zip(ts, ts[1:]))
    in_window = sum(1 for rec in records for x in rec["token_s"]
                    if 0.0 <= x < seconds)
    return {"attempted": len(sample), "failed": failed,
            "ttft_p95_ms": percentile(ttft, 95) if ttft else miss_ms,
            "ttft_p50_ms": percentile(ttft, 50) if ttft else miss_ms,
            "itl_p95_ms": percentile(gaps, 95) if gaps else miss_ms,
            "itl_p50_ms": percentile(gaps, 50) if gaps else miss_ms,
            "serve_tokens_per_s": in_window / seconds,
            "loadgen_late_p95_ms": percentile(late, 95) if late else None,
            "n_gaps": len(gaps)}


def pick_sample(ctx: dict, records: list, schedule: list,
                seed: int = None) -> list:
    """[(prompt, served)] of ``check_requests`` finished requests of the
    window: the longest, and the rest drawn from the seed."""
    by_index = {r["index"]: r for r in schedule}
    done = [rec for rec in records if by_index[rec["index"]]["sample"]
            and rec["done"] and rec["tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: r["index"])
    size = lambda r: len(by_index[r["index"]]["prompt"]) + len(r["tokens"])
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    rng = traffic.rng_of(ctx["seed"] if seed is None else seed, 3)
    k = min(len(rest), int(ctx["cell"]["check_requests"]) - 1)
    picked = [longest] + [rest[i] for i in
                          rng.choice(len(rest), size=k, replace=False)]
    return [(by_index[r["index"]]["prompt"], r["tokens"]) for r in picked]


def reference_gaps(ctx: dict, shapes: dict, sequences: list,
                   control: str = None) -> dict:
    cell, config = ctx["cell"], ctx["config"]
    family = importlib.import_module(
        "benchmark.reference." + config["reference"])
    layers = cell["num_hidden_layers"]
    want = family.param_shapes(config, layers)
    if want != {k: tuple(v) for k, v in shapes.items()}:
        raise AssertionError(
            "the reference and the program differ in leaves or shapes: "
            f"{sorted(set(want.items()) ^ set(shapes.items()))[:6]}")
    t = cell["traffic"]
    max_out = int(t["output_tokens"]["max"])
    pad_to = -(-(int(t["prompt_tokens"]["max"]) + max_out) // 128) * 128
    return ref_common.served_token_gaps(
        family, config, layers,
        lambda names: weights.make_weights(shapes, ctx["seed"], names=names),
        sequences, pad_to, max_out, control=control)


def run(ctx: dict) -> dict:
    cell = ctx["cell"]
    registry, names = None, ()
    if ctx["trace"]:
        # the registry exists only under observability.enable(): the
        # traced run enables it, the untraced run does not
        from paddle_tpu import observability as obs

        registry = obs.enable(crash_hooks=False).registry
        names = harness.registry_names(ctx)
    prog = Program(ctx)
    schedule = make_schedule(ctx)
    tracer = harness.Tracer() if ctx["trace"] else None
    try:
        info = drive(ctx, prog, schedule, ctx["seconds"], tracer, registry,
                     names)
    finally:
        prog.stop()
        if ctx["trace"]:
            from paddle_tpu import observability as obs

            obs.disable()
    m = client_metrics(info["records"], schedule, ctx["seconds"],
                       1e3 * float(cell["traffic"]["grace_s"]))
    harness.log("window:", {k: v for k, v in m.items()},
                "compiles:", info["compiles"])
    errors = sorted({str(r["error"])[:120] for r in info["records"]
                     if r["error"]})
    if errors:
        harness.log("request errors:", errors[:5])
    sequences = pick_sample(ctx, info["records"], schedule)
    shapes = prog.shapes
    engine_facts = {"max_batch": prog.engine.max_batch,
                    "prefill_chunk": prog.engine.prefill_chunk,
                    "page_size": prog.engine.page_size}
    scopes = {}
    if ctx["trace"]:
        text, mem = prog.compiled()
        scopes = hlo.instruction_scopes(text)
        harness.log("pallas kernels in the compiled step:",
                    hlo.pallas_kernels(text), "argument bytes",
                    mem.argument_size_in_bytes, "temporary bytes",
                    mem.temp_size_in_bytes, "instructions with a scope "
                    "path:", len(scopes))
        if not scopes:
            harness.log("the compiled step's text names no op_name (a "
                        "compile cache keyed without op metadata can "
                        "return one): the readers of regions read nothing")
        del text
    prog.free()

    numbers = {"requests_failed": (float(m["failed"]),
                                   f"of {m['attempted']}")}
    if sequences:
        gaps = reference_gaps(ctx, shapes, sequences)
        harness.log("reference:", {"gaps": gaps["gaps"],
                                   "served": [len(s[1]) for s in sequences]})
        numbers["served_logit_gap"] = (gaps["widest"], gaps["at"])
    else:
        numbers["served_logit_gap"] = (float("nan"), "no request finished")
    ok, checks = harness.judge(numbers, cell["limits"])

    end_to_end = {k: m[k] for k in ("ttft_p95_ms", "itl_p95_ms",
                                    "serve_tokens_per_s")}
    end_to_end["setup_s"] = info["setup_s"]
    rctx = None
    if ctx["trace"]:
        a, b = info["trace_window"]
        recs = info["records"]
        emitted = sum(1 for r in recs for x in r["token_s"] if a <= x < b)
        mid = 0.5 * (a + b)
        in_flight = sum(1 for r in recs if r["sent_s"] is not None
                        and r["sent_s"] <= mid and r["token_s"]
                        and r["token_s"][-1] >= mid)
        rctx = {"trace": tracer.read(), "scopes": scopes, "cell": cell,
                "config": ctx["config"], "layers": cell["num_hidden_layers"],
                "peaks": ctx["peaks"], "client": m,
                "engine": engine_facts,
                "counters": {"compiles_in_window": info["compiles"],
                             "window": info["window_counters"],
                             "traced": info.get("trace_counters", {}),
                             "traced_emitted": emitted,
                             "traced_in_flight": in_flight,
                             "kv_blocks_polls": info["polls"]}}
    return harness.result_line(
        ctx, correct=ok, attempted=m["attempted"], failed=m["failed"],
        end_to_end=end_to_end, checks=checks, device=info["device"],
        rctx=rctx)


def limit_readings(ctx: dict, seeds: list, control_seeds: set):
    """Rows for ``benchmark/limits.py``: one set-up (weights from the first
    seed), then for every seed a short window of the cell's own traffic,
    long enough to finish the mix's longest requests, and the reference
    over as many requests as a run compares; on a control seed also the
    gap of the token that float8 puts first at the same positions.  Both
    go through the run's own ``judge`` with the cell's own limits:
    ``correct`` has to be true of the program and false of the control.
    The engine stays on the device meanwhile: the reference goes layer by
    layer and fits beside it."""
    cell = ctx["cell"]
    ctx = dict(ctx, seed=seeds[0])
    seconds = float(cell["limits_window_s"])
    prog = Program(ctx)
    try:
        for seed in seeds:
            schedule = make_schedule(ctx, seconds=seconds, seed=seed)
            info = drive(ctx, prog, schedule, seconds)
            m = client_metrics(info["records"], schedule, seconds,
                               1e3 * float(cell["traffic"]["grace_s"]))
            sequences = pick_sample(ctx, info["records"], schedule, seed)
            gaps = reference_gaps(
                ctx, prog.shapes, sequences,
                control="fp8" if seed in control_seeds else None)
            for side, widest in (("program", gaps["widest"]),
                                 ("control_fp8", gaps["control_widest"])):
                if widest is None:
                    continue
                numbers = {"served_logit_gap": (widest, gaps["at"]),
                           "requests_failed": (float(m["failed"]),
                                               f"of {m['attempted']}")}
                ok, _ = harness.judge(numbers, cell["limits"])
                yield {"seed": seed, "weights_seed": seeds[0], "side": side,
                       "correct": ok,
                       "numbers": {k: v[0] for k, v in numbers.items()},
                       "gaps": gaps["gaps"], "at": gaps["at"],
                       "served": [len(s[1]) for s in sequences],
                       "client": m}
    finally:
        prog.stop()
