"""A training cell: ``amp.decorate(O2, bf16)`` + ``AdamW`` with
global-norm clipping + ``jit.TrainStep``, a fresh batch of seeded token
ids each step made on the host and put on the device as a loader would,
the loss fetched every tenth step and at the end.

Set-up builds one object, the compiled step with its state, drives it
from the seed through its first three steps (the readings that
``correct`` compares, taken through the window's own call and feed) and
hands that same object to the window.  Once the window has closed and
the peak memory is read, the program's state is freed and the plain
reference follows the same three steps in float32.
"""

from __future__ import annotations

import collections
import importlib
import statistics
import time

import jax
import numpy as np

from benchmark import harness, hlo, traffic, weights
from benchmark.reference import common as ref_common

PROBE_STEPS = 3
IN_FLIGHT = 2          # steps the host may run ahead of the device


class Program:
    """The system under test: the program's own model, optimizer and
    compiled step, built as its documentation builds them."""

    def __init__(self, ctx: dict):
        from paddle_tpu import amp, nn, optimizer
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.nn.layer import raw_params

        cell, config = ctx["cell"], ctx["config"]
        hp = cell["optimizer"]
        builder = importlib.import_module(
            "benchmark.builders." + config["builder"])
        with nn.meta_init():        # shapes only: the weights come below
            model = builder.build_model(config, cell["num_hidden_layers"],
                                        config["max_position_embeddings"])
        opt = optimizer.AdamW(
            learning_rate=hp["learning_rate"], beta1=hp["beta1"],
            beta2=hp["beta2"], epsilon=hp["epsilon"],
            weight_decay=hp["weight_decay"],
            grad_clip=nn.ClipGradByGlobalNorm(hp["clip_norm"]),
            parameters=model.parameters())
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
        self.shapes = {k: tuple(v.shape) for k, v in raw_params(model).items()}
        for name, leaf in weights.make_weights(self.shapes,
                                               ctx["seed"]).items():
            model._assign_by_path(name, leaf)
        self.model = model
        self.step = TrainStep(model, builder.loss_fn(), opt)
        self.state = self.step.init_state(seed=0)
        self.beta1 = hp["beta1"]

    def put(self, batch: dict) -> dict:
        return {k: jax.device_put(v) for k, v in batch.items()}

    def advance(self, batch: dict) -> dict:
        """One call of the timed path; returns the step's metrics."""
        self.state, metrics = self.step(self.state, batch)
        return metrics

    def grad1_norms(self) -> dict:
        """Leaf norms of the first gradient as the optimizer got it, from
        its state after one step: moment1 = (1 - beta1) x gradient."""
        m = self.state["opt"]["moment1"]
        norms = jax.jit(ref_common.leaf_norms)(m)
        return {k: float(v) / (1.0 - self.beta1) for k, v in norms.items()}

    def change_norms(self, seed: int) -> dict:
        """Leaf norms of float32 master minus the start, the start made
        again from the seed; and the one-dimensional leaves' change whole."""
        start = weights.make_weights(self.shapes, seed)
        master = self.state["opt"]["master"]
        params = self.state["params"]
        now = {k: (master[k] if master.get(k) is not None else params[k])
               for k in params}
        norms, small = jax.jit(ref_common.change_readings)(now, start)
        return ({k: float(v) for k, v in norms.items()},
                {k: np.asarray(v) for k, v in small.items()})

    def compiled(self, batch: dict):
        """(text, memory analysis) of the compiled step: a cache hit."""
        c = self.step.lower(self.state, batch).compile()
        return c.as_text(), c.memory_analysis()

    def free(self) -> None:
        self.state = self.step = self.model = None
        harness.release()


def reference_readings(ctx: dict, shapes: dict, batches: list,
                       mode: str = "f32", fault: str = None) -> dict:
    cell, config = ctx["cell"], ctx["config"]
    family = importlib.import_module(
        "benchmark.reference." + config["reference"])
    want = family.param_shapes(config, cell["num_hidden_layers"])
    if want != {k: tuple(v) for k, v in shapes.items()}:
        raise AssertionError(
            "the reference and the program differ in leaves or shapes: "
            f"{sorted(set(want.items()) ^ set(shapes.items()))[:6]}")
    return ref_common.adamw_reference(
        family, config, cell["num_hidden_layers"],
        lambda: weights.make_weights(shapes, ctx["seed"]),
        batches, cell["optimizer"], mode=mode, fault=fault)


def probe(ctx: dict, prog: Program, feed) -> tuple:
    """The first steps, through the window's own call and feed.  Returns
    (the program's readings, the batches as the host made them)."""
    batches, losses, g1 = [], [], None
    for i in range(PROBE_STEPS):
        with harness.span("next_batch"):
            host = feed.next()
            batch = prog.put(host)
        batches.append(host)
        metrics = prog.advance(batch)
        with harness.span("fetch_loss"):
            losses.append(float(metrics["loss"]))
        if i == 0:
            g1 = prog.grad1_norms()
    change, change_small = prog.change_norms(ctx["seed"])
    got = {"losses": losses, "grad1_norms": g1, "change_norms": change,
           "change_small": change_small}
    return got, batches


def window(ctx: dict, prog: Program, feed, tracer) -> dict:
    """Measure for ``seconds``: dispatch steps with at most IN_FLIGHT
    ahead of the device, fetch the loss every ``fetch_loss_every`` steps
    and at the end.  Tokens of every step completed, over the time from
    the first dispatch to the last ``block_until_ready``."""
    every = int(ctx["cell"]["traffic"]["fetch_loss_every"])
    t_cfg = ctx["cell"]["trace"]
    trace_at, trace_steps = t_cfg["after_s"], t_cfg["steps"]
    pending = collections.deque()
    steps, last_loss, tracing, traced = 0, None, False, 0
    laps = []        # per iteration: its start, then each phase's end
    clock = time.perf_counter
    t0 = clock()
    while True:
        now = clock() - t0
        if now >= ctx["seconds"]:
            break
        if tracer is not None and not tracing and traced == 0 \
                and now >= trace_at:
            jax.block_until_ready(prog.state["step"])
            tracer.start()
            tracing = True
        lap = [clock()]
        with harness.span("next_batch"):
            batch = prog.put(feed.next())
        lap.append(clock())
        with harness.span("step"):
            metrics = prog.advance(batch)
        lap.append(clock())
        steps += 1
        pending.append(metrics["loss"])
        if len(pending) > IN_FLIGHT:
            with harness.span("wait_step"):
                jax.block_until_ready(pending.popleft())
        lap.append(clock())
        if steps % every == 0:
            with harness.span("fetch_loss"):
                last_loss = float(metrics["loss"])
            pending.clear()
        lap.append(clock())
        laps.append(lap)
        if tracing:
            traced += 1
            if traced >= trace_steps:
                jax.block_until_ready(metrics["loss"])
                tracer.stop()
                tracing = False
    with harness.span("fetch_loss"):
        if steps:
            last_loss = float(metrics["loss"])
    jax.block_until_ready(prog.state["step"])
    seconds = clock() - t0
    if tracing:
        tracer.stop()
    return {"steps": steps, "seconds": seconds, "last_loss": last_loss,
            "traced_steps": traced,
            "slowest_iterations": slowest(laps, every)}


def slowest(laps: list, every: int, n: int = 3) -> list:
    """The ``n`` iterations of the window's loop that ran longest over the
    median of their kind on the host's clock (every ``every``-th fetches
    the loss and so waits for the steps in flight), each with its phases
    in milliseconds, so that a stall names the phase it sat in: an
    untraced run keeps nothing else."""
    names = ("next_batch", "dispatch", "wait_step", "fetch_loss")
    total = [lap[-1] - lap[0] for lap in laps]
    fetches = [(i + 1) % every == 0 for i in range(len(laps))]
    median = {k: statistics.median([t for t, f in zip(total, fetches)
                                    if f == k] or [0.0])
              for k in (False, True)}
    over = [t - median[f] for t, f in zip(total, fetches)]
    order = sorted(range(len(laps)), key=lambda i: -over[i])
    return [dict({"step": i + 1, "over_median_ms": round(1e3 * over[i], 1)},
                 **{k: round(1e3 * (b - a), 1) for k, a, b in
                    zip(names, laps[i], laps[i][1:])})
            for i in order[:n]]


def run(ctx: dict) -> dict:
    cell, config = ctx["cell"], ctx["config"]
    feed = traffic.TokenBatches(cell["traffic"], config["vocab_size"],
                                ctx["seed"])
    prog = Program(ctx)
    got, batches = probe(ctx, prog, feed)
    harness.log("program:", {"losses": got["losses"]})
    scopes = {}
    if ctx["trace"]:
        text, mem = prog.compiled(prog.put(batches[0]))
        scopes = hlo.instruction_scopes(text)
        harness.log("pallas kernels in the compiled step:",
                    hlo.pallas_kernels(text), "argument bytes",
                    mem.argument_size_in_bytes, "temporary bytes",
                    mem.temp_size_in_bytes)
        del text
    tracer = harness.Tracer() if ctx["trace"] else None
    with harness.sentinel() as sent:
        compiles0 = sent.compiles()
        setup_s = time.perf_counter() - ctx["t_start"]
        win = window(ctx, prog, feed, tracer)
        compiles = sent.compiles() - compiles0
    device = ctx["device_report"]()
    tokens = win["steps"] * feed.tokens_per_batch
    end_to_end = {"train_tokens_per_s": tokens / win["seconds"],
                  "setup_s": setup_s}
    harness.log("window:", win, "compiles:", compiles)
    n_params = int(sum(np.prod(s) for s in prog.shapes.values()))
    shapes = prog.shapes
    prog.free()

    ref = reference_readings(ctx, shapes, batches)
    harness.log("reference:", {"losses": ref["losses"]})
    numbers = ref_common.training_numbers(got, ref)
    ok, checks = harness.judge(numbers, cell["limits"])

    rctx = None
    if ctx["trace"]:
        rctx = {"trace": tracer.read(), "scopes": scopes, "cell": cell,
                "config": config, "layers": cell["num_hidden_layers"],
                "peaks": ctx["peaks"],
                "counters": {"compiles_in_window": compiles,
                             "n_params": n_params,
                             "traced_steps": win["traced_steps"]}}
    return harness.result_line(
        ctx, correct=ok, attempted=win["steps"], failed=0,
        end_to_end=end_to_end, checks=checks, device=device, rctx=rctx)


def limit_readings(ctx: dict, seeds: list, control_seeds: set):
    """Rows for ``benchmark/limits.py``: the program against the reference
    and, on a control seed, the float8 control and each planted fault
    against it.  Every row goes through the run's own ``judge`` with the
    cell's own limits: ``correct`` has to be true of the program and
    false of the control and of every fault."""
    cell, config = ctx["cell"], ctx["config"]
    for seed in seeds:
        ctx = dict(ctx, seed=seed)
        feed = traffic.TokenBatches(cell["traffic"], config["vocab_size"],
                                    seed)
        prog = Program(ctx)
        got, batches = probe(ctx, prog, feed)
        shapes = prog.shapes
        prog.free()
        ref = reference_readings(ctx, shapes, batches)

        def row(side, readings):
            nums = ref_common.training_numbers(readings, ref)
            ok, checks = harness.judge(nums, cell["limits"])
            return {"seed": seed, "side": side, "correct": ok,
                    "fails": [k for k, c in checks.items()
                              if not c["value"] <= c["limit"]],
                    "losses": readings["losses"],
                    "numbers": {k: v[0] for k, v in nums.items()},
                    "at": {k: str(v[1]) for k, v in nums.items()}}

        yield row("program", got)
        if seed in control_seeds:
            yield row("control_fp8", reference_readings(
                ctx, shapes, batches, mode="fp8"))
            for fault in ("half_batch", "state_unchanged"):
                yield row("fault_" + fault, reference_readings(
                    ctx, shapes, batches, fault=fault))
