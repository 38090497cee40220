"""Device time by region, and the work two of the regions need.

A region is a ``jax.named_scope`` that the program opens while it traces
its step (the program's ``observability/regions.py``; the names below are
the benchmark's own copy).  The device trace names an event by its HLO
instruction and the compiled text gives every instruction its
``op_name``, the scope path; so each leaf event of the traced whole steps
is given to the INNERMOST region on its path (``.../optimizer/clip/mul``
is ``clip``, ``.../mlp/jit(fused_swiglu_mlp)/pallas_call`` is ``mlp``).
A region is a whole component of the path, the wrappers of autodiff and
vmap apart (``transpose(jvp(forward))``); an XLA fusion carries its root's
path.  A
program without such scopes, as the parent of the PR that added this file,
leaves the regions empty, and the readers return nothing.
"""

from __future__ import annotations

import bisect
import re
import statistics

from benchmark import trace_reduce

REGIONS = ("embed", "norm", "attn_proj", "attn_core", "mlp", "lm_head_loss",
           "clip", "optimizer")
UNSCOPED = "unscoped"
# the transforms that wrap a name of the path (jax's name stack has these
# three); ``jit(clip)`` is a call of ``jnp.clip``, not the region ``clip``
_NUMBER = re.compile(r"[.\d]+$")      # fusion.81 -> fusion: an instruction's kind
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def region_of(scope_path: str):
    """The innermost region of an ``op_name``, or None."""
    found = None
    for part in (scope_path or "").split("/"):
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        if part in REGIONS:
            found = part
    return found


def _step_leaves(ctx: dict):
    """(runs of the step program, leaf events from the first run's start
    to the last run's end) on the first device; reckoned once a run."""
    if "step_leaves" not in ctx:
        runs = trace_reduce.module_runs(ctx["trace"],
                                        ctx["cell"]["step_program"])
        leaves = []
        if runs:
            t0, t1 = trace_reduce.span_of(runs)
            leaves = trace_reduce.leaf_ops(trace_reduce.clip_events(
                ctx["trace"]["devices"][0]["ops"], t0, t1))
        ctx["step_leaves"] = (runs, leaves)
    return ctx["step_leaves"]


def region_table(ctx: dict):
    """{"regions": {name: [seconds, events]}, "steps": runs of the step
    program, "seconds": all leaf device time} over the first run's start
    to the last run's end on the first device; ``unscoped`` holds the leaf
    events whose path names no region.  None where no step ran.  Reckoned
    once a run and kept in ``ctx``; the whole table goes to the run's log,
    and with it each region's seconds by kind of instruction (``fusion``,
    ``reshape``, a kernel's name), which is what tells a kernel's own time
    from the copies around it."""
    if "region_table" in ctx:
        return ctx["region_table"]
    table = None
    runs, leaves = _step_leaves(ctx)
    if runs:
        scopes = ctx.get("scopes") or {}
        rows = {name: [0.0, 0] for name in REGIONS + (UNSCOPED,)}
        kinds = {name: {} for name in rows}
        for name, _, dur in leaves:
            key = region_of(scopes.get(name)) or UNSCOPED
            rows[key][0] += dur
            rows[key][1] += 1
            kind = _NUMBER.sub("", name)
            kinds[key][kind] = kinds[key].get(kind, 0.0) + dur
        total = sum(r[0] for r in rows.values())
        if total > 0:
            table = {"regions": rows, "steps": len(runs), "seconds": total}
            ctx["notes"].append(
                f"device seconds by region in {len(runs)} steps (of "
                f"{total:.6f} s in leaf operations): " + ", ".join(
                    f"{k} {v[0]:.6f} s / {v[1]} events"
                    for k, v in rows.items()))
            ctx["notes"].append(
                "the same by kind of instruction, the four largest of a "
                "region: " + "; ".join(
                    k + ": " + ", ".join(
                        f"{n} {t:.6f}" for n, t in sorted(
                            v.items(), key=lambda kv: -kv[1])[:4])
                    for k, v in kinds.items() if v))
    ctx["region_table"] = table
    return table


def region_seconds(ctx: dict, name: str):
    """(seconds, events, steps) of one region, or None where it is empty."""
    table = region_table(ctx)
    if table is None or table["regions"][name][0] <= 0:
        return None
    seconds, events = table["regions"][name]
    return seconds, events, table["steps"]


def step_median_ms(ctx: dict, name: str):
    """Median over the runs of the step program of one region's leaf
    device milliseconds inside a run, or None where the scopes name no
    region or this one is empty in every run.  A serving step's work
    differs from step to step (a prompt's fan-out beside decode rows), so
    the median is the usual step's; the table's sums are all steps'."""
    if "step_medians" not in ctx:
        medians = None
        runs, leaves = _step_leaves(ctx)
        table = region_table(ctx)
        if table is not None and any(table["regions"][r][0] > 0
                                     for r in REGIONS):
            scopes = ctx.get("scopes") or {}
            starts = [r[1] for r in runs]
            per_run = [dict.fromkeys(REGIONS + (UNSCOPED,), 0.0)
                       for _ in runs]
            for op, start, dur in leaves:
                i = bisect.bisect_right(starts, start) - 1
                if i >= 0 and start < runs[i][1] + runs[i][2]:
                    per_run[i][region_of(scopes.get(op)) or UNSCOPED] += dur
            medians = {k: 1e3 * statistics.median(row[k] for row in per_run)
                       for k in per_run[0]}
            ctx["notes"].append(
                f"the median step of {len(runs)} by region, ms: "
                + ", ".join(f"{k} {v:.3f}" for k, v in medians.items())
                + f"; together {sum(medians.values()):.3f} of a median "
                f"step of {1e3 * statistics.median(r[2] for r in runs):.3f}")
        ctx["step_medians"] = medians
    medians = ctx["step_medians"]
    if medians is None or medians[name] <= 0:
        return None
    return medians[name]


def mlp_train_flops(config: dict, layers: int, tokens: int) -> float:
    """Forward and backward through the feed-forward matrices (three when
    gated, else two); recomputation is not counted."""
    mats = 3 if config["hidden_act"] == "silu" else 2
    return 6.0 * tokens * layers * mats * config["hidden_size"] \
        * config["intermediate_size"]


def lm_head_train_flops(config: dict, tokens: int) -> float:
    """Forward and backward through the output head, tied or not."""
    return 6.0 * tokens * config["vocab_size"] * config["hidden_size"]
