#!/usr/bin/env python
"""Fold a telemetry JSONL stream into the docs/BENCH.md table format.

Input: one or more JSONL files produced by ``paddle_tpu.observability``
(a training run's sink, bench.py's sidecar, or a ``*.postmortem`` crash
dump — same line format).  Output: markdown tables (per-site step stats,
span durations, compile attribution, collective volume, post-mortem
summary) on stdout, plus ONE JSON summary line on the last line — the
same artifact convention every other tool in this repo follows.

Crash-time streams get cut mid-line (the process died between ``write``
and ``flush``): unparseable/truncated lines are skipped, COUNTED, and
reported — never raised on.

Note: a ``.postmortem`` REPLAYS the last-N ring events; folding it in
the same invocation as its source JSONL double-counts that tail —
report them separately when exact step counts matter.

Pure stdlib on purpose: the report runs anywhere the JSONL landed (a CI
box, a laptop) without jax or the framework installed.

Fleet mode (docs/OBSERVABILITY.md "Fleet observability"): every
cluster worker writes its own JSONL sidecar; pass them all — as a
shell glob, a quoted glob this tool expands itself, or repeated
``--input`` flags — and the report folds them into ONE fleet view
plus a per-worker breakdown table (worker id taken from each file's
``cluster_register`` event, falling back to the file name).

Usage:  python tools/telemetry_report.py run_telemetry.jsonl [more.jsonl]
        python tools/telemetry_report.py run.jsonl run.jsonl.postmortem
        python tools/telemetry_report.py --json run.jsonl   # JSON only
        python tools/telemetry_report.py 'fleet/w*.jsonl'   # fleet fold
        python tools/telemetry_report.py --input w0.jsonl --input w1.jsonl
"""

from __future__ import annotations

import argparse
import glob as _glob
import importlib.util
import json
import math
import os
import sys
from collections import defaultdict

_SINKS = None


def _sinks():
    """Load observability/sinks.py STANDALONE (no package import, no
    jax): the report shares its prom name grammar — ``prom_split`` —
    with the live ``/metrics`` exporter, so bracketed registry names
    (``serve.tenant[acme].ttft_ms``) parse identically in both and the
    two surfaces cannot drift."""
    global _SINKS
    if _SINKS is None:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            os.pardir, "paddle_tpu", "observability",
                            "sinks.py")
        spec = importlib.util.spec_from_file_location(
            "_pdtpu_obs_sinks", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _SINKS = mod
    return _SINKS


def _labeled_metric(key, base_prefix, label_key):
    """``serve.tenant[acme].ttft_ms`` -> ("acme", "ttft_ms") for
    (``serve_tenant_``, ``tenant``), else None — parsed with the
    exporter's own grammar so report and /metrics never drift."""
    base, labels = _sinks().prom_split(key)
    if not base.startswith(base_prefix) or not labels:
        return None
    k, v = labels[0]
    if k != label_key:
        return None
    return v, base[len(base_prefix):]


def _tenant_metric(key):
    return _labeled_metric(key, "serve_tenant_", "tenant")


def _adapter_metric(key):
    """``serve.lora.adapter[fr-legal].tokens`` -> ("fr-legal",
    "tokens")."""
    return _labeled_metric(key, "serve_lora_adapter_", "adapter")


def _pct(sorted_vals, p):
    """Nearest-rank percentile — the registry Histogram's convention."""
    if not sorted_vals:
        return None
    rank = max(1, math.ceil(p / 100.0 * len(sorted_vals)))
    return sorted_vals[min(rank, len(sorted_vals)) - 1]


def load_events(paths):
    """Parse JSONL files; returns (events, malformed_line_count).

    A crash cuts the stream mid-line; a malformed tail (or any garbage
    line) is skipped and counted so the report can say how much of the
    stream was lost, instead of raising and reporting nothing."""
    events, malformed = [], 0
    for path in paths:
        with open(path, errors="replace") as f:
            for ln, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    malformed += 1
                    print(f"warning: {path}:{ln}: unparseable line skipped",
                          file=sys.stderr)
                    continue
                # a JSONL event is an object; a bare scalar that happens
                # to parse (a cut line like `42`) is stream damage too
                if isinstance(ev, dict):
                    events.append(ev)
                else:
                    malformed += 1
                    print(f"warning: {path}:{ln}: non-object line skipped",
                          file=sys.stderr)
    return events, malformed


def summarize(events):
    agg = {
        "steps": defaultdict(lambda: {"n": 0, "warmup": 0, "intervals": [],
                                      "tps": [], "mfu": [], "tokens": 0}),
        "spans": defaultdict(lambda: {"n": 0, "ms": []}),
        "compiles": defaultdict(lambda: {"n": 0, "total_ms": 0.0}),
        "storms": [], "preemptions": [], "hangs": [], "postmortems": [],
        "thread_stacks": [], "metrics": None, "bench_result": None,
        "run_meta": None,
        # resilience vocabulary (docs/RESILIENCE.md): per-site retry /
        # injected-fault counts, plus resume/restart occurrences
        "retries": defaultdict(int), "faults": defaultdict(int),
        "resumes": [], "restarts": [],
        # serving vocabulary (docs/SERVING.md): admission / step / finish,
        # plus the prefix-cache / ragged-step columns (prompt tokens
        # skipped via cache hits, real span tokens per dispatch) and the
        # front-door robustness columns (preempt/restore/shed/isolation,
        # per-tenant attribution)
        "serving": {"requests": 0, "prompt_lens": [], "steps": 0,
                    "step_ms": [], "tokens": 0, "max_active": 0,
                    "max_queue": 0, "max_kv_blocks": 0,
                    "finished": defaultdict(int), "req_ms": [],
                    "cached_tokens": 0, "span_tokens": 0,
                    "preempts": 0, "restores": 0, "swapped_pages": 0,
                    "sheds": defaultdict(int), "isolated": 0,
                    "tenants": defaultdict(int), "spec_errors": 0,
                    # disaggregated serving (docs/SERVING.md
                    # "Disaggregated serving"): prefill-complete
                    # handoffs, completed/failed KV-page transfers,
                    # bytes shipped, and per-transfer wall ms
                    "handoffs": 0, "xfers": 0, "xfer_failures": 0,
                    "xfer_bytes": 0, "xfer_ms": [],
                    # batched multi-LoRA (docs/SERVING.md "Multi-LoRA"):
                    # pool churn from serve_lora_load/evict events,
                    # per-adapter request attribution off serve_request
                    "lora_loads": 0, "lora_evicts": 0,
                    "adapters": defaultdict(int)},
        # DP replica routing (docs/SERVING.md "Sharded serving"):
        # per-replica routed/affinity counts from serve_route events,
        # failures/requeues from serve_replica_fail
        "replicas": defaultdict(lambda: {"routed": 0, "affinity": 0,
                                         "failures": 0, "requeued": 0}),
        # cluster control plane (docs/SERVING.md "Cluster serving"):
        # membership churn, evacuations (requests moved), elasticity
        # transitions with their wall ms, and the epoch-fence drops
        "cluster": {"registers": 0, "deregisters": 0, "deaths": 0,
                    "evacuations": 0, "evacuated": 0,
                    "commands": defaultdict(int), "routes": 0,
                    "role_flips": 0, "flip_ms": [],
                    "upgrades": 0, "upgrade_ms": [],
                    "lease_losses": 0, "autoscales": 0,
                    "transfer_failures": 0,
                    "stale": defaultdict(int),
                    # controller durability (docs/SERVING.md "Durable
                    # gateway"): lease takeovers, journal replay/dedupe,
                    # zombie fencing, spawner elasticity, gateway sheds
                    "takeovers": 0, "takeover_retries": 0, "fenced": 0,
                    "journal_replays": 0, "journal_replayed": 0,
                    "journal_dups": 0, "spawns": 0, "scale_downs": 0,
                    "gateway_sheds": defaultdict(int)},
        # request-lifecycle traces (docs/OBSERVABILITY.md "Tracing a
        # request"): one serve_trace event per retired request carries
        # the exact per-phase breakdown queue/prefill/decode
        "traces": [], "slo_captures": [],
    }
    for e in events:
        kind = e.get("event")
        if kind == "step":
            s = agg["steps"][e.get("site", "?")]
            s["n"] += 1
            s["tokens"] += e.get("tokens") or 0
            if e.get("warmup"):
                s["warmup"] += 1
                continue
            if e.get("interval_ms") is not None:
                s["intervals"].append(e["interval_ms"])
            if e.get("tokens_per_sec") is not None:
                s["tps"].append(e["tokens_per_sec"])
            if e.get("mfu") is not None:
                s["mfu"].append(e["mfu"])
        elif kind == "span":
            sp = agg["spans"][e.get("name", "?")]
            sp["n"] += 1
            if e.get("ms") is not None:
                sp["ms"].append(e["ms"])
        elif kind == "compile":
            c = agg["compiles"][e.get("site", "?")]
            c["n"] += 1
            c["total_ms"] += e.get("duration_ms") or 0.0
        elif kind == "retry":
            agg["retries"][e.get("site") or "?"] += 1
        elif kind == "fault":
            agg["faults"][e.get("site") or "?"] += 1
        elif kind == "resume":
            agg["resumes"].append(e)
        elif kind == "restart":
            agg["restarts"].append(e)
        elif kind == "serve_request":
            sv = agg["serving"]
            sv["requests"] += 1
            if e.get("prompt_len") is not None:
                sv["prompt_lens"].append(e["prompt_len"])
            sv["cached_tokens"] += e.get("cached_tokens") or 0
            if e.get("tenant"):
                sv["tenants"][e["tenant"]] += 1
            if e.get("adapter"):
                sv["adapters"][e["adapter"]] += 1
        elif kind == "serve_lora_load":
            agg["serving"]["lora_loads"] += 1
        elif kind == "serve_lora_evict":
            agg["serving"]["lora_evicts"] += 1
        elif kind == "serve_preempt":
            sv = agg["serving"]
            sv["preempts"] += 1
            sv["swapped_pages"] += e.get("pages") or 0
        elif kind == "serve_restore":
            agg["serving"]["restores"] += 1
        elif kind == "serve_shed":
            agg["serving"]["sheds"][e.get("reason") or "?"] += 1
        elif kind == "serve_isolated_failure":
            agg["serving"]["isolated"] += 1
        elif kind == "serve_handoff":
            agg["serving"]["handoffs"] += 1
        elif kind == "serve_xfer":
            sv = agg["serving"]
            sv["xfers"] += 1
            sv["xfer_bytes"] += e.get("bytes") or 0
            if e.get("ms") is not None:
                sv["xfer_ms"].append(e["ms"])
        elif kind == "serve_xfer_fail":
            agg["serving"]["xfer_failures"] += 1
        elif kind == "serve_route":
            rp = agg["replicas"][e.get("replica", "?")]
            rp["routed"] += 1
            if e.get("affinity_hits"):
                rp["affinity"] += 1
        elif kind == "serve_replica_fail":
            rp = agg["replicas"][e.get("replica", "?")]
            rp["failures"] += 1
            rp["requeued"] += e.get("moved") or 0
        elif kind == "serve_trace":
            s = e.get("summary") or {}
            # per-request speculative acceptance rides the retire event
            # of the timeline (engine._emit; zero for spec-off engines)
            retire = next((ev for ev in (e.get("events") or [])
                           if ev.get("phase") == "retire"), {})
            agg["traces"].append({"tenant": e.get("tenant"),
                                  "queue_ms": s.get("queue_ms"),
                                  "prefill_ms": s.get("prefill_ms"),
                                  "xfer_ms": s.get("xfer_ms"),
                                  "handoffs": s.get("handoffs") or 0,
                                  "decode_ms": s.get("decode_ms"),
                                  "wall_ms": s.get("wall_ms"),
                                  "decode_tokens": s.get("decode_tokens"),
                                  "preempts": s.get("preempts") or 0,
                                  "spec_proposed":
                                      retire.get("spec_proposed"),
                                  "spec_accepted":
                                      retire.get("spec_accepted")})
        elif kind == "serve_spec_error":
            agg["serving"]["spec_errors"] += 1
        elif kind == "serve_slo_capture":
            agg["slo_captures"].append(e)
        elif kind == "serve_step":
            sv = agg["serving"]
            sv["steps"] += 1
            sv["tokens"] += e.get("tokens") or 0
            sv["span_tokens"] += e.get("span_tokens") or 0
            if e.get("ms") is not None:
                sv["step_ms"].append(e["ms"])
            sv["max_active"] = max(sv["max_active"], e.get("active") or 0)
            sv["max_queue"] = max(sv["max_queue"], e.get("queue") or 0)
            sv["max_kv_blocks"] = max(sv["max_kv_blocks"],
                                      e.get("kv_blocks_used") or 0)
        elif kind == "serve_finish":
            sv = agg["serving"]
            sv["finished"][e.get("reason") or "?"] += 1
            if e.get("ms") is not None:
                sv["req_ms"].append(e["ms"])
        elif kind == "cluster_register":
            agg["cluster"]["registers"] += 1
        elif kind == "cluster_deregister":
            agg["cluster"]["deregisters"] += 1
        elif kind == "cluster_dead":
            agg["cluster"]["deaths"] += 1
        elif kind == "cluster_evacuate":
            cl = agg["cluster"]
            cl["evacuations"] += 1
            cl["evacuated"] += e.get("moved") or 0
        elif kind == "cluster_command":
            agg["cluster"]["commands"][e.get("kind") or "?"] += 1
        elif kind == "cluster_route":
            agg["cluster"]["routes"] += 1
        elif kind == "cluster_role_flip":
            cl = agg["cluster"]
            cl["role_flips"] += 1
            if e.get("ms") is not None:
                cl["flip_ms"].append(e["ms"])
        elif kind == "cluster_upgrade":
            cl = agg["cluster"]
            cl["upgrades"] += 1
            if e.get("ms") is not None:
                cl["upgrade_ms"].append(e["ms"])
        elif kind == "cluster_lease_lost":
            agg["cluster"]["lease_losses"] += 1
        elif kind == "cluster_autoscale":
            agg["cluster"]["autoscales"] += 1
        elif kind == "cluster_transfer_failed":
            agg["cluster"]["transfer_failures"] += 1
        elif kind in ("cluster_stale_command", "cluster_stale_item",
                      "cluster_stale_out"):
            agg["cluster"]["stale"][kind[len("cluster_stale_"):]] += 1
        elif kind == "cluster_takeover":
            agg["cluster"]["takeovers"] += 1
        elif kind == "cluster_takeover_retry":
            agg["cluster"]["takeover_retries"] += 1
        elif kind == "cluster_fenced":
            agg["cluster"]["fenced"] += 1
        elif kind == "cluster_journal_replay":
            cl = agg["cluster"]
            cl["journal_replays"] += 1
            cl["journal_replayed"] += e.get("replayed") or 0
        elif kind == "cluster_journal_dup":
            agg["cluster"]["journal_dups"] += 1
        elif kind == "cluster_spawn":
            agg["cluster"]["spawns"] += 1
        elif kind == "cluster_scale_down":
            agg["cluster"]["scale_downs"] += 1
        elif kind == "serve_gateway" and e.get("state") == "shed":
            agg["cluster"]["gateway_sheds"][e.get("reason") or "?"] += 1
        elif kind == "recompile_storm":
            agg["storms"].append(e)
        elif kind == "preemption":
            agg["preemptions"].append(e)
        elif kind == "hang":
            agg["hangs"].append(e)
        elif kind == "postmortem":
            agg["postmortems"].append(e)
        elif kind == "thread_stack":
            agg["thread_stacks"].append(e)
        elif kind == "metrics":
            agg["metrics"] = e.get("metrics") or {}
        elif kind == "bench_result":
            agg["bench_result"] = e
        elif kind == "run_meta":
            agg["run_meta"] = e
    return agg


def _phase_stats(traces):
    """Per-phase p50/p95 over the folded serve_trace summaries."""
    out = {}
    for phase in ("queue_ms", "prefill_ms", "xfer_ms", "decode_ms",
                  "wall_ms"):
        vals = sorted(t[phase] for t in traces
                      if t.get(phase) is not None)
        out[phase] = {"n": len(vals), "p50": _pct(vals, 50),
                      "p95": _pct(vals, 95)}
    per_tok = sorted(t["decode_ms"] / t["decode_tokens"]
                     for t in traces
                     if t.get("decode_ms") is not None
                     and t.get("decode_tokens"))
    out["decode_ms_per_token"] = {"n": len(per_tok),
                                  "p50": _pct(per_tok, 50),
                                  "p95": _pct(per_tok, 95)}
    return out


def _lora_stats(agg):
    """Multi-LoRA fold (docs/SERVING.md "Multi-LoRA"): pool gauges and
    churn counters plus the per-adapter request/token counters
    (``serve.lora.adapter[<name>].requests/tokens``), merged with the
    serve_request event attribution for telemetry-off runs."""
    m = agg["metrics"] or {}
    sv = agg["serving"]
    adapters = defaultdict(lambda: {"requests": 0, "tokens": 0})
    for key, snap in m.items():
        am = _adapter_metric(key)
        if am is None or isinstance(snap, dict):
            continue
        name, metric = am
        if metric in ("requests", "tokens"):
            adapters[name][metric] = snap
    for name, n in sv["adapters"].items():
        if name not in adapters:
            adapters[name]["requests"] = n
    return {"active_adapters": m.get("serve.lora.active_adapters") or 0,
            "loads": m.get("serve.lora.loads") or sv["lora_loads"],
            "evictions": m.get("serve.lora.evictions")
            or sv["lora_evicts"],
            "adapters": {k: dict(v)
                         for k, v in sorted(adapters.items())}}


def _tenant_stats(agg):
    """Per-tenant fold: trace phase breakdowns grouped by tenant merged
    with the per-tenant registry aggregates (serve.tenant[<t>].ttft_ms),
    parsed with the exporter's prom grammar."""
    tenants = defaultdict(lambda: {"traces": [], "ttft_p50": None,
                                   "ttft_p95": None})
    for t in agg["traces"]:
        tenants[t.get("tenant") or "—"]["traces"].append(t)
    for key, snap in (agg["metrics"] or {}).items():
        tm = _tenant_metric(key)
        if tm is None or not isinstance(snap, dict):
            continue
        tenant, metric = tm
        if metric == "ttft_ms":
            tenants[tenant]["ttft_p50"] = snap.get("p50")
            tenants[tenant]["ttft_p95"] = snap.get("p95")
    out = {}
    for tenant, d in tenants.items():
        ph = _phase_stats(d["traces"]) if d["traces"] else None
        out[tenant] = {"traces": len(d["traces"]),
                       "ttft_p50": d["ttft_p50"],
                       "ttft_p95": d["ttft_p95"],
                       "phases": ph}
    return out


def _fused_mode(agg):
    """The run's fused-kernel mode (bench.py --fused), from run_meta or
    the bench result's stats — None when the stream predates the flag."""
    for src in (agg.get("run_meta"), agg.get("bench_result")):
        if src is None:
            continue
        if src.get("fused") is not None:
            return src["fused"]
        extra = src.get("extra") or {}
        if extra.get("fused") is not None:
            return extra["fused"]
    return None


def render(agg, malformed=0):
    steps, compiles = agg["steps"], agg["compiles"]
    storms, preemptions = agg["storms"], agg["preemptions"]
    metrics = agg["metrics"]
    lines = ["## Telemetry report", ""]
    if malformed:
        lines.append(f"**{malformed} malformed/truncated line(s) skipped** "
                     "(a crash cuts the stream mid-line; the rest of the "
                     "report covers what survived)")
        lines.append("")
    if steps:
        # `fused` column: the run-level fused-kernel mode (bench.py
        # --fused A/B) so two streams' step tables identify their leg
        fused = _fused_mode(agg) or "—"
        lines += ["| Site | Steps | ms/step p50 | ms/step p95 | tok/s "
                  "| MFU | Fused |",
                  "|---|---|---|---|---|---|---|"]
        for site, s in sorted(steps.items()):
            iv = sorted(s["intervals"])
            p50 = _pct(iv, 50)
            p95 = _pct(iv, 95)
            tps = (sum(s["tps"]) / len(s["tps"])) if s["tps"] else None
            mfu = (sum(s["mfu"]) / len(s["mfu"])) if s["mfu"] else None

            def fmt(v, nd=2):
                return f"{v:.{nd}f}" if v is not None else "—"
            lines.append(
                f"| {site} | {s['n']} ({s['warmup']} warmup) | {fmt(p50)} "
                f"| {fmt(p95)} | {fmt(tps, 1)} | {fmt(mfu, 4)} "
                f"| {fused} |")
        lines.append("")
    if agg["spans"]:
        lines += ["| Span | Count | ms p50 | ms p95 |", "|---|---|---|---|"]
        for name, sp in sorted(agg["spans"].items()):
            ms = sorted(sp["ms"])
            p50, p95 = _pct(ms, 50), _pct(ms, 95)

            def fmt(v):
                return f"{v:.2f}" if v is not None else "—"
            lines.append(f"| {name} | {sp['n']} | {fmt(p50)} | {fmt(p95)} |")
        lines.append("")
    if compiles:
        lines += ["| Compile site | Compiles | Total compile ms |",
                  "|---|---|---|"]
        for site, c in sorted(compiles.items()):
            lines.append(f"| {site} | {c['n']} | {c['total_ms']:.1f} |")
        lines.append("")
    coll = {k: v for k, v in (metrics or {}).items()
            if k.startswith("collective.") and "[" not in k}
    if coll:
        ops = sorted({k.split(".")[1] for k in coll})
        lines += ["| Collective | Calls | Bytes |", "|---|---|---|"]
        for op in ops:
            lines.append(
                f"| {op} | {coll.get(f'collective.{op}.calls', 0)} "
                f"| {coll.get(f'collective.{op}.bytes', 0):,} |")
        lines.append("")
    if agg["retries"] or agg["faults"]:
        lines += ["| Resilience site | Retries | Injected faults |",
                  "|---|---|---|"]
        for site in sorted(set(agg["retries"]) | set(agg["faults"])):
            lines.append(f"| {site} | {agg['retries'].get(site, 0)} "
                         f"| {agg['faults'].get(site, 0)} |")
        lines.append("")
    sv = agg["serving"]
    if sv["requests"] or sv["steps"] or sv["sheds"] or sv["preempts"]:
        ms = sorted(sv["step_ms"])
        busy_s = sum(sv["step_ms"]) / 1e3
        agg_tps = (sv["tokens"] / busy_s) if busy_s else None
        fin = ", ".join(f"{n} {r}" for r, n in sorted(sv["finished"].items())) \
            or "—"
        pl = sorted(sv["prompt_lens"])
        m = metrics or {}
        ttft = m.get("serve.ttft_ms") or {}
        occ = m.get("serve.ragged_occupancy") or {}

        def fmt(v, nd=2):
            return f"{v:.{nd}f}" if v is not None else "—"
        lines += ["| Serving | |", "|---|---|",
                  f"| requests (finished) | {sv['requests']} ({fin}) |",
                  f"| prompt lens | {pl[0]}..{pl[-1]} |" if pl else
                  "| prompt lens | — |",
                  f"| steps | {sv['steps']} |",
                  f"| step ms p50 / p95 | {fmt(_pct(ms, 50))} / "
                  f"{fmt(_pct(ms, 95))} |",
                  f"| tokens (agg tok/s) | {sv['tokens']} "
                  f"({fmt(agg_tps, 1)}) |",
                  f"| ttft ms p50 / p95 | {fmt(ttft.get('p50'))} / "
                  f"{fmt(ttft.get('p95'))} |",
                  f"| peak active / queue / kv blocks | {sv['max_active']} "
                  f"/ {sv['max_queue']} / {sv['max_kv_blocks']} |"]
        # prefix-cache / ragged-step columns (docs/SERVING.md): page
        # hit rate from the counters, prompt tokens the cache skipped
        # from serve_request events, sharing + CoW from gauges/counters,
        # dispatch occupancy from the step histogram
        hits = m.get("serve.prefix_hits") or 0
        misses = m.get("serve.prefix_misses") or 0
        probes = hits + misses
        prompt_toks = sum(pl)
        if probes or sv["cached_tokens"]:
            rate = f" ({hits / probes:.3f})" if probes else ""
            lines.append(f"| prefix pages hit / missed | {hits} / "
                         f"{misses}{rate} |")
            cached_pct = (f" ({sv['cached_tokens'] / prompt_toks:.3f})"
                          if prompt_toks else "")
            lines.append(f"| prompt tokens from cache | "
                         f"{sv['cached_tokens']} / {prompt_toks}"
                         f"{cached_pct} |")
            lines.append(f"| shared / cached blocks (last) | "
                         f"{m.get('serve.shared_blocks', 0)} / "
                         f"{m.get('serve.cached_blocks', 0)} |")
            lines.append(f"| CoW copies | "
                         f"{m.get('serve.cow_copies', 0)} |")
        if occ or sv["span_tokens"]:
            lines.append(f"| ragged occupancy p50 / p95 | "
                         f"{fmt(occ.get('p50'))} / {fmt(occ.get('p95'))} "
                         f"({sv['span_tokens']} span tokens) |")
        # span fan-out (docs/SERVING.md "Step anatomy"): rows a
        # prefilling request held in a step (1 = no fan-out), steps
        # from a request's first chunk to its first token, and the token
        # tiles the step's MLP kernel multiplied (1 = the weights' one
        # crossing of HBM and no more)
        for label, key in (("prefill rows a step", "serve.prefill_rows"),
                           ("prefill steps to first token",
                            "serve.prefill_steps"),
                           ("MLP token tiles a step",
                            "serve.mlp_live_tiles")):
            h = m.get(key) or {}
            if h:
                lines.append(f"| {label} p50 / p95 / max | "
                             f"{fmt(h.get('p50'), 0)} / "
                             f"{fmt(h.get('p95'), 0)} / "
                             f"{fmt(h.get('max'), 0)} |")
        # speculative decoding (docs/SERVING.md "Speculative decoding"):
        # acceptance-rate column from the serve.spec.* counters, accept
        # length distribution from the histogram
        spec_prop = m.get("serve.spec.proposed") or 0
        spec_acc = m.get("serve.spec.accepted") or 0
        spec_err = m.get("serve.spec.draft_errors") or sv["spec_errors"]
        if spec_prop:
            al = m.get("serve.spec.accept_len") or {}
            lines.append(f"| spec drafts proposed / accepted | "
                         f"{spec_prop} / {spec_acc} "
                         f"({spec_acc / spec_prop:.3f}) |")
            lines.append(f"| spec accept len p50 / p95 | "
                         f"{fmt(al.get('p50'))} / {fmt(al.get('p95'))} |")
        if spec_err:
            # NOT nested under spec_prop: a run where drafting is
            # fully broken (errors > 0, proposed == 0) must still
            # surface the one signal that says so
            lines.append(f"| spec draft errors | {spec_err} |")
        # batched multi-LoRA (docs/SERVING.md "Multi-LoRA"): pool churn
        # plus per-adapter attribution — only when the run used a pool
        lstats = _lora_stats(agg)
        if lstats["loads"] or lstats["adapters"]:
            lines.append(f"| LoRA adapters active (loads / evicts) | "
                         f"{lstats['active_adapters']} "
                         f"({lstats['loads']} / "
                         f"{lstats['evictions']}) |")
            for name, d in lstats["adapters"].items():
                lines.append(f"| LoRA `{name}` requests / tokens | "
                             f"{d['requests']} / {d['tokens']} |")
        # front-door robustness columns (docs/SERVING.md "Front door"):
        # preemption/swap volume, shed reasons, isolation count, and
        # per-tenant attribution — only when the run exercised them
        if sv["preempts"] or sv["restores"]:
            lines.append(f"| preempted / restored (pages swapped) | "
                         f"{sv['preempts']} / {sv['restores']} "
                         f"({sv['swapped_pages']}) |")
        if sv["sheds"]:
            shed = ", ".join(f"{n} {r}" for r, n in
                             sorted(sv["sheds"].items()))
            lines.append(f"| shed (by reason) | {shed} |")
        if sv["isolated"]:
            lines.append(f"| isolated failures | {sv['isolated']} |")
        # disaggregated handoff columns (docs/SERVING.md
        # "Disaggregated serving") — only when the run handed off
        if sv["handoffs"] or sv["xfers"] or sv["xfer_failures"]:
            xms = sorted(sv["xfer_ms"])
            lines.append(
                f"| handoffs / transfers (failed) | {sv['handoffs']} / "
                f"{sv['xfers']} ({sv['xfer_failures']}) |")
            lines.append(
                f"| xfer bytes, ms p50 / p95 | {sv['xfer_bytes']} , "
                f"{fmt(_pct(xms, 50))} / {fmt(_pct(xms, 95))} |")
        if sv["tenants"]:
            ten = ", ".join(f"{t}: {n}" for t, n in
                            sorted(sv["tenants"].items()))
            lines.append(f"| requests by tenant | {ten} |")
        lines.append("")
    if agg["traces"]:
        # request-lifecycle attribution (docs/OBSERVABILITY.md "Tracing
        # a request"): where requests spent their time, per phase
        ph = _phase_stats(agg["traces"])

        def fmt(v, nd=2):
            return f"{v:.{nd}f}" if v is not None else "—"
        lines += [f"| Request phase ({len(agg['traces'])} traces) "
                  "| p50 ms | p95 ms |", "|---|---|---|"]
        for phase in ("queue_ms", "prefill_ms", "xfer_ms", "decode_ms",
                      "decode_ms_per_token", "wall_ms"):
            s = ph[phase]
            if phase == "xfer_ms" and not s["n"]:
                continue             # colocated runs never enter xfer
            lines.append(f"| {phase.replace('_ms', '').replace('_', ' ')} "
                         f"| {fmt(s['p50'])} | {fmt(s['p95'])} |")
        preempted = sum(1 for t in agg["traces"] if t["preempts"])
        if preempted:
            lines.append(f"| traces with preemptions | {preempted} | |")
        lines.append("")
        tstats = _tenant_stats(agg)
        if len(tstats) > 1 or (tstats and "—" not in tstats):
            lines += ["| Tenant | Traces | queue p50/p95 "
                      "| ttft p50/p95 | decode ms/tok p50/p95 |",
                      "|---|---|---|---|---|"]
            for tenant, d in sorted(tstats.items()):
                p = d["phases"] or {}
                q = p.get("queue_ms") or {}
                dk = p.get("decode_ms_per_token") or {}
                lines.append(
                    f"| {tenant} | {d['traces']} "
                    f"| {fmt(q.get('p50'))} / {fmt(q.get('p95'))} "
                    f"| {fmt(d['ttft_p50'])} / {fmt(d['ttft_p95'])} "
                    f"| {fmt(dk.get('p50'))} / {fmt(dk.get('p95'))} |")
            lines.append("")
    for cap in agg["slo_captures"]:
        if cap.get("state") == "done":
            lines.append(f"**SLO CAPTURE**: TTFT p95 "
                         f"{cap.get('ttft_p95_ms')}ms breached — "
                         f"profiler trace at `{cap.get('trace_dir')}` "
                         f"({cap.get('capture_steps')} steps)")
    if agg["replicas"]:
        # DP replica routing: where requests landed and what failed;
        # the live per-replica gauges (serve.replica[i].free_blocks /
        # queue_depth) ride the metrics snapshot below
        m = metrics or {}
        lines += ["| Replica | Routed | Affinity-pinned | Failures "
                  "| Requeued off | Free blocks (last) |",
                  "|---|---|---|---|---|---|"]
        for rep, rp in sorted(agg["replicas"].items(), key=str):
            free = m.get(f"serve.replica[{rep}].free_blocks", "—")
            lines.append(
                f"| {rep} | {rp['routed']} | {rp['affinity']} "
                f"| {rp['failures']} | {rp['requeued']} | {free} |")
        lines.append("")
    cl = agg["cluster"]
    if cl["registers"] or cl["routes"] or cl["deaths"]:
        # cluster control plane (docs/SERVING.md "Cluster serving"):
        # membership churn + elasticity transitions with their cost
        def fmt_ms(vals):
            if not vals:
                return "—"
            v = sorted(vals)
            return f"{_pct(v, 50):.1f} / {_pct(v, 95):.1f}"
        lines += ["| Cluster control plane | |", "|---|---|",
                  f"| registers / deregisters | {cl['registers']} / "
                  f"{cl['deregisters']} |",
                  f"| routes | {cl['routes']} |",
                  f"| deaths (lease expiry) | {cl['deaths']} |",
                  f"| evacuations (requests moved) | "
                  f"{cl['evacuations']} ({cl['evacuated']}) |",
                  f"| role flips, ms p50 / p95 | {cl['role_flips']} , "
                  f"{fmt_ms(cl['flip_ms'])} |",
                  f"| rolling upgrades, ms p50 / p95 | "
                  f"{cl['upgrades']} , {fmt_ms(cl['upgrade_ms'])} |",
                  f"| lease losses | {cl['lease_losses']} |",
                  f"| autoscale flips | {cl['autoscales']} |",
                  f"| hard transfer failures (re-prefilled) | "
                  f"{cl['transfer_failures']} |"]
        if cl["commands"]:
            cmds = ", ".join(f"{k}: {n}" for k, n in
                             sorted(cl["commands"].items()))
            lines.append(f"| commands (by kind) | {cmds} |")
        if cl["stale"]:
            stale = ", ".join(f"{k}: {n}" for k, n in
                              sorted(cl["stale"].items()))
            lines.append(f"| epoch-fence drops (by kind) | {stale} |")
        if cl["takeovers"] or cl["takeover_retries"] or cl["fenced"]:
            lines.append(
                f"| controller takeovers (retried / fenced zombies) | "
                f"{cl['takeovers']} ({cl['takeover_retries']} / "
                f"{cl['fenced']}) |")
        if cl["journal_replays"] or cl["journal_dups"]:
            lines.append(
                f"| journal replays (entries) / idempotent dups | "
                f"{cl['journal_replays']} ({cl['journal_replayed']}) / "
                f"{cl['journal_dups']} |")
        if cl["spawns"] or cl["scale_downs"]:
            lines.append(f"| worker spawns / scale-downs | "
                         f"{cl['spawns']} / {cl['scale_downs']} |")
        if cl["gateway_sheds"]:
            sheds = ", ".join(f"{k}: {n}" for k, n in
                              sorted(cl["gateway_sheds"].items()))
            lines.append(f"| gateway sheds (by reason) | {sheds} |")
        lines.append("")
    for r in agg["resumes"]:
        lines.append(f"**RESUME**: step {r.get('step')} from "
                     f"`{r.get('ckpt')}` (restart {r.get('restarts')})")
    for r in agg["restarts"]:
        lines.append(f"**RESTART** #{r.get('restarts')}: {r.get('exc')}: "
                     f"{r.get('message')}")
    for st in storms:
        lines.append(f"**RECOMPILE STORM**: `{st.get('site')}` — "
                     f"{st.get('compiles_after_warmup')} compiles beyond "
                     f"warmup within {st.get('window_s')}s "
                     "(see docs/OBSERVABILITY.md)")
    for p in preemptions:
        lines.append(f"**PREEMPTION**: {p.get('reason')} at step "
                     f"{p.get('step')} (ts {p.get('ts')})")
    for h in agg["hangs"]:
        lines.append(f"**HANG**: no progress for {h.get('age_s')}s "
                     f"(deadline {h.get('deadline_s')}s) — post-mortem: "
                     f"{h.get('postmortem')}")
    if agg["postmortems"]:
        lines.append("")
        lines.append("### Post-mortem")
        for pm in agg["postmortems"]:
            lines.append(f"- reason: `{pm.get('reason')}` (ts {pm.get('ts')}"
                         f", pid {pm.get('pid')})")
            exc = pm.get("exception")
            if exc:
                lines.append(f"  - exception: `{exc.get('type')}: "
                             f"{exc.get('message')}`")
        n_threads = len(agg["thread_stacks"])
        if n_threads:
            lines.append(f"- {n_threads} thread stack(s) captured:")
            for ts_ in agg["thread_stacks"]:
                frames = ts_.get("frames") or []
                # the innermost frame is where the thread was stuck
                tail = (" — ".join(l.strip() for l in
                                   frames[-1].strip().splitlines())
                        if frames else "?")
                lines.append(f"  - `{ts_.get('thread')}`"
                             f"{' (daemon)' if ts_.get('daemon') else ''}: "
                             f"{tail}")
    if not (steps or agg["spans"] or compiles or coll or storms
            or preemptions or agg["hangs"] or agg["postmortems"]
            or agg["retries"] or agg["faults"] or agg["resumes"]
            or agg["restarts"] or sv["requests"] or sv["steps"]
            or sv["sheds"] or sv["preempts"] or agg["replicas"]
            or agg["traces"] or agg["slo_captures"]):
        lines.append("(no telemetry events found)")
    return "\n".join(lines)


def expand_inputs(paths, inputs):
    """Positionals + repeated ``--input`` flags, each glob-expanded
    (quoted globs work without shell help); order-preserving dedup so
    ``w*.jsonl w0.jsonl`` doesn't double-count a stream."""
    out, seen = [], set()
    for p in list(paths or []) + list(inputs or []):
        matches = sorted(_glob.glob(p)) or [p]  # non-glob / missing:
        for m in matches:                       # open() reports it
            if m not in seen:
                seen.add(m)
                out.append(m)
    return out


def _worker_label(path, events):
    """A per-file worker label for the fleet breakdown: the worker id
    the stream registered under, else the file's basename."""
    for e in events:
        if e.get("event") == "cluster_register" and e.get("worker"):
            return str(e["worker"])
    return os.path.basename(path)


def worker_breakdown(per_file):
    """``[(path, events)] -> {label: row}`` — the per-worker fold
    behind the fleet report's breakdown table."""
    rows = {}
    for path, events in per_file:
        label = _worker_label(path, events)
        if label in rows:            # two streams, one worker id
            label = f"{label} ({os.path.basename(path)})"
        a = summarize(events)
        sv = a["serving"]
        step_ms = sorted(sv["step_ms"])
        walls = sorted(t["wall_ms"] for t in a["traces"]
                       if t.get("wall_ms") is not None)
        rows[label] = {
            "file": path,
            "events": len(events),
            "requests": sv["requests"],
            "traces": len(a["traces"]),
            "tokens": sv["tokens"],
            "steps": sv["steps"],
            "step_p95_ms": _pct(step_ms, 95),
            "wall_p95_ms": _pct(walls, 95),
            "handoffs": sv["handoffs"],
            "evacuations": a["cluster"]["evacuations"],
        }
    return rows


def render_workers(rows):
    lines = [f"| Worker ({len(rows)} streams) | Events | Requests "
             "| Traces | Tokens | step p95 ms | wall p95 ms "
             "| Handoffs |",
             "|---|---|---|---|---|---|---|---|"]

    def fmt(v, nd=2):
        return f"{v:.{nd}f}" if v is not None else "—"
    for label, r in sorted(rows.items()):
        lines.append(
            f"| {label} | {r['events']} | {r['requests']} "
            f"| {r['traces']} | {r['tokens']} "
            f"| {fmt(r['step_p95_ms'])} | {fmt(r['wall_p95_ms'])} "
            f"| {r['handoffs']} |")
    lines.append("")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("paths", nargs="*", help="telemetry JSONL file(s); "
                    "globs are expanded")
    ap.add_argument("--input", action="append", default=[],
                    metavar="PATH", help="additional JSONL file/glob "
                    "(repeatable) — fleet sidecars")
    ap.add_argument("--json", action="store_true",
                    help="print only the JSON summary line")
    args = ap.parse_args(argv)
    paths = expand_inputs(args.paths, args.input)
    if not paths:
        ap.error("no input files (positional paths or --input)")

    per_file, events, malformed = [], [], 0
    for path in paths:
        evs, bad = load_events([path])
        per_file.append((path, evs))
        events.extend(evs)
        malformed += bad
    agg = summarize(events)
    workers = worker_breakdown(per_file) if len(per_file) > 1 else None
    if not args.json:
        print(render(agg, malformed))
        if workers:
            print()
            print(render_workers(workers))
    summary = {
        "metric": "telemetry_report",
        "events": len(events),
        "malformed_lines": malformed,
        "sites": {site: {"steps": s["n"],
                         "p50_ms": _pct(sorted(s["intervals"]), 50),
                         "p95_ms": _pct(sorted(s["intervals"]), 95),
                         "mean_mfu": (round(sum(s["mfu"]) / len(s["mfu"]), 4)
                                      if s["mfu"] else None)}
                  for site, s in sorted(agg["steps"].items())},
        "spans": {name: {"n": sp["n"],
                         "p50_ms": _pct(sorted(sp["ms"]), 50),
                         "p95_ms": _pct(sorted(sp["ms"]), 95)}
                  for name, sp in sorted(agg["spans"].items())},
        "compiles": {site: c["n"]
                     for site, c in sorted(agg["compiles"].items())},
        "storms": len(agg["storms"]),
        "preemptions": len(agg["preemptions"]),
        "hangs": len(agg["hangs"]),
        "retries": dict(sorted(agg["retries"].items())),
        "faults": dict(sorted(agg["faults"].items())),
        "resumes": len(agg["resumes"]),
        "restarts": len(agg["restarts"]),
        "postmortems": [pm.get("reason") for pm in agg["postmortems"]],
        "thread_stacks": len(agg["thread_stacks"]),
    }
    sv = agg["serving"]
    if sv["requests"] or sv["steps"] or sv["sheds"] or sv["preempts"]:
        busy_s = sum(sv["step_ms"]) / 1e3
        m = agg["metrics"] or {}
        hits = m.get("serve.prefix_hits") or 0
        misses = m.get("serve.prefix_misses") or 0
        occ = m.get("serve.ragged_occupancy") or {}
        summary["serving"] = {
            "requests": sv["requests"],
            "finished": dict(sorted(sv["finished"].items())),
            "steps": sv["steps"],
            "tokens": sv["tokens"],
            "agg_tok_s": (round(sv["tokens"] / busy_s, 1)
                          if busy_s else None),
            "step_p50_ms": _pct(sorted(sv["step_ms"]), 50),
            "step_p95_ms": _pct(sorted(sv["step_ms"]), 95),
            "req_p50_ms": _pct(sorted(sv["req_ms"]), 50),
            "peak_active": sv["max_active"],
            "peak_queue": sv["max_queue"],
            "peak_kv_blocks": sv["max_kv_blocks"],
            "prefix_hits": hits,
            "prefix_misses": misses,
            "prefix_hit_rate": (round(hits / (hits + misses), 3)
                                if hits + misses else None),
            "cached_tokens": sv["cached_tokens"],
            "cow_copies": m.get("serve.cow_copies") or 0,
            "shared_blocks": m.get("serve.shared_blocks") or 0,
            "cached_blocks": m.get("serve.cached_blocks") or 0,
            "span_tokens": sv["span_tokens"],
            "ragged_occupancy_p50": occ.get("p50"),
            "ragged_occupancy_p95": occ.get("p95"),
            "prefill_rows_p95":
                (m.get("serve.prefill_rows") or {}).get("p95"),
            "prefill_steps_p95":
                (m.get("serve.prefill_steps") or {}).get("p95"),
            "preempts": sv["preempts"],
            "restores": sv["restores"],
            "swapped_pages": sv["swapped_pages"],
            "sheds": dict(sorted(sv["sheds"].items())),
            "isolated_failures": sv["isolated"],
            "tenants": dict(sorted(sv["tenants"].items())),
            "spec_proposed": m.get("serve.spec.proposed") or 0,
            "spec_accepted": m.get("serve.spec.accepted") or 0,
            "spec_accept_rate": (
                round((m.get("serve.spec.accepted") or 0)
                      / m["serve.spec.proposed"], 3)
                if m.get("serve.spec.proposed") else None),
            "spec_draft_errors": m.get("serve.spec.draft_errors") or 0,
            # disaggregated handoff/transfer fold (docs/SERVING.md
            # "Disaggregated serving")
            "handoffs": sv["handoffs"],
            "xfers": sv["xfers"],
            "xfer_failures": sv["xfer_failures"],
            "xfer_bytes": sv["xfer_bytes"],
            "xfer_p50_ms": _pct(sorted(sv["xfer_ms"]), 50),
            "xfer_p95_ms": _pct(sorted(sv["xfer_ms"]), 95),
            # batched multi-LoRA (docs/SERVING.md "Multi-LoRA")
            "lora": _lora_stats(agg),
        }
    if agg["replicas"]:
        summary["replicas"] = {
            str(rep): dict(rp)
            for rep, rp in sorted(agg["replicas"].items(), key=str)}
    cl = agg["cluster"]
    if cl["registers"] or cl["routes"] or cl["deaths"]:
        summary["cluster"] = {
            "registers": cl["registers"],
            "deregisters": cl["deregisters"],
            "routes": cl["routes"],
            "deaths": cl["deaths"],
            "evacuations": cl["evacuations"],
            "evacuated_requests": cl["evacuated"],
            "role_flips": cl["role_flips"],
            "flip_p50_ms": _pct(sorted(cl["flip_ms"]), 50),
            "flip_p95_ms": _pct(sorted(cl["flip_ms"]), 95),
            "upgrades": cl["upgrades"],
            "upgrade_p50_ms": _pct(sorted(cl["upgrade_ms"]), 50),
            "upgrade_p95_ms": _pct(sorted(cl["upgrade_ms"]), 95),
            "lease_losses": cl["lease_losses"],
            "autoscale_flips": cl["autoscales"],
            "transfer_failures": cl["transfer_failures"],
            "commands": dict(sorted(cl["commands"].items())),
            "stale_drops": dict(sorted(cl["stale"].items())),
            "takeovers": cl["takeovers"],
            "takeover_retries": cl["takeover_retries"],
            "fenced_controllers": cl["fenced"],
            "journal_replays": cl["journal_replays"],
            "journal_replayed_entries": cl["journal_replayed"],
            "journal_dups": cl["journal_dups"],
            "worker_spawns": cl["spawns"],
            "worker_scale_downs": cl["scale_downs"],
            "gateway_sheds": dict(sorted(cl["gateway_sheds"].items()))}
    if agg["traces"]:
        summary["trace_phases"] = _phase_stats(agg["traces"])
        summary["trace_tenants"] = _tenant_stats(agg)
    if agg["slo_captures"]:
        summary["slo_captures"] = [
            c.get("trace_dir") for c in agg["slo_captures"]
            if c.get("state") == "done"]
    if workers:
        summary["workers"] = workers
    if agg["bench_result"] is not None:
        summary["bench_value"] = agg["bench_result"].get("value")
    fused = _fused_mode(agg)
    if fused is not None:
        summary["fused"] = fused
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
