#!/usr/bin/env python
"""Headline benchmark: Llama causal-LM training MFU on one TPU chip.

Prints ONE JSON line:
  {"metric": "llama_train_mfu", "value": <MFU>, "unit": "mfu_fraction",
   "vs_baseline": <MFU / 0.40 north-star>}

Config scales to the 16 GiB HBM of a single v5e: llama-350m, seq 2048,
bf16 params + fp32 master weights + AdamW, flash-attention path, donated
compiled step (the same TrainStep users run).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp


# MFU accounting (peak table + flops/token formula) lives in
# paddle_tpu/observability/mfu.py — ONE source shared with the runtime
# StepMonitor, so bench numbers and telemetry step events agree by
# construction.  Thin re-exports keep the historical bench.py surface.

def peak_flops() -> float:
    from paddle_tpu.observability.mfu import peak_flops as _pf
    return _pf()


def provenance(fused_ops="auto") -> dict:
    """Attribution block stamped into every bench JSON so
    tools/bench_compare.py trajectories can say WHICH code/toolchain
    produced each point (r01–r05 predate this; the compare tool
    backfills).  Never fatal — a missing .git dir just yields null."""
    git_sha = None
    try:
        import subprocess
        git_sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass
    return {"git_sha": git_sha,
            "jax": getattr(jax, "__version__", None),
            "backend": jax.default_backend(),
            "device": getattr(jax.devices()[0], "device_kind", "cpu"),
            "fused": fused_ops}


def measure(preset, batch_size, seq_len, steps, windows, remat=False,
            loss_chunks=1, remat_layers=None,
            fused_ops="auto"):
    """One full measurement: build model+step, warm up, time `windows`
    independent windows of `steps` steps.  Returns (mfu, stats dict).

    ``fused_ops`` routes the model through the fused-kernel library
    (docs/KERNELS.md): "on"/"off"/"auto" — the one-flag MFU A/B
    (``--fused`` on the CLI)."""
    import gc

    import paddle_tpu as pt
    from paddle_tpu import amp, nn, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import causal_lm_loss, llama

    pt.seed(0)
    model = llama(preset, max_position_embeddings=seq_len,
                  use_recompute=remat, loss_seq_chunks=loss_chunks,
                  recompute_num_layers=remat_layers,
                  fused_ops=fused_ops)
    cfg = model.cfg
    opt = optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0),
                          parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    step = TrainStep(model, causal_lm_loss, opt)
    state = step.init_state(seed=0)

    ids = jax.random.randint(jax.random.key(0), (batch_size, seq_len), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}

    # warmup / compile (float() waits for the step and brings the loss
    # to the host)
    state, m = step(state, batch)
    _ = float(m["loss"])

    # measure N independent windows; report the BEST but record ALL window
    # values so a transient stall is visible in the artifact, not
    # silently discarded (VERDICT r2 weak #5)
    window_dts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch)
        _ = float(m["loss"])
        window_dts.append(time.perf_counter() - t0)
    dt = min(window_dts)

    steps_per_sec = steps / dt
    tokens_per_sec = steps_per_sec * batch_size * seq_len
    n_params = cfg.num_params()
    # causal-attention-aware model flops per token: 6N + 6*L*h*T (the
    # shared accounting in observability/mfu.py)
    from paddle_tpu.observability.mfu import causal_lm_flops_per_token
    flops_per_token = causal_lm_flops_per_token(
        n_params, cfg.num_hidden_layers, cfg.hidden_size, seq_len)
    mfu = tokens_per_sec * flops_per_token / peak_flops()
    stats = {
        "preset": preset, "params": n_params,
        "tokens_per_sec_per_chip": round(tokens_per_sec, 1),
        "ms_per_step": round(1000 * dt / steps, 2),
        "window_ms_per_step": [round(1000 * w / steps, 2)
                               for w in window_dts],
        "batch": batch_size, "seq": seq_len,
        "loss": float(m["loss"]),
        "fused": fused_ops,
    }
    # free this model's device buffers before a follow-up measurement
    del state, step, model, opt, batch, ids
    gc.collect()
    return mfu, stats


def main():
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    # the one-flag fused-kernel A/B (docs/KERNELS.md): --fused off is
    # the pre-fusion baseline, --fused on forces the fused entry points
    # everywhere, auto (default) fuses where a kernel serves
    ap.add_argument("--fused", choices=("on", "off", "auto"),
                    default="auto")
    args, _ = ap.parse_known_args()
    fused_ops = args.fused
    on_tpu = jax.default_backend() != "cpu"
    preset = os.environ.get("PDTPU_BENCH_PRESET",
                            "llama-350m" if on_tpu else "tiny")
    # telemetry sidecar: every bench run also produces a runtime-schema
    # JSONL stream (step/compile/metrics events — docs/OBSERVABILITY.md),
    # so BENCH_r*.json and production telemetry share one vocabulary.
    # Set PDTPU_BENCH_TELEMETRY="" to disable.
    tel = None
    tel_path = os.environ.get("PDTPU_BENCH_TELEMETRY",
                              "bench_telemetry.jsonl")
    if tel_path:
        from paddle_tpu import observability as obs
        tel = obs.enable(jsonl_path=tel_path)
        tel.emit({"event": "run_meta", "kind": "bench", "preset": preset,
                  "backend": jax.default_backend(), "fused": fused_ops,
                  "device": getattr(jax.devices()[0], "device_kind", "cpu")})
    # defaults picked by on-chip sweep (v5e, 2026-07-30): bs4/seq2048 with
    # recompute OFF fits 16 GiB HBM and lands 0.42 MFU; remat ON costs an
    # uncredited extra forward (0.32), bs8 no-remat OOMs by 1.7 GiB
    batch_size = int(os.environ.get("PDTPU_BENCH_BATCH", 4 if on_tpu else 2))
    seq_len = int(os.environ.get("PDTPU_BENCH_SEQ", 2048 if on_tpu else 64))
    # 60 steps ≈ 15s of steady-state (r2: widened from 40 — headline
    # run-to-run spread was ~0.002 MFU at 40)
    steps = int(os.environ.get("PDTPU_BENCH_STEPS", 60 if on_tpu else 3))

    remat = os.environ.get("PDTPU_BENCH_REMAT", "0") == "1"
    # seq-chunked rematerialized vocab CE skips the [B,S,V] logits
    # materialization; it makes bs8 fit (bs8 is slower end-to-end, so the
    # default stays bs4 + unchunked: 0.437 vs 0.435 chunked, sweep
    # 2026-07-30) — the knob exists for memory-tight configs
    loss_chunks = int(os.environ.get("PDTPU_BENCH_LOSS_CHUNKS", 1))
    windows = max(1, int(os.environ.get("PDTPU_BENCH_WINDOWS",
                                        2 if on_tpu else 1)))

    mfu, stats = measure(preset, batch_size, seq_len, steps, windows,
                         remat=remat, loss_chunks=loss_chunks,
                         fused_ops=fused_ops)
    extra = {**stats,
             "backend": jax.default_backend(),
             "device": getattr(jax.devices()[0], "device_kind", "cpu"),
             "provenance": provenance(fused_ops)}

    def extra_point(prefix, *args, keys=("ms_per_step",
                                         "window_ms_per_step",
                                         "tokens_per_sec_per_chip"), **kw):
        # secondary measurement: never let it kill the already-measured
        # headline JSON (an unvalidated env geometry, e.g. seq 4096, may
        # OOM the memory-tightest config)
        try:
            p_mfu, p_stats = measure(*args, **kw)
        except Exception as e:  # noqa: BLE001 — report, don't die
            extra[f"{prefix}_error"] = f"{type(e).__name__}: {e}"[:300]
            return
        extra[f"{prefix}_mfu"] = round(p_mfu, 4)
        for k in keys:
            extra[f"{prefix}_{k}"] = p_stats[k]

    # north-star attention geometry (head_dim 128, the 7B shape): measured
    # in the same run so the driver artifact carries it, not just docs
    # (VERDICT r2 weak #1 / next-round #4)
    if on_tpu and os.environ.get("PDTPU_BENCH_HD128", "1") == "1":
        extra_point("hd128", "llama-350m-hd128", batch_size, seq_len,
                    max(20, steps // 2), windows, fused_ops=fused_ops)

    # first measured point above 350M: llama-1b (h=2048, 16×d128, 0.94B
    # params).  fp32 master + AdamW moments alone are 10.5 GiB of the
    # 16 GiB HBM, so the honest single-chip config needs remat; the
    # on-chip sweep (2026-07-31) picked bs4 + partial remat of 12/16
    # layers (RL=8 OOMs, full remat 0.559, RL=12 0.564).  MFU is credited
    # at 6N — no recompute credit — so this carries a ~22% remat tax the
    # sharded-moment multi-chip config does not pay (docs/BENCH.md §1b).
    if on_tpu and os.environ.get("PDTPU_BENCH_LLAMA1B", "1") == "1":
        extra_point("llama1b", "llama-1b", 4, seq_len,
                    max(20, steps // 2), windows,
                    keys=("ms_per_step", "window_ms_per_step",
                          "tokens_per_sec_per_chip", "params"),
                    remat=True, remat_layers=12, fused_ops=fused_ops)

    # serving decode at the recommended quantized point (int8 weights +
    # int8 KV — docs/BENCH.md "stacked serving quantization"), slope
    # protocol so per-call overhead cancels; non-fatal like the other
    # extras
    if on_tpu and os.environ.get("PDTPU_BENCH_DECODE", "1") == "1":
        try:
            import contextlib
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            from decode_bench import bench_generate
            with contextlib.redirect_stdout(sys.stderr):  # ONE-JSON-line contract
                # full decode_bench protocol (512-token slope, 3 repeats):
                # shorter windows measured 4x-impossible throughputs
                r = bench_generate(batch=1, n_lo=16, n_hi=528, repeats=3,
                                   kv_cache_dtype="int8", weight_quant="int8")
            extra["decode_bs1_int8w_int8kv_tok_s"] = r["tokens_per_sec"]
            extra["decode_bs1_ms_per_token"] = r["ms_per_token"]
        except Exception as e:  # noqa: BLE001
            extra["decode_error"] = f"{type(e).__name__}: {e}"[:300]

    # aggregate continuous-batching serving throughput (serving.Engine
    # over the paged KV pools — docs/SERVING.md): mixed prompt lengths
    # churning through max_batch=8 slots.  Runs on CPU too (tiny preset,
    # small budget) so the metric's PLUMBING is exercised everywhere;
    # the numbers that matter come from TPU rounds.  Non-fatal like the
    # other extras.
    if os.environ.get("PDTPU_BENCH_SERVE", "1") == "1":
        import contextlib
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        try:
            from decode_bench import bench_serve
            with contextlib.redirect_stdout(sys.stderr):
                if on_tpu:
                    r = bench_serve(max_batch=8, kv_cache_dtype="int8")
                else:
                    r = bench_serve(preset="tiny", max_batch=4,
                                    n_requests=6, max_new=8,
                                    prompt_lens=(5, 12, 9, 17),
                                    page_size=8, repeats=1)
            extra["serve_bs8_tok_s" if on_tpu else "serve_cpu_tok_s"] = \
                r["agg_tokens_per_sec"]
            extra["serve_detail"] = {k: r[k] for k in
                                     ("max_batch", "requests", "kv",
                                      "max_new_tokens", "gen_tokens",
                                      "wall_s")}
        except Exception as e:  # noqa: BLE001
            extra["serve_error"] = f"{type(e).__name__}: {e}"[:300]

        # shared-prefix / bursty-admission serving: millions of users
        # behind one system prompt — prefix-cache hit rate must be > 0
        # and TTFT p95 under burst load is the latency headline
        # (docs/SERVING.md).  Same CPU-plumbing / TPU-numbers split and
        # non-fatality as the churn workload above.
        try:
            from decode_bench import bench_serve_prefix
            with contextlib.redirect_stdout(sys.stderr):
                if on_tpu:
                    r = bench_serve_prefix(max_batch=8,
                                           kv_cache_dtype="int8")
                else:
                    r = bench_serve_prefix(preset="tiny", max_batch=2,
                                           n_requests=4, shared_prefix=16,
                                           tail_lens=(4, 9), max_new=6,
                                           page_size=8, prefill_chunk=8)
            pre = "serve_prefix" if on_tpu else "serve_prefix_cpu"
            extra[f"{pre}_ttft_p95_ms"] = r["warm_ttft_p95_ms"]
            extra[f"{pre}_tok_s"] = r["warm_agg_tokens_per_sec"]
            extra[f"{pre}_hit_rate"] = r["prefix_hit_rate"]
            if tel is not None and (r.get("cold_trace")
                                    or r.get("warm_trace")):
                # sampled per-request phase breakdown (one cold, one
                # prefix-warm) into the sidecar: BENCH rounds carry
                # attribution, not just aggregates (OBSERVABILITY.md)
                tel.emit({"event": "serve_trace_sample", "row": pre,
                          "cold": r.get("cold_trace"),
                          "warm": r.get("warm_trace")})
            extra[f"{pre}_detail"] = {
                k: r[k] for k in ("requests", "shared_prefix",
                                  "prefill_chunk", "cold_ttft_p95_ms",
                                  "cold_agg_tokens_per_sec",
                                  "warm_prefix_hits", "cow_copies")}
        except Exception as e:  # noqa: BLE001
            extra["serve_prefix_error"] = f"{type(e).__name__}: {e}"[:300]

        # overload: offered load > capacity through the bounded front
        # door (docs/SERVING.md "Front door") — goodput tok/s, shed
        # rate, and TTFT p95 for the traffic that WAS admitted.  Same
        # CPU-plumbing / TPU-numbers split and non-fatality as above.
        try:
            from decode_bench import bench_serve_burst
            with contextlib.redirect_stdout(sys.stderr):
                if on_tpu:
                    r = bench_serve_burst(max_batch=8,
                                          kv_cache_dtype="int8")
                else:
                    r = bench_serve_burst(preset="tiny", max_batch=2,
                                          offered=8, max_queue_depth=3,
                                          prompt_lens=(5, 11, 8),
                                          max_new=6, page_size=8)
            pre = "serve_burst" if on_tpu else "serve_burst_cpu"
            extra[f"{pre}_goodput_tok_s"] = r["goodput_tok_s"]
            extra[f"{pre}_shed_rate"] = r["shed_rate"]
            extra[f"{pre}_ttft_p95_ms"] = r["admitted_ttft_p95_ms"]
            extra[f"{pre}_detail"] = {
                k: r[k] for k in ("offered", "admitted", "shed",
                                  "max_queue_depth", "gen_tokens",
                                  "wall_s", "admitted_ttft_p50_ms")}
        except Exception as e:  # noqa: BLE001
            extra["serve_burst_error"] = f"{type(e).__name__}: {e}"[:300]

        # speculative decoding (docs/SERVING.md "Speculative
        # decoding"): n-gram self-drafting through the one compiled
        # verify step on a repetitive (code/templated) workload —
        # acceptance rate and tok/s vs the spec-off engine.  Same
        # CPU-plumbing / TPU-numbers split and non-fatality as above.
        try:
            from decode_bench import bench_serve_spec
            with contextlib.redirect_stdout(sys.stderr):
                if on_tpu:
                    r = bench_serve_spec(max_batch=8,
                                         kv_cache_dtype="int8")
                else:
                    r = bench_serve_spec(preset="tiny", max_batch=4,
                                         n_requests=6, max_new=24,
                                         motif_len=6, motif_reps=3,
                                         draft_depth=4, page_size=8)
            pre = "serve_spec" if on_tpu else "serve_spec_cpu"
            extra[f"{pre}_tok_s"] = r["agg_tokens_per_sec"]
            extra[f"{pre}_accept_rate"] = r["accept_rate"]
            extra[f"{pre}_detail"] = {
                k: r[k] for k in ("draft_depth", "proposed", "accepted",
                                  "tokens_per_verify_step", "steps",
                                  "base_steps", "base_tokens_per_sec",
                                  "vs_spec_off", "gen_tokens", "wall_s")}
        except Exception as e:  # noqa: BLE001
            extra["serve_spec_error"] = f"{type(e).__name__}: {e}"[:300]

        # disaggregated serving (docs/SERVING.md "Disaggregated
        # serving"): bursty long-prompt admission against 1 prefill +
        # N decode replicas — decode tok/s (busy-time projection)
        # scaling with N while admitted-TTFT p95 stays flat vs the
        # 1-decode configuration.  Same CPU-plumbing / TPU-numbers
        # split and non-fatality as above.
        try:
            from decode_bench import bench_serve_disagg
            with contextlib.redirect_stdout(sys.stderr):
                if on_tpu:
                    r = bench_serve_disagg(n_decode=2, max_batch=8,
                                           kv_cache_dtype="int8")
                else:
                    r = bench_serve_disagg(preset="tiny", n_decode=2,
                                           max_batch=4, n_requests=10,
                                           prompt_lens=(24, 33, 28, 30),
                                           max_new=24, page_size=8)
            pre = "serve_disagg" if on_tpu else "serve_disagg_cpu"
            extra[f"{pre}_decode_tok_s"] = r["decode_tok_s"]
            extra[f"{pre}_vs_1_decode"] = r["vs_1_decode"]
            extra[f"{pre}_ttft_p95_ms"] = r["ttft_p95_ms"]
            extra[f"{pre}_detail"] = {
                k: r[k] for k in ("n_decode", "requests", "kv",
                                  "gen_tokens", "wall_s", "handoffs",
                                  "xfer_bytes",
                                  "ttft_p95_1_decode_ms",
                                  "ttft_p95_colocated_ms",
                                  "decode_tok_s_1_decode",
                                  "colocated_tok_s")}
        except Exception as e:  # noqa: BLE001
            extra["serve_disagg_error"] = f"{type(e).__name__}: {e}"[:300]

        # batched multi-LoRA (docs/SERVING.md "Multi-LoRA"): N adapters
        # + base mixed in one engine's batch (grouped BGMV over the
        # stacked pools) vs the serial one-merged-engine-per-tenant
        # deployment — batched tok/s over the serial busy-time
        # projection.  Same CPU-plumbing / TPU-numbers split and
        # non-fatality as above.
        try:
            from decode_bench import bench_serve_lora
            with contextlib.redirect_stdout(sys.stderr):
                if on_tpu:
                    r = bench_serve_lora(n_adapters=3, rank=8,
                                         max_batch=8,
                                         kv_cache_dtype="int8")
                else:
                    r = bench_serve_lora(preset="tiny", n_adapters=3,
                                         rank=8, max_batch=4,
                                         n_requests=8,
                                         prompt_lens=(5, 9, 7, 12),
                                         max_new=8, page_size=8)
            pre = "serve_lora" if on_tpu else "serve_lora_cpu"
            extra[f"{pre}_tok_s"] = r["batched_tok_s"]
            extra[f"{pre}_vs_serial"] = r["vs_serial"]
            extra[f"{pre}_detail"] = {
                k: r[k] for k in ("adapters", "rank", "requests", "kv",
                                  "gen_tokens", "wall_s",
                                  "serial_tok_s", "serial_wall_s",
                                  "active_adapters")}
        except Exception as e:  # noqa: BLE001
            extra["serve_lora_error"] = f"{type(e).__name__}: {e}"[:300]

        # sharded serving (docs/SERVING.md "Sharded serving"): the
        # TP-partitioned engine and the DP replica router need >= 2
        # devices (a multi-chip slice, or the forced virtual CPU mesh
        # the CI gate / tests run under).  Same CPU-plumbing /
        # TPU-numbers split and non-fatality as the rows above.
        if len(jax.devices()) >= 2:
            try:
                from decode_bench import bench_serve_tp
                with contextlib.redirect_stdout(sys.stderr):
                    if on_tpu:
                        r = bench_serve_tp(tp=2, max_batch=8,
                                           kv_cache_dtype="int8")
                    else:
                        r = bench_serve_tp(preset="tiny", tp=2,
                                           max_batch=2, n_requests=4,
                                           max_new=8,
                                           prompt_lens=(5, 12, 9, 17),
                                           page_size=8, repeats=1)
                extra["serve_tp_tok_s" if on_tpu
                      else "serve_tp_cpu_tok_s"] = r["agg_tokens_per_sec"]
                extra["serve_tp_detail"] = {
                    k: r[k] for k in ("tp", "max_batch", "requests", "kv",
                                      "gen_tokens", "wall_s")}
            except Exception as e:  # noqa: BLE001
                extra["serve_tp_error"] = f"{type(e).__name__}: {e}"[:300]

            try:
                from decode_bench import bench_serve_dp
                with contextlib.redirect_stdout(sys.stderr):
                    if on_tpu:
                        r = bench_serve_dp(replicas=2, max_batch=8,
                                           kv_cache_dtype="int8")
                    else:
                        r = bench_serve_dp(preset="tiny", replicas=2,
                                           max_batch=4, n_requests=16,
                                           prompt_lens=(24,), max_new=32,
                                           page_size=8)
                pre = "serve_dp" if on_tpu else "serve_dp_cpu"
                extra[f"{pre}_agg_tok_s"] = r["agg_tokens_per_sec"]
                extra[f"{pre}_vs_single_replica"] = r["vs_single_replica"]
                extra[f"{pre}_detail"] = {
                    k: r[k] for k in ("replicas", "tp", "max_batch",
                                      "requests", "gen_tokens", "wall_s",
                                      "wall_tokens_per_sec",
                                      "single_replica_tok_s")}
            except Exception as e:  # noqa: BLE001
                extra["serve_dp_error"] = f"{type(e).__name__}: {e}"[:300]

    result = {
        "metric": "llama_train_mfu",
        "value": round(mfu, 4),
        "unit": "mfu_fraction",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": extra,
    }
    if tel is not None:
        # the sidecar carries the same payload the driver records, plus
        # the final registry snapshot (via disable's flush)
        tel.emit({"event": "bench_result", **result})
        from paddle_tpu import observability as obs
        obs.disable()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
