"""End-to-end decode throughput benchmark (VERDICT r2 #5; SURVEY L10).

Measures, on the real chip:
1. ``generate()`` decode tokens/sec for llama-350m at bs in {1, 8}
   (greedy, KV cache, prefill 128) using the SLOPE method: time two decode
   lengths inside the compiled loop and divide the delta — prefill cost
   and dispatch overhead cancel (docs/BENCH.md protocol).
2. op-level paged vs contiguous (masked) decode attention at the same
   shapes, amortized inside one jit.

Prints one JSON line per measurement.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def bench_generate(preset="llama-350m", batch=1, prefill=128,
                   n_lo=16, n_hi=528, repeats=4, kv_cache_dtype=None,
                   weight_quant=None):
    """n_hi - n_lo = 512 decode steps: host stalls must be small against
    the measured delta or the slope is noise.

    ``weight_quant``: "int8" | "int4" stores every projection weight-only
    quantized (nn.quant) — at batch 1 the parameter stream IS the HBM
    roofline, so this is decode's other halving lever next to the int8
    KV cache."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import llama

    pt.seed(0)
    model = llama(preset, max_position_embeddings=prefill + n_hi + 8,
                  dtype="bfloat16")
    model.astype("bfloat16")   # cfg.dtype sets cache dtype only; decode is
    model.eval()               # bandwidth-bound, params must be bf16 too
    if weight_quant:
        from paddle_tpu.nn.quant import quantize_linears
        n = quantize_linears(model, algo=f"weight_only_{weight_quant}")
        print(f"# weight_quant={weight_quant}: {n} linears", flush=True)
    ids = jax.random.randint(jax.random.key(1), (batch, prefill), 0,
                             model.cfg.vocab_size)

    def run(n):
        out = model.generate(ids, max_new_tokens=n,
                             kv_cache_dtype=kv_cache_dtype)
        jax.block_until_ready(out)
        return out

    # compile both lengths
    run(n_lo), run(n_hi)

    def timed(n):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = run(n)
            _ = int(np.asarray(out)[0, -1])  # wait for the device
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo, t_hi = timed(n_lo), timed(n_hi)
    for _ in range(3):
        if t_hi > t_lo:
            break
        # a host stall poisoned a window (negative slope): re-measure
        t_lo, t_hi = min(t_lo, timed(n_lo)), min(t_hi, timed(n_hi))
    per_tok = (t_hi - t_lo) / (n_hi - n_lo)
    return {"metric": "decode_tokens_per_sec", "preset": preset,
            "kv": str(kv_cache_dtype or "bf16"),
            "batch": batch, "prefill": prefill,
            "ms_per_token": round(1000 * per_tok, 3),
            "tokens_per_sec": round(batch / per_tok, 1),
            "sec_lo": round(t_lo, 3), "sec_hi": round(t_hi, 3),
            "decode_lens": [n_lo, n_hi]}


def bench_serve(preset="llama-350m", max_batch=8, n_requests=None,
                prompt_lens=(16, 96, 32, 128, 64, 48, 112, 80),
                max_new=64, page_size=16, repeats=2,
                kv_cache_dtype=None):
    """Aggregate continuous-batching decode throughput (serving.Engine).

    The serving headline: ``n_requests`` mixed-length prompts (default
    3x the slot count, cycling through ``prompt_lens``) drain through
    one warmed engine, so the batch churns — requests join and leave
    mid-flight — for the whole window.  Reported tokens/sec is the
    AGGREGATE across the batch: total generated tokens / wall-clock from
    first step to drain (prefills included, compilation excluded) — the
    number that moves when continuous batching works, as opposed to
    ``decode_bs1``'s per-sequence latency."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if n_requests is None:
        n_requests = 3 * max_batch
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(n_requests)]
    max_seq_len = max(lens) + max_new
    pt.seed(0)
    model = llama(preset, max_position_embeddings=max_seq_len,
                  dtype="bfloat16")
    model.astype("bfloat16")
    eng = serving.Engine(model, max_batch=max_batch,
                         max_seq_len=max_seq_len, page_size=page_size,
                         kv_cache_dtype=kv_cache_dtype).warmup()
    rng = np.random.default_rng(0)

    def one_pass():
        rids = [eng.add_request(
            rng.integers(0, model.cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=max_new) for n in lens]
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
        assert eng.kv_blocks_used == 0, "KV blocks leaked at drain"
        return sum(len(outs[r]) for r in rids), dt

    best, tokens = float("inf"), 0
    for _ in range(repeats):
        tokens, dt = one_pass()
        best = min(best, dt)
    return {"metric": "serve_continuous_batching_tok_s", "preset": preset,
            "kv": str(kv_cache_dtype or "bf16"),
            "max_batch": max_batch, "requests": n_requests,
            "prompt_lens": sorted(set(lens)), "max_new_tokens": max_new,
            "page_size": page_size, "gen_tokens": tokens,
            "wall_s": round(best, 3),
            "agg_tokens_per_sec": round(tokens / best, 1)}


def bench_serve_prefix(preset="llama-350m", max_batch=8, n_requests=None,
                       shared_prefix=96, tail_lens=(8, 24, 16, 32),
                       max_new=48, page_size=16, prefill_chunk=32,
                       kv_cache_dtype=None):
    """Shared-prefix / bursty-admission serving benchmark: the
    millions-of-users-one-system-prompt workload plus the TTFT story.

    ``n_requests`` (default 3x the slot count) prompts share a
    ``shared_prefix``-token head (the "system prompt") with mixed-length
    unique tails, and are ALL submitted before the first step — a burst,
    so admission pressure and time-in-queue land in TTFT.  Two passes
    through one warmed engine: the cold pass populates the prefix cache,
    the warm pass hits it — the delta in prefill work shows up as
    warm-vs-cold TTFT p95 and the reported hit rate.  Chunked prefill
    (the ragged unified step) keeps decode flowing during the burst,
    which is what bounds TTFT p95 under load in the first place."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if n_requests is None:
        n_requests = 3 * max_batch
    tails = [tail_lens[i % len(tail_lens)] for i in range(n_requests)]
    max_seq_len = shared_prefix + max(tails) + max_new
    pt.seed(0)
    model = llama(preset, max_position_embeddings=max_seq_len,
                  dtype="bfloat16")
    model.astype("bfloat16")
    eng = serving.Engine(model, max_batch=max_batch,
                         max_seq_len=max_seq_len, page_size=page_size,
                         prefill_chunk=prefill_chunk,
                         kv_cache_dtype=kv_cache_dtype).warmup()
    rng = np.random.default_rng(0)
    common = rng.integers(0, model.cfg.vocab_size,
                          size=shared_prefix).astype(np.int32)

    def one_pass(tag):
        hits0 = eng.prefix_stats()["hits"]
        rids = [eng.add_request(
            np.concatenate([common, rng.integers(
                0, model.cfg.vocab_size, size=t).astype(np.int32)]),
            max_new_tokens=max_new) for t in tails]   # bursty: all queued
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
        assert eng.kv_blocks_used == 0, "KV blocks leaked at drain"
        # pdtpu-lint: disable=lock-discipline — single-threaded bench
        ttfts = sorted(
            (eng._states[r].first_token_t - eng._states[r].submit_t) * 1e3
            for r in rids)
        p = lambda q: ttfts[min(len(ttfts) - 1,
                                int(q / 100 * len(ttfts)))]  # noqa: E731
        st = eng.prefix_stats()
        # sampled request-lifecycle attribution (one request per pass):
        # the BENCH round carries WHERE the cold vs prefix-warm request
        # spent its time (queue/prefill/decode), not just aggregates —
        # bench.py forwards it to the bench_telemetry.jsonl sidecar
        from paddle_tpu import observability as obs
        tracer = obs.get_request_tracer()
        trace = None
        if tracer is not None:
            tl = tracer.timeline(rids[0])
            if tl is not None:
                trace = {"id": rids[0], **tl["summary"]}
        return {f"{tag}_ttft_p50_ms": round(p(50), 2),
                f"{tag}_ttft_p95_ms": round(p(95), 2),
                f"{tag}_agg_tokens_per_sec": round(
                    sum(len(outs[r]) for r in rids) / dt, 1),
                f"{tag}_prefix_hits": st["hits"] - hits0,
                f"{tag}_trace": trace}

    out = {"metric": "serve_shared_prefix_ttft", "preset": preset,
           "kv": str(kv_cache_dtype or "bf16"), "max_batch": max_batch,
           "requests": n_requests, "shared_prefix": shared_prefix,
           "tail_lens": sorted(set(tails)), "max_new_tokens": max_new,
           "page_size": page_size, "prefill_chunk": prefill_chunk}
    out.update(one_pass("cold"))
    out.update(one_pass("warm"))
    st = eng.prefix_stats()
    probes = st["hits"] + st["misses"]
    out["prefix_hit_rate"] = round(st["hits"] / probes, 3) if probes else 0.0
    out["cow_copies"] = st["cow_copies"]
    return out


def bench_serve_burst(preset="llama-350m", max_batch=8, offered=None,
                      prompt_lens=(24, 64, 40, 96), max_new=32,
                      page_size=16, max_queue_depth=None,
                      kv_cache_dtype=None):
    """Overload serving benchmark: offered load ABOVE capacity through
    the bounded front door (docs/SERVING.md "Front door").

    ``offered`` requests (default 6x the slot count) hit a FrontDoor
    whose queue bound (default 2x the slot count) is far below the
    burst, so most of it sheds with a typed retry-after answer and the
    admitted remainder drains.  The numbers a fleet sizes against:
    GOODPUT tok/s (generated tokens over wall-clock — what survived the
    overload), the SHED RATE (offered minus admitted over offered), and
    TTFT p95 FOR ADMITTED requests (the latency the accepted traffic
    actually saw while the door was slamming)."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if offered is None:
        offered = 6 * max_batch
    if max_queue_depth is None:
        max_queue_depth = 2 * max_batch
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(offered)]
    max_seq_len = max(lens) + max_new
    pt.seed(0)
    model = llama(preset, max_position_embeddings=max_seq_len,
                  dtype="bfloat16")
    model.astype("bfloat16")
    eng = serving.Engine(model, max_batch=max_batch,
                         max_seq_len=max_seq_len, page_size=page_size,
                         kv_cache_dtype=kv_cache_dtype).warmup()
    door = serving.FrontDoor(eng, max_queue_depth=max_queue_depth)
    rng = np.random.default_rng(0)

    admitted, sheds = [], 0
    t0 = time.perf_counter()
    for n in lens:
        a = door.submit(rng.integers(0, model.cfg.vocab_size,
                                     size=n).astype(np.int32),
                        max_new_tokens=max_new)
        if a.admitted:
            admitted.append(a.request_id)
        else:
            sheds += 1
            assert a.retry_after_s and a.retry_after_s > 0, \
                "shed without a retry-after answer"
    outs = door.run()
    dt = time.perf_counter() - t0
    assert eng.kv_blocks_used == 0, "KV blocks leaked at drain"
    tokens = sum(len(outs[r]) for r in admitted)
    # pdtpu-lint: disable=lock-discipline — single-threaded bench driver
    ttfts = sorted(
        (eng._states[r].first_token_t - eng._states[r].submit_t) * 1e3
        for r in admitted)
    p = lambda q: ttfts[min(len(ttfts) - 1,
                            int(q / 100 * len(ttfts)))]  # noqa: E731
    return {"metric": "serve_burst_goodput", "preset": preset,
            "kv": str(kv_cache_dtype or "bf16"), "max_batch": max_batch,
            "offered": offered, "admitted": len(admitted),
            "shed": sheds, "shed_rate": round(sheds / offered, 3),
            "max_queue_depth": max_queue_depth,
            "max_new_tokens": max_new, "page_size": page_size,
            "gen_tokens": tokens, "wall_s": round(dt, 3),
            "goodput_tok_s": round(tokens / dt, 1),
            "admitted_ttft_p50_ms": round(p(50), 2),
            "admitted_ttft_p95_ms": round(p(95), 2)}


def bench_serve_tp(preset="llama-350m", tp=2, max_batch=8, n_requests=None,
                   prompt_lens=(16, 96, 32, 128, 64, 48, 112, 80),
                   max_new=64, page_size=16, repeats=2,
                   kv_cache_dtype=None):
    """TP-sharded continuous-batching throughput: the ``bench_serve``
    churn workload through ONE engine whose compiled step is
    GSPMD-partitioned over a ``tp``-device mesh (params by their
    partition specs, paged KV pools head-sharded — docs/SERVING.md
    "Sharded serving").  The number that matters on hardware: what a
    model too big for one chip serves at once it spans the mesh."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if n_requests is None:
        n_requests = 3 * max_batch
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(n_requests)]
    max_seq_len = max(lens) + max_new
    pt.seed(0)
    model = llama(preset, max_position_embeddings=max_seq_len,
                  dtype="bfloat16")
    model.astype("bfloat16")
    mesh = serving.serving_mesh(tp=tp)
    eng = serving.Engine(model, max_batch=max_batch,
                         max_seq_len=max_seq_len, page_size=page_size,
                         kv_cache_dtype=kv_cache_dtype, mesh=mesh).warmup()
    rng = np.random.default_rng(0)

    def one_pass():
        rids = [eng.add_request(
            rng.integers(0, model.cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=max_new) for n in lens]
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
        assert eng.kv_blocks_used == 0, "KV blocks leaked at drain"
        return sum(len(outs[r]) for r in rids), dt

    best, tokens = float("inf"), 0
    for _ in range(repeats):
        tokens, dt = one_pass()
        best = min(best, dt)
    return {"metric": "serve_tp_tok_s", "preset": preset, "tp": tp,
            "kv": str(kv_cache_dtype or "bf16"),
            "max_batch": max_batch, "requests": n_requests,
            "max_new_tokens": max_new, "page_size": page_size,
            "gen_tokens": tokens, "wall_s": round(best, 3),
            "agg_tokens_per_sec": round(tokens / best, 1)}


def bench_serve_dp(preset="llama-350m", replicas=2, tp=1, max_batch=8,
                   n_requests=None, prompt_lens=(24, 24, 24, 24),
                   max_new=32, page_size=8, kv_cache_dtype=None):
    """DP replica-set throughput: ``n_requests`` prompts routed across
    ``replicas`` engines (each ``tp`` devices) by the least-loaded /
    prefix-affinity router, against a single-replica baseline of the
    SAME per-replica config serving the same offered load.

    Two aggregate numbers per config: ``wall`` tok/s (generated tokens
    over this host's wall clock) and the PROJECTED tok/s — total tokens
    over the SLOWEST replica's own busy time (``Engine.busy_s``, each
    engine's dispatch+sync+bookkeeping seconds only).  On real hardware
    replicas own their chips and run concurrently, so projected ≈ wall;
    on the CPU plumbing run replicas time-slice one host, so wall is
    flat by construction and projected is the honest estimator of the
    deployed aggregate — the ``serve_dp_agg_tok_s`` headline and the
    ≥1.5x-of-single-replica bar the serving-dist plumbing asserts."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if n_requests is None:
        n_requests = 2 * replicas * max_batch
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(n_requests)]
    max_seq_len = max(lens) + max_new
    rng = np.random.default_rng(0)
    prompts = None

    def build_set(n_reps):
        # one submesh per replica even at tp=1 (a 1-device mesh): each
        # replica owns its devices, which is the deployed DP layout
        meshes = serving.replica_meshes(n_reps, tp)
        reps = []
        for m in meshes:
            pt.seed(0)
            model = llama(preset, max_position_embeddings=max_seq_len,
                          dtype="bfloat16")
            model.astype("bfloat16")
            reps.append(serving.Engine(
                model, max_batch=max_batch, max_seq_len=max_seq_len,
                page_size=page_size, kv_cache_dtype=kv_cache_dtype,
                mesh=m))
        return serving.EngineReplicaSet(reps).warmup(), reps

    def one_pass(n_reps):
        nonlocal prompts
        rset, reps = build_set(n_reps)
        if prompts is None:
            prompts = [rng.integers(0, reps[0].model.cfg.vocab_size,
                                    size=n).astype(np.int32) for n in lens]
        rids = [rset.add_request(p, max_new_tokens=max_new)
                for p in prompts]
        t0 = time.perf_counter()
        outs = rset.run()
        wall = time.perf_counter() - t0
        assert rset.kv_blocks_used == 0, "KV blocks leaked at drain"
        tokens = sum(len(outs[r]) for r in rids)
        return tokens, wall, max(r.busy_s for r in reps)

    base_tokens, base_wall, base_busy = one_pass(1)
    tokens, wall, busy = one_pass(replicas)
    agg = round(tokens / busy, 1)
    single = round(base_tokens / base_busy, 1)
    return {"metric": "serve_dp_agg_tok_s", "preset": preset,
            "replicas": replicas, "tp": tp,
            "kv": str(kv_cache_dtype or "bf16"), "max_batch": max_batch,
            "requests": n_requests, "max_new_tokens": max_new,
            "page_size": page_size, "gen_tokens": tokens,
            "wall_s": round(wall, 3),
            "agg_tokens_per_sec": agg,
            "wall_tokens_per_sec": round(tokens / wall, 1),
            "single_replica_tok_s": single,
            "single_replica_wall_s": round(base_wall, 3),
            "vs_single_replica": round(agg / single, 2) if single else None}


def bench_serve_disagg(preset="llama-350m", n_decode=2, max_batch=8,
                       n_requests=None,
                       prompt_lens=(96, 128, 112, 80), max_new=48,
                       page_size=16, kv_cache_dtype=None):
    """Disaggregated serving benchmark: bursty LONG-prompt admission
    against 1 prefill + N decode replicas (docs/SERVING.md
    "Disaggregated serving").

    The workload disaggregation exists for: every prompt is long (so
    prefill compute dominates admission) and the whole batch arrives as
    a burst.  Colocated, that burst stalls decode slots behind prefill
    chunks; split, the prefill replica chews the burst while decode
    replicas drain handoffs.  Three configurations run the same burst:
    a colocated single engine (the TTFT context row), then the disagg
    set at 1 and at ``n_decode`` decode replicas.

    Numbers: DECODE tok/s under the busy-time projection — decode-tier
    tokens over the slowest decode replica's own busy seconds
    (``Engine.busy_s``, the PR-8 estimator: on hardware each replica
    owns its chips so projected ≈ wall; on the CPU plumbing run
    replicas time-slice one host and wall is flat by construction) —
    and its scaling ``vs_1_decode``, plus admitted-TTFT p50/p95 per
    configuration.  The headline claim the plumbing test pins: decode
    throughput scales with the decode-replica count while admitted-TTFT
    p95 stays within noise of the 1-decode configuration (TTFT lives on
    the prefill tier, which did not change)."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if n_requests is None:
        n_requests = 3 * max_batch
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(n_requests)]
    max_seq_len = max(lens) + max_new
    rng = np.random.default_rng(0)
    prompts = None

    def build_engine(role):
        pt.seed(0)
        model = llama(preset, max_position_embeddings=max_seq_len,
                      dtype="bfloat16")
        model.astype("bfloat16")
        return serving.Engine(model, max_batch=max_batch,
                              max_seq_len=max_seq_len,
                              page_size=page_size,
                              kv_cache_dtype=kv_cache_dtype, role=role)

    def one_pass(engine_or_set, decoders):
        nonlocal prompts
        if prompts is None:
            vocab = decoders[0].model.cfg.vocab_size
            prompts = [rng.integers(0, vocab, size=n).astype(np.int32)
                       for n in lens]
        tgt = engine_or_set
        rids = [tgt.add_request(p, max_new_tokens=max_new)
                for p in prompts]            # bursty: all queued up front
        t0 = time.perf_counter()
        outs = tgt.run()
        wall = time.perf_counter() - t0
        assert tgt.kv_blocks_used == 0, "KV blocks leaked at drain"
        tokens = sum(len(outs[r]) for r in rids)
        # pdtpu-lint: disable=lock-discipline — single-threaded bench
        ttfts = sorted(
            (tgt._states[r].first_token_t - tgt._states[r].submit_t) * 1e3
            for r in rids)
        p = lambda q: ttfts[min(len(ttfts) - 1,
                                int(q / 100 * len(ttfts)))]  # noqa: E731
        # decode-tier busy-time projection: tokens the decode replicas
        # emitted over the slowest one's own busy seconds
        dec_tokens = sum(d.tokens_emitted for d in decoders)
        busy = max(d.busy_s for d in decoders)
        return {"tokens": tokens, "wall_s": round(wall, 3),
                "ttft_p50_ms": round(p(50), 2),
                "ttft_p95_ms": round(p(95), 2),
                "decode_tok_s": round(dec_tokens / max(busy, 1e-9), 1)}

    # colocated context row: one engine runs both phases
    colo = build_engine("both").warmup()
    colo_r = one_pass(colo, [colo])

    def disagg_pass(n_dec):
        pre = [build_engine("prefill")]
        dec = [build_engine("decode") for _ in range(n_dec)]
        ds = serving.DisaggReplicaSet(pre, dec).warmup()
        r = one_pass(ds, dec)
        r["handoffs"] = ds.disagg_stats()["handoffs"]
        r["xfer_bytes"] = ds.disagg_stats()["xfer_bytes"]
        return r

    base = disagg_pass(1)
    scaled = disagg_pass(n_decode)
    return {"metric": "serve_disagg", "preset": preset,
            "kv": str(kv_cache_dtype or "bf16"), "max_batch": max_batch,
            "requests": n_requests, "prompt_lens": sorted(set(lens)),
            "max_new_tokens": max_new, "page_size": page_size,
            "n_decode": n_decode,
            "decode_tok_s": scaled["decode_tok_s"],
            "vs_1_decode": round(
                scaled["decode_tok_s"] / base["decode_tok_s"], 2)
            if base["decode_tok_s"] else None,
            "ttft_p50_ms": scaled["ttft_p50_ms"],
            "ttft_p95_ms": scaled["ttft_p95_ms"],
            "ttft_p95_1_decode_ms": base["ttft_p95_ms"],
            "ttft_p95_colocated_ms": colo_r["ttft_p95_ms"],
            "gen_tokens": scaled["tokens"], "wall_s": scaled["wall_s"],
            "handoffs": scaled["handoffs"],
            "xfer_bytes": scaled["xfer_bytes"],
            "decode_tok_s_1_decode": base["decode_tok_s"],
            "colocated_tok_s": colo_r["decode_tok_s"]}


def bench_serve_spec(preset="llama-350m", max_batch=8, n_requests=None,
                     motif_len=12, motif_reps=4, max_new=64,
                     draft_depth=4, page_size=16,
                     kv_cache_dtype=None):
    """Speculative-decoding serving benchmark: the same continuous-
    batching drain run spec-OFF then spec-ON (n-gram self-drafting
    through the one compiled verify step — docs/SERVING.md "Speculative
    decoding"), on a REPETITIVE workload where history predicts the
    continuation (looping motifs — the code/templated-prose shape
    n-gram drafting exists for).

    The numbers: per-engine aggregate tok/s (wall), the ACCEPTANCE RATE
    (accepted / proposed draft tokens), and TOKENS PER VERIFY STEP
    (1 + accepted/verifies — what one weight-streaming pass buys; > 1.0
    means speculation is paying).  On hardware the tok/s ratio is the
    headline (decode is bandwidth-bound, verify flops are spare); on
    the CPU plumbing run the verify pass costs real host time, so
    tokens-per-step is the honest signal there and the plumbing test
    asserts it > 1.0.  Greedy outputs are asserted token-identical
    between the two engines — speculation is a perf lever, never a
    quality trade."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if n_requests is None:
        n_requests = 2 * max_batch
    max_seq_len = motif_len * motif_reps + max_new
    pt.seed(0)
    model = llama(preset, max_position_embeddings=max_seq_len,
                  dtype="bfloat16")
    model.astype("bfloat16")
    rng = np.random.default_rng(0)
    # looping prompts: per-request motif tiled motif_reps times, so the
    # n-gram index has matches from the very first decode step
    prompts = [np.tile(rng.integers(0, model.cfg.vocab_size,
                                    size=motif_len).astype(np.int32),
                       motif_reps) for _ in range(n_requests)]

    def one_pass(spec):
        eng = serving.Engine(model, max_batch=max_batch,
                             max_seq_len=max_seq_len, page_size=page_size,
                             kv_cache_dtype=kv_cache_dtype,
                             spec_decode=spec,
                             draft_depth=draft_depth).warmup()
        rids = [eng.add_request(p, max_new_tokens=max_new)
                for p in prompts]
        t0 = time.perf_counter()
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
        dt = time.perf_counter() - t0
        assert eng.kv_blocks_used == 0, "KV blocks leaked at drain"
        outs = [eng.output_ids(r) for r in rids]
        return outs, sum(len(o) for o in outs), dt, steps, \
            eng.spec_stats()

    base_outs, base_tokens, base_dt, base_steps, _ = one_pass(False)
    outs, tokens, dt, steps, st = one_pass(True)
    assert outs == base_outs, \
        "speculative greedy outputs diverged from the plain engine"
    verifies = st["verifies"] or 1
    return {"metric": "serve_spec_decode", "preset": preset,
            "kv": str(kv_cache_dtype or "bf16"), "max_batch": max_batch,
            "requests": n_requests, "max_new_tokens": max_new,
            "draft_depth": draft_depth,
            "motif": f"{motif_len}x{motif_reps}",
            "gen_tokens": tokens, "wall_s": round(dt, 3),
            "agg_tokens_per_sec": round(tokens / dt, 1),
            "base_tokens_per_sec": round(base_tokens / base_dt, 1),
            "vs_spec_off": round((tokens / dt) / (base_tokens / base_dt),
                                 2),
            "steps": steps, "base_steps": base_steps,
            "proposed": st["proposed"], "accepted": st["accepted"],
            "accept_rate": round(st["accept_rate"], 3),
            "tokens_per_verify_step": round(
                1.0 + st["accepted"] / verifies, 2)}


def bench_serve_lora(preset="llama-350m", n_adapters=3, rank=8,
                     max_batch=8, n_requests=None,
                     prompt_lens=(16, 40, 24, 32), max_new=32,
                     page_size=16, kv_cache_dtype=None):
    """Batched multi-LoRA serving benchmark: N adapters + the base model
    mixed in ONE engine vs the status-quo SERIAL deployment — one
    merged-weight engine per tenant model (docs/SERVING.md
    "Multi-LoRA").

    The workload: ``n_requests`` prompts arriving round-robin across
    base + ``n_adapters`` tenants.  BATCHED, all of them share one
    engine's slots, cache and compiled step (per-slot adapter ids index
    the stacked pools through the grouped BGMV).  SERIAL, each tenant's
    share runs through its own dedicated engine — so every engine's
    batch is ~(tenants)x emptier and each token pays a ~full step of
    dispatch work.  The numbers: batched tok/s over the one engine's
    own busy seconds vs the serial projection (total tokens over the
    SUMMED busy seconds of the per-tenant engines — they'd time-share
    the same chip, the PR-8 busy-time estimator).  ``vs_serial`` is the
    headline the plumbing test pins at >= 1.3x on CPU; identity is
    asserted in-bench (batched outputs == each serial engine's)."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    if n_requests is None:
        n_requests = 2 * max_batch
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(n_requests)]
    max_seq_len = max(lens) + max_new
    rng = np.random.default_rng(0)

    def build_model():
        pt.seed(0)
        m = llama(preset, max_position_embeddings=max_seq_len,
                  dtype="bfloat16")
        m.astype("bfloat16")
        return m

    model = build_model()
    names = [f"lora-{i}" for i in range(n_adapters)]
    weights = {n: serving.random_adapter(
        model, rank=rank, rng=np.random.default_rng(100 + i),
        scale=0.02) for i, n in enumerate(names)}
    tenants = [None] + names                     # base + adapters
    prompts = [rng.integers(0, model.cfg.vocab_size,
                            size=n).astype(np.int32) for n in lens]
    assign = [tenants[i % len(tenants)] for i in range(n_requests)]

    # batched: one engine, one stacked pool, mixed-adapter churn
    pool = serving.LoRAPool(model, max_adapters=n_adapters, rank=rank)
    for n in names:
        pool.load(n, weights[n])
    beng = serving.Engine(model, max_batch=max_batch,
                          max_seq_len=max_seq_len, page_size=page_size,
                          kv_cache_dtype=kv_cache_dtype,
                          lora=pool).warmup()
    rids = [beng.add_request(p, max_new_tokens=max_new, adapter=ad)
            for p, ad in zip(prompts, assign)]
    t0 = time.perf_counter()
    bouts = beng.run()
    bwall = time.perf_counter() - t0
    assert beng.kv_blocks_used == 0, "KV blocks leaked at drain"
    btokens = sum(len(bouts[r]) for r in rids)

    # serial: one merged-weight engine per tenant, each serving only
    # its own share of the same offered load
    serial_busy = 0.0
    serial_tokens = 0
    serial_wall = 0.0
    for ad in tenants:
        m = build_model()
        if ad is not None:
            serving.merge_adapter(m, weights[ad])
        seng = serving.Engine(m, max_batch=max_batch,
                              max_seq_len=max_seq_len,
                              page_size=page_size,
                              kv_cache_dtype=kv_cache_dtype).warmup()
        mine = [(p, r) for p, a, r in zip(prompts, assign, rids)
                if a == ad]
        srids = [seng.add_request(p, max_new_tokens=max_new)
                 for p, _ in mine]
        t0 = time.perf_counter()
        souts = seng.run()
        serial_wall += time.perf_counter() - t0
        assert seng.kv_blocks_used == 0, "KV blocks leaked at drain"
        serial_busy += seng.busy_s
        serial_tokens += sum(len(souts[r]) for r in srids)
        for (p, brid), srid in zip(mine, srids):
            assert bouts[brid] == souts[srid], \
                f"batched output diverged from the serial " \
                f"{'base' if ad is None else ad} engine"
    batched = btokens / max(beng.busy_s, 1e-9)
    serial = serial_tokens / max(serial_busy, 1e-9)
    return {"metric": "serve_lora", "preset": preset,
            "kv": str(kv_cache_dtype or "bf16"), "max_batch": max_batch,
            "requests": n_requests, "adapters": n_adapters,
            "rank": rank, "max_new_tokens": max_new,
            "page_size": page_size, "gen_tokens": btokens,
            "wall_s": round(bwall, 3),
            "batched_tok_s": round(batched, 1),
            "serial_tok_s": round(serial, 1),
            "serial_wall_s": round(serial_wall, 3),
            "vs_serial": round(batched / serial, 2) if serial else None,
            "active_adapters": pool.active_adapters}


def bench_decode_attention(batch=8, heads=16, head_dim=64, ctx=1024,
                           block_size=64, iters=200):
    """Paged vs contiguous decode attention, op-level, slope-amortized."""
    from paddle_tpu.incubate.nn import functional as IF

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal(
        (batch, heads, head_dim)).astype("float32"))
    kc = jnp.asarray(rng.standard_normal(
        (batch, ctx, heads, head_dim)).astype("float32"))
    vc = jnp.asarray(rng.standard_normal(
        (batch, ctx, heads, head_dim)).astype("float32"))
    lens = jnp.full((batch,), ctx, jnp.int32)

    n_blocks = ctx // block_size
    k_pool = kc.reshape(batch * n_blocks, block_size, heads, head_dim)
    v_pool = vc.reshape(batch * n_blocks, block_size, heads, head_dim)
    tables = jnp.arange(batch * n_blocks, dtype=jnp.int32).reshape(
        batch, n_blocks)

    def loop(fn, *args):
        def body(x, _):
            out = fn(*args)
            return x + out.sum(), None
        return jax.lax.scan(body, jnp.zeros(()), None, length=iters)[0]

    def contiguous(q=q):
        return IF.masked_multihead_attention(q, kc, vc, lens)[0]

    def paged(q=q):
        return IF.paged_attention(q, k_pool, v_pool, tables, lens)

    out = {}
    for name, fn in (("contiguous_masked", contiguous), ("paged", paged)):
        # one fresh jit per benchmarked variant is the point here: each
        # is compiled, warmed, and timed exactly once (two iterations)
        # pdtpu-lint: disable=retrace-hazard — deliberate per-variant jit
        jitted = jax.jit(lambda fn=fn: loop(fn))
        try:
            _ = float(jitted())            # compile + warm
            t0 = time.perf_counter()
            _ = float(jitted())
            dt = time.perf_counter() - t0
            out[name + "_us_per_call"] = round(1e6 * dt / iters, 1)
        except Exception as e:  # noqa: BLE001
            out[name + "_error"] = str(e)[:200]
    out.update({"metric": "decode_attention_paged_vs_contiguous",
                "batch": batch, "ctx": ctx, "heads": heads,
                "head_dim": head_dim, "block_size": block_size})
    return out


def main():
    for batch in (1, 8):
        print(json.dumps(bench_generate(batch=batch)), flush=True)
    # int8 KV cache: halves the dominant decode traffic (docs/BENCH.md)
    for batch in (1, 8):
        print(json.dumps(bench_generate(batch=batch,
                                        kv_cache_dtype="int8")), flush=True)
    # weight-only int8 stacked with the int8 KV cache: both halves of the
    # decode HBM stream quantized (bs1 = params-dominated, bs8 = cache)
    for batch in (1, 8):
        print(json.dumps(bench_generate(batch=batch, kv_cache_dtype="int8",
                                        weight_quant="int8")), flush=True)
    # continuous batching: the aggregate serving number next to the
    # per-sequence decode rows (bf16 and the int8-KV serving point)
    print(json.dumps(bench_serve()), flush=True)
    print(json.dumps(bench_serve(kv_cache_dtype="int8")), flush=True)
    # shared-prefix burst: prefix-cache hit rate + TTFT under load
    print(json.dumps(bench_serve_prefix(kv_cache_dtype="int8")), flush=True)
    # overload: offered > capacity through the bounded front door —
    # goodput, shed rate, TTFT p95 for the admitted traffic
    print(json.dumps(bench_serve_burst(kv_cache_dtype="int8")), flush=True)
    # speculative decoding: n-gram self-drafting through the one
    # compiled verify step on a repetitive workload — acceptance rate
    # and tokens-per-verify-step next to the spec-off baseline
    print(json.dumps(bench_serve_spec(kv_cache_dtype="int8")), flush=True)
    # disaggregated serving: bursty long-prompt admission against
    # 1 prefill + N decode replicas — decode tok/s scaling with N while
    # admitted-TTFT p95 stays flat (docs/SERVING.md "Disaggregated
    # serving")
    print(json.dumps(bench_serve_disagg(kv_cache_dtype="int8")),
          flush=True)
    # batched multi-LoRA: N adapters + base mixed in one engine vs the
    # serial one-merged-engine-per-tenant deployment (docs/SERVING.md
    # "Multi-LoRA")
    print(json.dumps(bench_serve_lora(kv_cache_dtype="int8")),
          flush=True)
    # sharded serving (docs/SERVING.md "Sharded serving"): TP-partitioned
    # engine + DP replica routing — needs a multi-chip slice
    if len(jax.devices()) >= 2:
        print(json.dumps(bench_serve_tp(tp=2, kv_cache_dtype="int8")),
              flush=True)
        print(json.dumps(bench_serve_dp(replicas=2,
                                        kv_cache_dtype="int8")),
              flush=True)
    print(json.dumps(bench_decode_attention()), flush=True)


if __name__ == "__main__":
    main()
