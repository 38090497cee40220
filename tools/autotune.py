#!/usr/bin/env python
"""Block-shape / fusion / serving-knob autotuner for the fused-kernel
library (docs/KERNELS.md "Autotuning").

Generalizes tools/tune_sweep.py: per (model preset, backend) it sweeps

- Pallas block shapes for the fused kernels (TPU only — on CPU the
  kernels run the Pallas interpreter, whose timings say nothing about
  Mosaic, so blocks keep their defaults there);
- fusion on/off per op: the fused entry point vs the unfused eager
  composition, timed as separate dispatches (the honest A/B — inside
  one jit XLA hides the boundary).  A measured loss records
  ``{"enabled": false}`` which ``fused_ops="auto"`` models respect;
- serving knobs: KV page size × prefill-chunk C on a small
  continuous-batching drain through a warmed Engine.

Winners persist to ``tools/tuned_configs.json`` under the backend key —
the file ``paddle_tpu.ops.tuning`` reads ONCE at trace/construction
time.  Re-run after a hardware or shape change:

    python tools/autotune.py --preset llama-350m --update
    python tools/autotune.py --ops serving --update     # knobs only

Without ``--update`` the sweep prints its table and JSON but writes
nothing.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tuned_configs.json")


def _time(f, *args, iters=20, reps=3):
    out = f(*args)
    _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(*args)
        _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1000  # ms


def _geometry(preset):
    from paddle_tpu.models.llama import PRESETS
    cfg = PRESETS[preset]
    return dict(h=cfg.hidden_size, i=cfg.intermediate_size,
                hd=cfg.head_dim,
                nq=cfg.num_attention_heads * cfg.head_dim,
                nk=cfg.num_key_value_heads * cfg.head_dim,
                eps=cfg.rms_norm_eps, layers=cfg.num_hidden_layers,
                kv_heads=cfg.num_key_value_heads)


def _operands(geom, t, dtype):
    r = np.random.default_rng(0)

    def arr(*shape, scale=0.05):
        return jnp.asarray(r.normal(size=shape) * scale, dtype)

    h, i, hd, nq, nk = (geom["h"], geom["i"], geom["hd"], geom["nq"],
                        geom["nk"])
    x = arr(t, h, scale=1.0)
    gw = jnp.ones((h,), dtype)
    pos = np.arange(t)
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    fr = np.einsum("s,d->sd", pos, inv)
    emb = np.concatenate([fr, fr], -1)
    return dict(
        x=x, gw=gw,
        wq=arr(h, nq), wk=arr(h, nk), wv=arr(h, nk),
        cos=jnp.asarray(np.cos(emb), dtype),
        sin=jnp.asarray(np.sin(emb), dtype),
        wg=arr(h, i), wu=arr(h, i), wd=arr(i, h))


def sweep_fusion(preset, t, dtype, iters):
    """Fused entry point vs unfused eager composition, per op — the
    round-trips the fused op is supposed to delete are only visible
    across dispatch boundaries, so each leg is its own jit."""
    from paddle_tpu.incubate.nn import functional as IF
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops import tuning

    geom = _geometry(preset)
    ops = _operands(geom, t, dtype)
    hd, eps = geom["hd"], geom["eps"]

    # unfused compositions: each stage a separate dispatch, the shape of
    # the pre-fusion model path (norm / three projections / rope)
    norm = jax.jit(lambda x, g: F.rms_norm(x, g, eps))
    proj = jax.jit(lambda x, w: x @ w)
    rope = jax.jit(F.apply_rotary_pos_emb)

    def unfused_qkv(x, gw, wq, wk, wv, cos, sin):
        # the pre-fusion model path: norm, three projections, then the
        # rope pass — four separate dispatches over the hidden states
        nx = norm(x, gw)
        q, k, v = proj(nx, wq), proj(nx, wk), proj(nx, wv)
        tq = q.reshape(1, t, geom["nq"] // hd, hd)
        tk = k.reshape(1, t, geom["nk"] // hd, hd)
        qr, kr = rope(tq, tk, cos, sin)
        return qr, kr, v

    fused_qkv = jax.jit(lambda x, gw, wq, wk, wv, cos, sin:
                        IF.fused_rms_rope_qkv(x, gw, wq, wk, wv, cos,
                                              sin, hd, eps))

    swi = jax.jit(lambda g, u: F.swiglu(g, u))

    def unfused_mlp(x, wg, wu, wd):
        return proj(swi(proj(x, wg), proj(x, wu)), wd)

    fused_mlp = jax.jit(IF.fused_swiglu_mlp)

    results = {}
    cases = {
        "fused_rms_rope_qkv": (
            tuning.geom_key(h=geom["h"], nq=geom["nq"], nk=geom["nk"],
                            hd=hd),
            lambda: _time(unfused_qkv, ops["x"], ops["gw"], ops["wq"],
                          ops["wk"], ops["wv"], ops["cos"], ops["sin"],
                          iters=iters),
            lambda: _time(fused_qkv, ops["x"], ops["gw"], ops["wq"],
                          ops["wk"], ops["wv"], ops["cos"], ops["sin"],
                          iters=iters)),
        "fused_swiglu_mlp": (
            tuning.geom_key(h=geom["h"], i=geom["i"]),
            lambda: _time(unfused_mlp, ops["x"], ops["wg"], ops["wu"],
                          ops["wd"], iters=iters),
            lambda: _time(fused_mlp, ops["x"], ops["wg"], ops["wu"],
                          ops["wd"], iters=iters)),
    }
    for op, (key, run_unfused, run_fused) in cases.items():
        # interleave the legs and keep the per-leg best: the process's
        # first measured leg pays thread-pool/turbo ramp-up, which
        # otherwise biases the ratio by 2x (observed on this container)
        fused = run_fused()
        base = run_unfused()
        fused = min(fused, run_fused())
        base = min(base, run_unfused())
        speedup = base / fused if fused else 0.0
        results[op] = {key: {"enabled": bool(speedup >= 1.0),
                             "speedup": round(speedup, 3),
                             "unfused_ms": round(base, 4),
                             "fused_ms": round(fused, 4)}}
    return results


def sweep_blocks(preset, t, dtype, iters):
    """Pallas block shapes, TPU only (interpret-mode timings on CPU say
    nothing about Mosaic)."""
    if jax.default_backend() != "tpu":
        print("# block sweep skipped: backend is "
              f"{jax.default_backend()!r} (kernels run interpreted)")
        return {}
    from paddle_tpu.ops.pallas import fused_mlp as FM
    from paddle_tpu.ops.pallas import fused_norm_qkv as FQ
    from paddle_tpu.ops import tuning

    geom = _geometry(preset)
    ops = _operands(geom, t, dtype)
    hd, eps = geom["hd"], geom["eps"]
    results = {}

    key = tuning.geom_key(h=geom["h"], nq=geom["nq"], nk=geom["nk"],
                          hd=hd)
    best = (float("inf"), None)
    for bt in (128, 256, 512, 1024):
        try:
            # pdtpu-lint: disable=retrace-hazard — one compile per swept config, by design
            ms = _time(jax.jit(lambda x, *a, _bt=bt: FQ.fused_rms_rope_qkv(
                x, *a, hd, eps=eps, block_t=_bt)),
                ops["x"], ops["gw"], ops["wq"], ops["wk"], ops["wv"],
                ops["cos"], ops["sin"], iters=iters)
        except Exception as e:  # noqa: BLE001 — VMEM overflow etc.
            print(f"# fused_rms_rope_qkv bt={bt}: {type(e).__name__}")
            continue
        print(f"# fused_rms_rope_qkv bt={bt}: {ms:.3f} ms")
        best = min(best, (ms, bt))
    if best[1] is not None:
        results["fused_rms_rope_qkv"] = {key: {"block_t": best[1]}}

    key = tuning.geom_key(h=geom["h"], i=geom["i"])
    best = (float("inf"), None)
    for bt in (128, 256, 512):
        for bi in (256, 512, 1024):
            try:
                # pdtpu-lint: disable=retrace-hazard — one compile per swept config, by design
                ms = _time(jax.jit(
                    lambda x, *a, _bt=bt, _bi=bi: FM.fused_swiglu_mlp(
                        x, *a, block_t=_bt, block_i=_bi)),
                    ops["x"], ops["wg"], ops["wu"], ops["wd"],
                    iters=iters)
            except Exception as e:  # noqa: BLE001
                print(f"# fused_swiglu_mlp bt={bt} bi={bi}: "
                      f"{type(e).__name__}")
                continue
            print(f"# fused_swiglu_mlp bt={bt} bi={bi}: {ms:.3f} ms")
            best = min(best, (ms, (bt, bi)))
    if best[1] is not None:
        results["fused_swiglu_mlp"] = {key: {"block_t": best[1][0],
                                             "block_i": best[1][1]}}

    # grouped BGMV (multi-LoRA decode, ops/pallas/lora_matmul.py): the
    # expand stripe width over d_out, at the serving shapes — decode
    # span batches (B slots x chunk C) against a stacked pool
    from paddle_tpu.ops.pallas import lora_matmul as LM
    r_ = np.random.default_rng(0)
    bsz, c, rank, n_ad = 8, 16, 16, 9
    h, nq = geom["h"], geom["nq"]
    lx = jnp.asarray(r_.normal(size=(bsz, c, h)), dtype)
    la = jnp.asarray(r_.normal(size=(n_ad, h, rank)) * 0.05, dtype)
    lb = jnp.asarray(r_.normal(size=(n_ad, rank, nq)) * 0.05, dtype)
    lidx = jnp.asarray(r_.integers(0, n_ad, size=(bsz,)).astype(np.int32))
    key = tuning.geom_key(h=h, r=rank, o=nq)
    best = (float("inf"), None)
    for bo in (256, 512, 1024, 2048):
        if bo > nq:
            continue
        try:
            # one compile per swept config, by design (grouped_bgmv is
            # its own jit entry with block_o static)
            ms = _time(lambda x_, a_, b_, i_, _bo=bo: LM.grouped_bgmv(
                x_, a_, b_, i_, block_o=_bo), lx, la, lb, lidx,
                iters=iters)
        except Exception as e:  # noqa: BLE001 — VMEM overflow etc.
            print(f"# lora_bgmv bo={bo}: {type(e).__name__}")
            continue
        print(f"# lora_bgmv bo={bo}: {ms:.3f} ms")
        best = min(best, (ms, bo))
    if best[1] is not None:
        results["lora_bgmv"] = {key: {"block_o": best[1]}}
    return results


def sweep_serving(preset, on_tpu):
    """Page size × prefill chunk on a small continuous-batching drain.
    Engines are built per combo and timed over one warmed pass."""
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama
    from paddle_tpu.ops import tuning

    if on_tpu:
        sp, lens, max_new, batch = preset, (16, 96, 32, 128), 48, 8
        pages, chunks = (16, 64, 128), (16, 32, 64)
    else:
        # CPU: the tiny plumbing geometry the tests/gates run
        sp, lens, max_new, batch = "tiny", (5, 17, 9, 26), 8, 4
        pages, chunks = (8, 16), (8, 16)
    max_seq = max(lens) + max_new
    rng = np.random.default_rng(0)
    best = (float("inf"), None)
    rows = []
    for page in pages:
        for chunk in chunks:
            if page > max_seq or chunk > max_seq:
                continue
            pt.seed(0)
            model = llama(sp, max_position_embeddings=max_seq)
            eng = serving.Engine(model, max_batch=batch,
                                 max_seq_len=max_seq, page_size=page,
                                 prefill_chunk=chunk).warmup()
            prompts = [rng.integers(0, model.cfg.vocab_size,
                                    size=n).astype(np.int32)
                       for n in (lens * 3)[:3 * batch]]
            for p in prompts:   # warm pass: compile + prefix-cache fill
                eng.add_request(p, max_new_tokens=max_new)
            eng.run()
            t0 = time.perf_counter()
            for p in prompts:
                eng.add_request(p, max_new_tokens=max_new)
            outs = eng.run()
            dt = time.perf_counter() - t0
            toks = sum(len(v) for v in outs.values())
            tok_s = toks / dt
            rows.append((page, chunk, round(tok_s, 1)))
            print(f"# serving page={page} chunk={chunk}: "
                  f"{tok_s:.1f} tok/s")
            best = min(best, (-tok_s, (page, chunk)))
    if best[1] is None:
        return {}
    geom = _geometry(sp)
    key = tuning.geom_key(h=geom["h"], l=geom["layers"],
                          kv=geom["kv_heads"], hd=geom["hd"])
    return {"serving": {key: {"page_size": best[1][0],
                              "prefill_chunk": best[1][1],
                              "tok_s": round(-best[0], 1)}}}


def _merge(store, backend, results):
    dst = store.setdefault(backend, {})
    for op, table in results.items():
        dst.setdefault(op, {}).update(table)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="llama-350m")
    ap.add_argument("--ops", default="all",
                    help="comma list of: fusion, blocks, serving")
    ap.add_argument("--tokens", type=int, default=None,
                    help="token count for the op sweeps (default: 2048 "
                         "on TPU, 256 on CPU)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--update", action="store_true",
                    help="write winners to tools/tuned_configs.json")
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    t = args.tokens or (2048 if on_tpu else 256)
    iters = args.iters or (20 if on_tpu else 5)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    wanted = (("fusion", "blocks", "serving")
              if args.ops == "all" else tuple(args.ops.split(",")))
    preset = args.preset

    results = {}
    if "fusion" in wanted:
        _merge(results, "_", sweep_fusion(preset, t, dtype, iters))
    if "blocks" in wanted:
        _merge(results, "_", sweep_blocks(preset, t, dtype, iters))
    if "serving" in wanted:
        _merge(results, "_", sweep_serving(preset, on_tpu))

    backend = jax.default_backend()
    out = {backend: results.get("_", {})}
    print(json.dumps(out, indent=2))

    if args.update:
        store = {}
        if os.path.exists(OUT_PATH):
            with open(OUT_PATH) as f:
                store = json.load(f)
        _merge(store, backend, results.get("_", {}))
        with open(OUT_PATH, "w") as f:
            json.dump(store, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"tuned configs recorded for {backend!r} -> {OUT_PATH}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
