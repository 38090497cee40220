#!/usr/bin/env python
"""Op-benchmark gate (reference: the op-benchmark CI job comparing PR
kernel timings against baselines).

Times a fixed set of hot ops on the current backend and compares against
``tools/op_baseline.json`` (per host/backend). Regressions beyond the
tolerance fail; ``--update`` records new baselines.

    python tools/op_benchmark.py --update
    python tools/op_benchmark.py --tolerance 0.25
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # the TPU plugin overrides the env var; config wins
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp

BASE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "op_baseline.json")


ITER_SCALE = 1.0  # --fast shrinks every op's iteration budget
REPS = 5


def _time(f, *args, iters=100):
    """Per-iter ms, one host sync per block (a large block amortizes the
    sync below the noise floor)."""
    iters = max(1, int(iters * ITER_SCALE))
    out = f(*args)
    _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(*args)
        _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1000  # ms


def suite():
    from paddle_tpu.incubate.nn import functional as IF
    from paddle_tpu.nn import functional as F
    from paddle_tpu.nn import quant as QN
    from paddle_tpu.ops.pallas.int4_matmul import int4_matmul as _int4_kernel

    key = jax.random.key(0)
    x = jax.random.normal(key, (4096, 1024), jnp.bfloat16)
    w = jax.random.normal(key, (1024, 4096), jnp.bfloat16)
    _wq8 = QN.weight_quantize(w, algo="weight_only_int8")
    _wq4 = QN.weight_quantize(w, algo="weight_only_int4")
    q = jax.random.normal(key, (2, 1024, 8, 64), jnp.bfloat16)
    # decode-shape operands: one new token against a 1024-token KV cache
    qd = jax.random.normal(key, (8, 8, 64), jnp.bfloat16)
    kc = jax.random.normal(key, (8, 1024, 8, 64), jnp.bfloat16)
    lens = jnp.full((8,), 1000, jnp.int32)
    vlens = jnp.asarray([1024, 900], jnp.int32)  # one length per q batch row
    ops = {
        "matmul_4kx1kx4k": (jax.jit(lambda a, b: a @ b), (x, w)),
        "flash_attn_fwd": (jax.jit(lambda q: F.scaled_dot_product_attention(
            q, q, q, is_causal=True)), (q,)),
        # the "cutlass memory-efficient attention" capability claim (SURVEY
        # §2.1): masked XLA attention, benchmarked against the flash kernel
        # above so the claim is a recorded ratio, not an assertion
        "varlen_memeff_attn": (jax.jit(
            lambda q, l: IF.variable_length_memory_efficient_attention(
                q, q, q, seq_lens=l, causal=True)), (q, vlens)),
        # masked single-step decode against a dense KV cache
        "masked_decode_attn": (jax.jit(
            lambda qd, kc, lens: IF.masked_multihead_attention(
                qd, kc, kc, lens)[0]), (qd, kc, lens)),
        # paged (block-pool) decode — the serving path's kernel
        # (docs/BENCH.md "Decode throughput" has the e2e numbers).  The
        # CPU fallback is a materializing gather — far off the Pallas
        # path's cost — so it gets a reduced iteration count
        "paged_decode_attn": (jax.jit(
            lambda qd, kp, bt, lens: IF.paged_attention(
                qd, kp, kp, bt, lens)),
            (qd, kc.reshape(8 * 16, 64, 8, 64),
             jnp.arange(8 * 16, dtype=jnp.int32).reshape(8, 16), lens),
            {"iters": 100 if jax.default_backend() == "tpu" else 3}),
        # weight-only serving GEMMs (nn.quant): the decode-path matmul
        # with int8 / packed-int4 weight streams (SURVEY §2.1 fpA_intB)
        "weight_only_int8_gemm": (jax.jit(
            lambda a, qw, s: QN.weight_only_linear(a, qw, weight_scale=s)),
            (x, *_wq8)),
        "weight_only_int4_gemm": (jax.jit(
            lambda a, qw, s: QN.weight_only_linear(
                a, qw, weight_scale=s, weight_dtype="int4")),
            (x, *_wq4)),
        # the fused dequant-in-matmul kernel at a decode (GEMV) shape —
        # interpret mode on CPU is far off the Mosaic cost, so few iters
        "int4_gemm_kernel": (
            (lambda a, qw, s: _int4_kernel(
                a, qw, s, interpret=jax.default_backend() != "tpu")),
            (x[:8], *_wq4),
            {"iters": 100 if jax.default_backend() == "tpu" else 2}),
        "rms_norm": (jax.jit(lambda a: a * jax.lax.rsqrt(
            jnp.mean(a.astype(jnp.float32) ** 2, -1, keepdims=True) + 1e-6
        ).astype(a.dtype)), (x,)),
        "softmax_ce": (jax.jit(lambda a: -jax.nn.log_softmax(
            a.astype(jnp.float32))[..., 0].mean()), (x,)),
    }
    ops.update(_fused_ops())
    out = {}
    for name, spec in ops.items():
        f, args = spec[0], spec[1]
        kw = spec[2] if len(spec) > 2 else {}
        out[name] = _time(f, *args, **kw)
    return out


# fused-op rows come in (fused_X, unfused_X) pairs; the ratio per op is
# printed as `fused_speedups` and tracked by tests/test_fused_kernels.py
FUSED_PAIRS = ("rms_rope_qkv", "swiglu_mlp", "int8_gemv", "adamw",
               "mega_decode")

# set by _fused_ops(): top-level jaxpr equation counts of the two
# mega_decode legs — the dispatch-count half of the megakernel's A/B
# (the ms rows above are the timing half).  Printed with the results.
MEGA_DISPATCHES = None


def _fused_ops():
    """Fused-kernel library rows (docs/KERNELS.md): each op as a
    (fused, unfused-composition) pair at the llama-350m geometry.

    What each pair compares:
    - int8_gemv / adamw — the fused entry point (Pallas kernel on TPU,
      its XLA composition elsewhere) vs the pre-fusion path as separate
      dispatches (dequantize-then-fp-matmul; per-stage optimizer
      update).  Both fusions hold their win on CPU XLA too (the
      materialized fp weight / the extra state passes are real traffic
      everywhere).
    - rms_rope_qkv / swiglu_mlp — on TPU both legs are real (kernel vs
      XLA dispatches).  On CPU both legs run the PALLAS INTERPRETER
      (one fused pass vs the separate norm/matmul/rope/elementwise
      passes with materialized intermediates): the XLA-composition A/B
      is dispatch-bound noise on CPU for these matmul-chain ops
      (tools/tuned_configs.json records ~0.9-1.05, which is why
      `fused_ops="auto"` keeps them off there), so the CPU rows
      exercise the kernels' structural claim — one read of the hidden
      states, no intermediate round-trips — in the only mode CPU can
      run the kernels.
    """
    from paddle_tpu.incubate.nn import functional as IF
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.pallas import fused_mlp as FM

    on_tpu = jax.default_backend() == "tpu"
    key = jax.random.key(1)
    t, h, i = (2048, 1024, 2816) if on_tpu else (256, 1024, 2816)
    hd, nq, nk = 64, 1024, 1024
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    r = jax.random
    x = r.normal(key, (t, h), dt)
    gw = jnp.ones((h,), dt)
    wq, wk, wv = (r.normal(r.fold_in(key, j), (h, n), dt) * 0.05
                  for j, n in ((1, nq), (2, nk), (3, nk)))
    wg, wu = (r.normal(r.fold_in(key, j), (h, i), dt) * 0.05
              for j in (4, 5))
    wdn = r.normal(r.fold_in(key, 6), (i, h), dt) * 0.05
    inv = 1.0 / (10000.0 ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    fr = jnp.einsum("s,d->sd", jnp.arange(t, dtype=jnp.float32), inv)
    emb = jnp.concatenate([fr, fr], -1)
    cos, sin = jnp.cos(emb).astype(dt), jnp.sin(emb).astype(dt)

    proj = jax.jit(lambda a, w: a @ w)
    if on_tpu:
        # -- real kernels vs XLA per-stage dispatches -----------------------
        fused_qkv = jax.jit(lambda a: IF.fused_rms_rope_qkv(
            a, gw, wq, wk, wv, cos, sin, hd, 1e-5))
        norm = jax.jit(lambda a: F.rms_norm(a, gw, 1e-5))
        rope = jax.jit(F.apply_rotary_pos_emb)

        def unfused_qkv(a):
            nx = norm(a)
            q, k, v = proj(nx, wq), proj(nx, wk), proj(nx, wv)
            qr, kr = rope(q.reshape(1, t, nq // hd, hd),
                          k.reshape(1, t, nk // hd, hd), cos, sin)
            return qr, kr, v

        mlp_fused = jax.jit(lambda a: IF.fused_swiglu_mlp(a, wg, wu, wdn))
        _swi = jax.jit(F.swiglu)

        def mlp_unfused(a):
            return proj(_swi(proj(a, wg), proj(a, wu)), wdn)
        pair_iters = {}
    else:
        # -- interpret-vs-interpret (see docstring) -------------------------
        from jax.experimental import pallas as pl
        from paddle_tpu.ops.pallas import fused_norm_qkv as FQ
        from paddle_tpu.ops.pallas._common import pick_block

        def _mm_kernel(a_ref, b_ref, o_ref):
            o_ref[...] = jax.lax.dot(
                a_ref[...], b_ref[...],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

        def _interp_mm(a, b):
            m, k2 = a.shape
            n = b.shape[1]
            bn = pick_block(n, 512)     # must DIVIDE n: uncovered grid
            return pl.pallas_call(      # columns would stay unwritten
                _mm_kernel, grid=(n // bn,),
                in_specs=[pl.BlockSpec((m, k2), lambda j: (0, 0)),
                          pl.BlockSpec((k2, bn), lambda j: (0, j))],
                out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
                out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
                interpret=True)(a, b)

        def _ew2(fn, a, b):
            m, n = a.shape
            bn = pick_block(n, 512)

            def _k(a_ref, b_ref, o_ref):
                o_ref[...] = fn(a_ref[...], b_ref[...]).astype(o_ref.dtype)
            return pl.pallas_call(
                _k, grid=(n // bn,),
                in_specs=[pl.BlockSpec((m, bn), lambda j: (0, j))] * 2,
                out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
                out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
                interpret=True)(a, b)

        def _interp_norm(a):
            def _k(a_ref, g_ref, o_ref):
                af = a_ref[...].astype(jnp.float32)
                ms = jnp.mean(jnp.square(af), -1, keepdims=True)
                o_ref[...] = (af * jax.lax.rsqrt(ms + 1e-5)
                              * g_ref[...].astype(jnp.float32)) \
                    .astype(o_ref.dtype)
            return pl.pallas_call(
                _k,
                in_specs=[pl.BlockSpec((t, h), lambda: (0, 0)),
                          pl.BlockSpec((1, h), lambda: (0, 0))],
                out_specs=pl.BlockSpec((t, h), lambda: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((t, h), a.dtype),
                interpret=True)(a, gw.reshape(1, h))

        def _interp_rope(y):
            n = y.shape[1]
            cr = jnp.concatenate([cos] * (n // hd), axis=1)
            sr = jnp.concatenate([sin] * (n // hd), axis=1)

            def _k(y_ref, c_ref, s_ref, o_ref):
                yv = y_ref[...].astype(jnp.float32)
                yh = yv.reshape(t, n // hd, hd)
                half = hd // 2
                rot = jnp.concatenate([-yh[..., half:], yh[..., :half]],
                                      -1).reshape(t, n)
                o_ref[...] = (yv * c_ref[...].astype(jnp.float32)
                              + rot * s_ref[...].astype(jnp.float32)) \
                    .astype(o_ref.dtype)
            return pl.pallas_call(
                _k,
                in_specs=[pl.BlockSpec((t, n), lambda: (0, 0))] * 3,
                out_specs=pl.BlockSpec((t, n), lambda: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((t, n), y.dtype),
                interpret=True)(y, cr, sr)

        def unfused_qkv(a):
            nx = _interp_norm(a)
            q, k, v = (_interp_mm(nx, wq), _interp_mm(nx, wk),
                       _interp_mm(nx, wv))
            return _interp_rope(q), _interp_rope(k), v

        def fused_qkv(a):
            return FQ.fused_rms_rope_qkv(a, gw, wq, wk, wv, cos, sin,
                                         hd, eps=1e-5, interpret=True)

        def mlp_unfused(a):
            return _interp_mm(
                _ew2(lambda g, u: jax.nn.silu(g.astype(jnp.float32))
                     * u.astype(jnp.float32),
                     _interp_mm(a, wg), _interp_mm(a, wu)),
                wdn)

        def mlp_fused(a):
            return FM.fused_swiglu_mlp(a, wg, wu, wdn, interpret=True)
        pair_iters = {"iters": 2}

    # -- int8_gemv: fused dequant-in-matmul vs materialize-then-matmul ------
    from paddle_tpu.nn import quant as QN
    kk, nn_ = 1024, 4096
    wfp = r.normal(r.fold_in(key, 7), (kk, nn_), jnp.float32) * 0.05
    qw8, sc8 = QN.weight_quantize(wfp, algo="weight_only_int8")
    xd = r.normal(r.fold_in(key, 8), (8, kk), dt)
    i8_fused = jax.jit(lambda a: QN.weight_only_linear(
        a, qw8, weight_scale=sc8))
    deq = jax.jit(lambda: QN.weight_dequantize(
        qw8, sc8, algo="weight_only_int8"))

    def i8_unfused(a):
        return proj(a, deq().astype(a.dtype))

    # -- adamw: one fused pass vs per-stage updates.  (4096, 2048) f32 —
    # 32 MiB per state array, past LLC, so the pass-count difference is
    # memory traffic, not cache noise
    p0 = r.normal(r.fold_in(key, 9), (4096, 2048), jnp.float32)
    g0 = p0 * 0.01
    m0 = jnp.zeros_like(p0)
    v0 = jnp.zeros_like(p0)
    lr, c1, c2 = (jnp.float32(1e-3), jnp.float32(10.0),
                  jnp.float32(1000.0))
    b1, b2, eps, wd_ = 0.9, 0.999, 1e-8, 0.01

    def _aw_fused(p, g, m, v):
        from paddle_tpu.ops import dispatch as _d
        impl = _d.get("fused_adamw")
        if impl is not None:
            out = impl(p, g, m, v, lr, c1, c2, beta1=b1, beta2=b2,
                       eps=eps, wd=wd_)
            if out is not None:
                return out
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * jnp.square(g)
        up = (m2 * c1) / (jnp.sqrt(v2 * c2) + eps) + wd_ * p
        return p - lr * up, m2, v2

    aw_fused = jax.jit(_aw_fused)
    # the _adam_core composition stage by stage: moment EMAs, the two
    # bias-corrected estimates, the update quotient, the decayed axpy —
    # each materialized, the pre-fusion pass structure
    m_up = jax.jit(lambda m, g: b1 * m + (1 - b1) * g)
    v_up = jax.jit(lambda v, g: b2 * v + (1 - b2) * jnp.square(g))
    mhat = jax.jit(lambda m: m * c1)
    vhat = jax.jit(lambda v: jnp.sqrt(v * c2) + eps)
    quot = jax.jit(lambda mh, vh: mh / vh)
    axpy = jax.jit(lambda p, u: p - lr * (u + wd_ * p))

    def aw_unfused(p, g, m, v):
        m2 = m_up(m, g)
        v2 = v_up(v, g)
        return axpy(p, quot(mhat(m2), vhat(v2))), m2, v2

    # -- mega_decode: the whole ragged decoder-layer attention block as
    # ONE closed dispatch (docs/KERNELS.md "Decode megakernel") vs the
    # pre-fusion serving path's per-stage dispatches (fused qkv+rope /
    # ragged paged attention + span pool write / o-proj + residual).
    # The fused leg goes through the public mega_decode_layer entry —
    # the Pallas megakernel on TPU, the one-dispatch XLA composition on
    # CPU (exactly what fused_ops="mega" executes there), so the CPU
    # row measures the dispatch-boundary cost the fusion deletes, not a
    # kernel-vs-XLA claim.  MEGA_DISPATCHES records the structural half
    # of the A/B: top-level jaxpr equations per leg.
    bm, cm, hdm = 8, 8, 128
    hm, nqm, nkm = 1024, 1024, 512        # GQA 8q/4kv at MXU-wide heads
    hkv = nkm // hdm
    pagem, mbm = 64, 16
    nbm = bm * mbm
    gwm = jnp.ones((hm,), dt)
    wqm = r.normal(r.fold_in(key, 10), (hm, nqm), dt) * 0.05
    wkm = r.normal(r.fold_in(key, 11), (hm, nkm), dt) * 0.05
    wvm = r.normal(r.fold_in(key, 12), (hm, nkm), dt) * 0.05
    wom = r.normal(r.fold_in(key, 13), (nqm, hm), dt) * 0.05
    xm = r.normal(r.fold_in(key, 14), (bm, cm, hm), dt)
    kpm = r.normal(r.fold_in(key, 15), (nbm, pagem, hkv, hdm), dt) * 0.5
    vpm = r.normal(r.fold_in(key, 16), (nbm, pagem, hkv, hdm), dt) * 0.5
    tbm = r.permutation(r.fold_in(key, 17),
                        nbm).reshape(bm, mbm).astype(jnp.int32)
    # mixed decode (len 1, long prefix) + chunked-prefill-tail spans
    stm = jnp.asarray([1016, 37, 512, 0, 777, 128, 960, 7], jnp.int32)
    lnm = jnp.asarray([1, cm, 1, cm, 1, 1, cm, 1], jnp.int32)
    posm = stm[:, None] + jnp.arange(cm)[None, :]
    invm = 1.0 / (10000.0 ** (jnp.arange(0, hdm, 2, jnp.float32) / hdm))
    angm = posm[..., None].astype(jnp.float32) * invm
    cosm = jnp.concatenate([jnp.cos(angm)] * 2, -1).astype(dt)
    sinm = jnp.concatenate([jnp.sin(angm)] * 2, -1).astype(dt)

    def _mega_one(a, kp, vp):
        return IF.mega_decode_layer(a, gwm, wqm, wkm, wvm, wom, cosm,
                                    sinm, (kp, vp), tbm, stm, lnm, hdm,
                                    1e-5)

    mega_fused = jax.jit(_mega_one)
    qkv_stage = jax.jit(lambda a: IF.fused_rms_rope_qkv(
        a.reshape(bm * cm, hm), gwm, wqm, wkm, wvm,
        cosm.reshape(bm * cm, hdm), sinm.reshape(bm * cm, hdm), hdm,
        1e-5))
    att_stage = jax.jit(lambda kp, vp, q, k, v: IF.ragged_paged_attend(
        (kp, vp), q.reshape(bm, cm, nqm // hdm, hdm),
        k.reshape(bm, cm, hkv, hdm), v.reshape(bm, cm, hkv, hdm),
        tbm, stm, lnm))
    oproj_stage = jax.jit(lambda a, attn: a + (
        attn.reshape(bm * cm, nqm) @ wom.astype(a.dtype)
    ).astype(a.dtype).reshape(bm, cm, hm))

    def mega_unfused(a, kp, vp):
        q, k, v = qkv_stage(a)
        attn, new_cache = att_stage(kp, vp, q, k, v)
        return oproj_stage(a, attn), new_cache

    global MEGA_DISPATCHES
    MEGA_DISPATCHES = {
        "fused": len(jax.make_jaxpr(_mega_one)(xm, kpm, vpm).jaxpr.eqns),
        "unfused": len(jax.make_jaxpr(mega_unfused)(xm, kpm,
                                                    vpm).jaxpr.eqns),
    }
    mega_iters = {"iters": 100 if on_tpu else 3}

    return {
        "fused_rms_rope_qkv": (fused_qkv, (x,), pair_iters),
        "unfused_rms_rope_qkv": (unfused_qkv, (x,), pair_iters),
        "fused_swiglu_mlp": ((lambda a: mlp_fused(a)), (x,), pair_iters),
        "unfused_swiglu_mlp": (mlp_unfused, (x,), pair_iters),
        "fused_int8_gemv": (i8_fused, (xd,)),
        "unfused_int8_gemv": (i8_unfused, (xd,)),
        "fused_adamw": (aw_fused, (p0, g0, m0, v0)),
        "unfused_adamw": (aw_unfused, (p0, g0, m0, v0)),
        "fused_mega_decode": (mega_fused, (xm, kpm, vpm), mega_iters),
        "unfused_mega_decode": (mega_unfused, (xm, kpm, vpm), mega_iters),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true")
    ap.add_argument("--tolerance", type=float, default=0.5,
                    help="allowed fractional slowdown before failing")
    ap.add_argument("--fast", action="store_true",
                    help="~10x fewer iterations + 2 reps: noisier, meant "
                         "for the standing CI gate (tools/ci.py) where the "
                         "tolerance is loose anyway")
    ap.add_argument("--platform", default=None,
                    help="pin the jax backend (the CI gate passes 'cpu': "
                         "fast-mode timings are too short to match the "
                         "recorded TPU baselines, which come from full "
                         "runs)")
    args = ap.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    if args.fast:
        global ITER_SCALE, REPS
        ITER_SCALE, REPS = 0.1, 2

    backend = jax.default_backend()
    results = suite()
    # fused-kernel library A/B (docs/KERNELS.md): ratio per op pair —
    # the number the CPU-container acceptance bar reads (≥ 1.2x each)
    speedups = {op: round(results[f"unfused_{op}"] / results[f"fused_{op}"],
                          3)
                for op in FUSED_PAIRS
                if f"fused_{op}" in results and f"unfused_{op}" in results}
    payload = {"backend": backend, "ms": results,
               "fused_speedups": speedups}
    if MEGA_DISPATCHES is not None:
        # structural half of the megakernel A/B: top-level equations of
        # the one-dispatch layer vs the per-stage composition
        payload["mega_dispatches"] = MEGA_DISPATCHES
    print(json.dumps(payload, indent=2))

    base = {}
    if os.path.exists(BASE_PATH):
        with open(BASE_PATH) as f:
            base = json.load(f)
    if args.update:
        base[backend] = results
        with open(BASE_PATH, "w") as f:
            json.dump(base, f, indent=2)
        print(f"baseline recorded for {backend!r} -> {BASE_PATH}")
        return 0
    if backend not in base:
        # a GATE run must never self-record (a bogus section written as a
        # side effect would be committed as truth) — state it and pass
        print(f"op-benchmark: no baseline for backend {backend!r}; "
              "skipping comparison (run with --update to record one)")
        return 0

    failures = []
    for name, ms in results.items():
        ref = base[backend].get(name)
        if ref is None:
            print(f"op-benchmark: WARNING no {backend!r} baseline entry "
                  f"for {name!r} — not gated (run --update)")
        elif ms > ref * (1 + args.tolerance):
            failures.append(f"{name}: {ms:.3f} ms vs baseline {ref:.3f} ms")
    if failures:
        print("op-benchmark gate FAILED:")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("op-benchmark gate OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
