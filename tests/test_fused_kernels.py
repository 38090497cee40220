"""Fused-kernel library (docs/KERNELS.md): interpret-mode kernel vs XLA
fallback equivalence, gradients, model/optimizer/engine wiring, tuned
configs, and the bench plumbing.

The engine/model dispatch between the Pallas kernels (TPU) and the XLA
compositions (CPU/other) per backend, so a drift here would make TPU and
CPU CI disagree about what the fused paths compute."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import tuning
from paddle_tpu.ops.pallas import fused_adamw as FA
from paddle_tpu.ops.pallas import fused_mlp as FM
from paddle_tpu.ops.pallas import fused_norm_qkv as FQ
from paddle_tpu.ops.pallas import int8_matmul as I8

R = np.random.default_rng(0)


def _arr(*shape, dtype=jnp.float32, scale=0.05):
    return jnp.asarray(R.normal(size=shape) * scale, dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _cos_sin(t, hd, dtype=jnp.float32):
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    fr = np.einsum("s,d->sd", np.arange(t), inv)
    emb = np.concatenate([fr, fr], -1)
    return (jnp.asarray(np.cos(emb), dtype),
            jnp.asarray(np.sin(emb), dtype))


class TestFusedMLPKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("t", [64, 37])    # odd T pads internally
    def test_swiglu_kernel_matches_fallback(self, dtype, t):
        h, i = 128, 256
        x = _arr(t, h, dtype=dtype, scale=1.0)
        wg, wu, wd = _arr(h, i, dtype=dtype), _arr(h, i, dtype=dtype), \
            _arr(i, h, dtype=dtype)
        got = FM.fused_swiglu_mlp(x, wg, wu, wd, interpret=True)
        want = IF._fused_swiglu_mlp_ref(x, wg, wu, wd)
        assert got.shape == (t, h) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))

    def test_swiglu_kernel_blocked_inner_axis(self):
        # block_i < I exercises the accumulating 2-D grid
        h, i, t = 128, 512, 32
        x = _arr(t, h, scale=1.0)
        wg, wu, wd = _arr(h, i), _arr(h, i), _arr(i, h)
        got = FM.fused_swiglu_mlp(x, wg, wu, wd, block_t=16, block_i=128,
                                  interpret=True)
        want = IF._fused_swiglu_mlp_ref(x, wg, wu, wd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("i,block_i", [(256, 256), (512, 128)],
                             ids=["one-block", "several-blocks"])
    @pytest.mark.parametrize("n_live", [0, 1, 23, 128, 129, 512])
    def test_live_rows_are_the_token_tiled_kernels_and_the_rest_zero(
            self, n_live, i, block_i, dtype):
        """The weight-stationary order (``n_live`` given): rows below
        ``n_live`` are the token-tiled kernel's on the same rows to the
        last bit (the same I-blocks added in the same order), rows past
        it are zero; 0 is the warm-up's all-empty step, 129 one row into
        the second token tile, 512 every lane."""
        h, t = 128, 512
        x = _arr(t, h, dtype=dtype, scale=1.0)
        wg, wu, wd = _arr(h, i, dtype=dtype), _arr(h, i, dtype=dtype), \
            _arr(i, h, dtype=dtype)
        want = np.asarray(FM.fused_swiglu_mlp(
            x, wg, wu, wd, block_i=block_i, interpret=True), np.float32)
        got = FM.fused_swiglu_mlp(x, wg, wu, wd, jnp.int32(n_live),
                                  block_i=block_i, interpret=True)
        assert got.shape == (t, h) and got.dtype == dtype
        got = np.asarray(got, np.float32)
        np.testing.assert_array_equal(got[:n_live], want[:n_live])
        assert not got[n_live:].any()

    def test_which_loop_order_runs_is_read_from_the_call(self):
        """No ``n_live``: the token-tiled grid.  ``n_live`` and a
        ``(T, H)`` VMEM can hold three times over: one grid axis, over
        I, and a scalar-prefetch operand.  ``n_live`` and a training
        batch's ``(T, H)``: the token-tiled grid again, every row
        computed."""
        h, i = 128, 256
        wg, wd = _arr(h, i), _arr(i, h)

        def grid_of(t, *n):
            x = jax.ShapeDtypeStruct((t, h), jnp.float32)
            jaxpr = jax.make_jaxpr(lambda x, *n: FM.fused_swiglu_mlp(
                x, wg, wg, wd, *n, interpret=True))(x, *n)
            eqn, = [e for e in jaxpr.jaxpr.eqns
                    if e.primitive.name == "pallas_call"]
            gm = eqn.params["grid_mapping"]
            return gm.grid, gm.num_index_operands

        n = jnp.int32(5)
        assert grid_of(512) == ((2, 1), 0)
        assert grid_of(512, n) == ((1,), 1)
        big = 2 ** 17            # 128 Ki rows x 128 floats, thrice: no
        assert not FM.holds_live(
            jax.ShapeDtypeStruct((big, h), jnp.float32), wg)
        assert grid_of(big, n) == ((big // 256, 1), 0)

    def test_entry_matches_unfused_model_path(self):
        # semantic pin: the fused entry ≈ the pre-fusion LlamaMLP math
        h, i, t = 128, 256, 16
        x = _arr(t, h, scale=1.0)
        wg, wu, wd = _arr(h, i), _arr(h, i), _arr(i, h)
        got = IF.fused_swiglu_mlp(x, wg, wu, wd)
        want = F.swiglu(x @ wg, x @ wu) @ wd
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_gradients_match_composition(self):
        h, i, t = 64, 128, 8
        x = _arr(t, h, scale=1.0)
        wg, wu, wd = _arr(h, i), _arr(h, i), _arr(i, h)

        def loss_fused(x, wg, wu, wd):
            return jnp.sum(IF.fused_swiglu_mlp(x, wg, wu, wd) ** 2)

        def loss_ref(x, wg, wu, wd):
            return jnp.sum((F.swiglu(x @ wg, x @ wu) @ wd) ** 2)

        gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, wg, wu, wd)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestFusedNormRopeQKV:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("t,nk", [(32, 256), (29, 128)])
    def test_kernel_matches_fallback(self, dtype, t, nk):
        """GQA (nk < nq), odd seq lens, both dtypes."""
        h, nq, hd = 128, 256, 32
        x = _arr(t, h, dtype=dtype, scale=1.0)
        gw = jnp.asarray(1.0 + 0.1 * R.normal(size=(h,)), dtype)
        wq, wk, wv = (_arr(h, nq, dtype=dtype), _arr(h, nk, dtype=dtype),
                      _arr(h, nk, dtype=dtype))
        cos, sin = _cos_sin(t, hd, dtype)
        got = FQ.fused_rms_rope_qkv(x, gw, wq, wk, wv, cos, sin, hd,
                                    eps=1e-5, interpret=True)
        want = IF._fused_rms_rope_qkv_ref(x, gw, wq, wk, wv, cos, sin,
                                          hd, 1e-5)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == dtype
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       **_tol(dtype))

    def test_entry_matches_unfused_model_path(self):
        """Semantic pin against the pre-fusion composition: rms_norm →
        projections → apply_rotary_pos_emb."""
        t, h, nq, nk, hd = 24, 128, 256, 128, 32
        x = _arr(t, h, scale=1.0)
        gw = jnp.asarray(1.0 + 0.1 * R.normal(size=(h,)), jnp.float32)
        wq, wk, wv = _arr(h, nq), _arr(h, nk), _arr(h, nk)
        cos, sin = _cos_sin(t, hd)
        q, k, v = IF.fused_rms_rope_qkv(x, gw, wq, wk, wv, cos, sin, hd,
                                        1e-5)
        nx = F.rms_norm(x, gw, 1e-5)
        q_ref = (nx @ wq).reshape(1, t, nq // hd, hd)
        k_ref = (nx @ wk).reshape(1, t, nk // hd, hd)
        qr, kr = F.apply_rotary_pos_emb(q_ref, k_ref, cos, sin)
        np.testing.assert_allclose(np.asarray(q),
                                   np.asarray(qr.reshape(t, nq)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(k),
                                   np.asarray(kr.reshape(t, nk)),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(v), np.asarray(nx @ wv),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_composition(self):
        t, h, nq, nk, hd = 8, 64, 128, 128, 32
        x = _arr(t, h, scale=1.0)
        gw = jnp.ones((h,), jnp.float32)
        wq, wk, wv = _arr(h, nq), _arr(h, nk), _arr(h, nk)
        cos, sin = _cos_sin(t, hd)

        def loss_fused(x, wq):
            q, k, v = IF.fused_rms_rope_qkv(x, gw, wq, wk, wv, cos, sin,
                                            hd, 1e-5)
            return jnp.sum(q ** 2) + jnp.sum(k * v)

        def loss_ref(x, wq):
            nx = F.rms_norm(x, gw, 1e-5)
            qr, kr = F.apply_rotary_pos_emb(
                (nx @ wq).reshape(1, t, nq // hd, hd),
                (nx @ wk).reshape(1, t, nk // hd, hd), cos, sin)
            return jnp.sum(qr.reshape(t, nq) ** 2) \
                + jnp.sum(kr.reshape(t, nk) * (nx @ wv))

        gf = jax.grad(loss_fused, argnums=(0, 1))(x, wq)
        gr = jax.grad(loss_ref, argnums=(0, 1))(x, wq)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_supported_gates(self):
        x = _arr(8, 128)
        assert FQ.supported(x, _arr(128, 256), _arr(128, 128), 64)
        # misaligned widths / wrong dtypes / giant geometry fall back
        assert not FQ.supported(x, _arr(128, 200), _arr(128, 128), 64)
        assert not FQ.supported(x.astype(jnp.float16), _arr(128, 256),
                                _arr(128, 128), 64)
        big = jax.ShapeDtypeStruct((8, 8192), jnp.float32)
        assert not FQ.supported(
            jnp.zeros((8, 8192), jnp.bfloat16),
            jnp.zeros((8192, 8192), jnp.bfloat16),
            jnp.zeros((8192, 8192), jnp.bfloat16), 128), big


class TestInt8MatmulKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_matches_xla_int8_path(self, dtype):
        from paddle_tpu.nn.quant import weight_quantize, weight_only_linear
        k, n = 256, 384
        w_fp = np.asarray(R.normal(size=(k, n)) * 0.1, np.float32)
        qw, sc = weight_quantize(jnp.asarray(w_fp),
                                 algo="weight_only_int8")
        x = _arr(8, k, dtype=dtype, scale=1.0)
        got = I8.int8_matmul(x, qw, sc, interpret=True)
        want = weight_only_linear(x, qw, weight_scale=sc)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))

    def test_kernel_within_quant_tolerance_of_fp(self):
        from paddle_tpu.nn.quant import weight_quantize
        k, n = 256, 256
        w_fp = np.asarray(R.normal(size=(k, n)) * 0.1, np.float32)
        qw, sc = weight_quantize(jnp.asarray(w_fp),
                                 algo="weight_only_int8")
        x = _arr(4, k, scale=1.0)
        got = np.asarray(I8.int8_matmul(x, qw, sc, interpret=True))
        ref = np.asarray(x) @ w_fp
        # int8 per-channel symmetric quantization: ~0.4% relative error
        assert np.abs(got - ref).max() <= 2e-2 * np.abs(ref).max() + 1e-3

    def test_blocked_k_path(self):
        from paddle_tpu.nn.quant import weight_quantize, weight_only_linear
        k, n = 512, 256
        qw, sc = weight_quantize(
            jnp.asarray(R.normal(size=(k, n)) * 0.1, jnp.float32),
            algo="weight_only_int8")
        x = _arr(4, k, scale=1.0)
        got = I8.int8_matmul(x, qw, sc, block_k=128, block_n=128,
                             interpret=True)
        # force the 2-D accumulating grid via a tiny MAX_1D_K
        old = I8.MAX_1D_K
        try:
            I8.MAX_1D_K = 256
            got2 = I8.int8_matmul(x, qw, sc, block_k=128, block_n=128,
                                  interpret=True)
        finally:
            I8.MAX_1D_K = old
        want = weight_only_linear(x, qw, weight_scale=sc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_shape_validation(self):
        with pytest.raises(ValueError):
            I8.int8_matmul(_arr(4, 128), jnp.zeros((64, 128), jnp.int8),
                           jnp.ones((128,)), interpret=True)
        with pytest.raises(ValueError):
            I8.int8_matmul(_arr(4, 128), jnp.zeros((128, 128), jnp.int8),
                           jnp.ones((64,)), interpret=True)


class TestFusedAdamWKernel:
    def _state(self, shape, g_dtype):
        p = jnp.asarray(R.normal(size=shape), jnp.float32)
        g = jnp.asarray(R.normal(size=shape), g_dtype)
        m = jnp.asarray(R.normal(size=shape) * 0.1, jnp.float32)
        v = jnp.asarray(np.abs(R.normal(size=shape)) * 0.01, jnp.float32)
        return p, g, m, v

    def _legs(self, p, g, m, v, step, wd, low_dtype=None):
        from paddle_tpu import optimizer as opt
        aw = opt.AdamW(learning_rate=1e-3, weight_decay=wd,
                       use_fused=False)
        lr = jnp.float32(1e-3)
        t = jnp.float32(step + 1)
        c1 = 1.0 / (1.0 - 0.9 ** t)
        c2 = 1.0 / (1.0 - 0.999 ** t)
        got = FA.fused_adamw_update(p, g, m, v, lr, c1, c2, beta1=0.9,
                                    beta2=0.999, eps=1e-8, wd=wd,
                                    low_dtype=low_dtype, interpret=True)
        want_p, slots = aw._update_one(
            "w", p, g.astype(jnp.float32), lr,
            {"moment1": m, "moment2": v}, jnp.int32(step), wd)
        return got, (want_p, slots["moment1"], slots["moment2"])

    # native blocks over the leaf's own trailing dimensions, more than one
    # block each way and not square: (shape, gradient dtype, grid)
    NATIVE = [((64, 384), jnp.float32, (8, 3)),
              ((24, 256), jnp.float32, (3, 2)),
              ((2, 16, 256), jnp.float32, (4, 2)),      # 3-D: rows fold
              ((64, 256), jnp.bfloat16, (4, 2))]

    @pytest.mark.parametrize("wd", [0.0, 0.01])
    @pytest.mark.parametrize("shape,g_dtype,grid", NATIVE, ids=[
        "64x384", "24x256", "2x16x256", "64x256-bf16grad"])
    def test_kernel_matches_adam_core(self, wd, shape, g_dtype, grid,
                                      monkeypatch):
        sub = FA._sublanes(g_dtype)
        monkeypatch.setattr(FA, "BLOCK_COLS", FA.LANES)          # one tile
        monkeypatch.setattr(FA, "BLOCK_ELEMS", sub * FA.LANES)
        rows, cols = FA._view(shape, sub)
        br, bc = FA._block(rows, cols, sub)
        assert (rows // br, cols // bc) == grid
        p, g, m, v = self._state(shape, g_dtype)
        got, want = self._legs(p, g, m, v, step=7, wd=wd)
        assert len(got) == 3
        for a, b in zip(got, want):
            assert a.shape == shape and a.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("wd", [0.0, 0.01])
    def test_low_precision_copy_is_the_cast_of_the_master(self, wd,
                                                          monkeypatch):
        """amp O2: bfloat16 gradient in, and the bfloat16 parameter out
        equal bit for bit to ``new_p.astype(bfloat16)``."""
        monkeypatch.setattr(FA, "BLOCK_COLS", FA.LANES)
        monkeypatch.setattr(FA, "BLOCK_ELEMS", 16 * FA.LANES)
        p, g, m, v = self._state((32, 384), jnp.bfloat16)
        got, want = self._legs(p, g, m, v, step=3, wd=wd,
                               low_dtype=jnp.bfloat16)
        new_p, _, _, low = got
        assert low.dtype == jnp.bfloat16 and low.shape == (32, 384)
        np.testing.assert_array_equal(
            np.asarray(low.astype(jnp.float32)),
            np.asarray(new_p.astype(jnp.bfloat16).astype(jnp.float32)))
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("dims", [
        (4096, 14336), (14336, 4096), (4096, 1024), (32000, 4096),
        (4096, 32000), (4096, 12288), (50304, 4096), (2048, 4096),
        (4096, 11008), (11008, 4096), (16, 128), (8, 4096, 1024)],
        ids=lambda d: "x".join(map(str, d)))
    def test_block_divides_the_leaf(self, dims):
        """The benchmark's and the smoke's leaves: the block divides the
        leaf, sits on whole tiles and inside the VMEM budget."""
        for sub in (8, 16):
            rows, cols = FA._view(dims, sub)
            br, bc = FA._block(rows, cols, sub)
            assert rows % br == 0 and cols % bc == 0
            assert br % sub == 0 and bc % FA.LANES == 0
            assert br * bc <= FA.BLOCK_ELEMS

    F32, BF16 = jnp.float32, jnp.bfloat16

    @pytest.mark.parametrize("dims,dtype,g_dtype,low,ok", [
        ((8, 128), F32, F32, None, True),
        ((8, 128), F32, BF16, None, False),     # a bf16 tile is 16 rows
        ((16, 128), F32, BF16, BF16, True),
        ((8, 128), F32, F32, BF16, False),
        ((8, 128), BF16, F32, None, False),     # the state is float32
        ((1024,), F32, F32, None, False),       # one-dimensional
        ((100, 128), F32, F32, None, False),    # rows off the tile
        ((64, 100), F32, F32, None, False),     # lanes off the tile
        ((8, 3, 3, 16), F32, F32, None, False),     # a conv kernel
        ((4, 16, 256), F32, BF16, BF16, True),  # leading dims fold
        ((4, 8, 256), F32, BF16, BF16, False),  # ... only on whole tiles
        ((0, 128), F32, F32, None, False),
    ])
    def test_eligibility(self, dims, dtype, g_dtype, low, ok):
        p = jax.ShapeDtypeStruct(dims, dtype)
        g = jax.ShapeDtypeStruct(dims, g_dtype)
        assert FA.eligible(p, g, low) == ok

    def _as_tpu(self, monkeypatch, calls):
        """The registry answers as on the chip; the kernel it hands out
        runs in interpret mode and notes each leaf it was given."""
        from paddle_tpu.ops import dispatch
        monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
        real = FA.fused_adamw_update

        def interpreted(p, *a, **k):
            calls.append(tuple(p.shape))
            return real(p, *a, interpret=True, **k)
        monkeypatch.setattr(FA, "fused_adamw_update", interpreted)

    @pytest.mark.parametrize("shape", [(1024,), (8, 3, 3, 16), (100, 128)],
                             ids=["1d", "conv", "ragged-rows"])
    def test_declined_shape_takes_the_xla_composition(self, shape,
                                                      monkeypatch):
        from paddle_tpu import optimizer as opt
        calls = []
        self._as_tpu(monkeypatch, calls)
        p, g, m, v = self._state(shape, jnp.bfloat16)
        lr, step = jnp.float32(1e-3), jnp.int32(4)
        outs = []
        for fused in (None, False):
            aw = opt.AdamW(learning_rate=1e-3, weight_decay=0.01,
                           use_fused=fused)
            outs.append(aw._update_leaf(
                "w", p, g, lr, {"moment1": m, "moment2": v}, step, 0.01,
                jnp.bfloat16))
        assert not calls
        want = aw._adam_core(p, g.astype(jnp.float32), lr, m, v, step, 0.01,
                             decoupled=True)
        for (new_p, low, slots) in outs:
            assert low.dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(new_p),
                                          np.asarray(want[0]))
            np.testing.assert_array_equal(
                np.asarray(low.astype(jnp.float32)),
                np.asarray(want[0].astype(jnp.bfloat16).astype(jnp.float32)))
            np.testing.assert_array_equal(np.asarray(slots["moment1"]),
                                          np.asarray(want[1]))
            np.testing.assert_array_equal(np.asarray(slots["moment2"]),
                                          np.asarray(want[2]))

    @pytest.mark.parametrize("master", [True, False],
                             ids=["amp-O2", "float32"])
    def test_apply_routes_matrices_through_the_kernel(self, master,
                                                      monkeypatch):
        """``Optimizer.apply`` as the train step calls it: the matrices
        take the kernel (gradient as it arrives, low-precision copy out
        under amp O2), the norm weight the XLA composition, and the
        result is ``use_fused=False``'s."""
        from paddle_tpu import nn, optimizer as opt
        calls = []
        self._as_tpu(monkeypatch, calls)
        dt = jnp.bfloat16 if master else jnp.float32
        params = {"w": jnp.asarray(R.normal(size=(32, 256)), dt),
                  "e": jnp.asarray(R.normal(size=(2, 16, 128)), dt),
                  "norm": jnp.asarray(R.normal(size=(256,)), dt)}
        grads = {k: jnp.asarray(R.normal(size=v.shape), dt)
                 for k, v in params.items()}
        outs = []
        for fused in (None, False):
            aw = opt.AdamW(learning_rate=1e-2, weight_decay=0.1,
                           grad_clip=nn.ClipGradByGlobalNorm(1.0),
                           multi_precision=master, use_fused=fused)
            state = aw.init(params)
            new_params, state = aw.apply(grads, state, params)
            outs.append(aw.apply(grads, state, new_params))
        assert sorted(calls) == [(2, 16, 128), (2, 16, 128),
                                 (32, 256), (32, 256)]
        (p_k, s_k), (p_x, s_x) = outs
        for k in params:
            assert p_k[k].dtype == dt
            np.testing.assert_allclose(
                np.asarray(p_k[k].astype(jnp.float32)),
                np.asarray(p_x[k].astype(jnp.float32)), rtol=1e-6, atol=1e-6)
            for slot in ("moment1", "moment2") + (("master",) * master):
                np.testing.assert_allclose(np.asarray(s_k[slot][k]),
                                           np.asarray(s_x[slot][k]),
                                           rtol=1e-6, atol=1e-6)

    def test_adamw_use_fused_kwarg_cpu_noop(self):
        """On CPU the dispatch declines and use_fused falls back to the
        XLA core — updates bitwise-identical to use_fused=False."""
        from paddle_tpu import optimizer as opt
        p = jnp.asarray(R.normal(size=(16, 128)), jnp.float32)
        g = jnp.asarray(R.normal(size=(16, 128)), jnp.float32)
        slots = {"moment1": jnp.zeros_like(p), "moment2": jnp.zeros_like(p)}
        lr = jnp.float32(1e-3)
        outs = []
        for fused in (None, False):
            aw = opt.AdamW(learning_rate=1e-3, weight_decay=0.01,
                           use_fused=fused)
            outs.append(aw._update_leaf("w", p, g, lr, dict(slots),
                                        jnp.int32(0), 0.01, jnp.float32))
        np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                      np.asarray(outs[1][0]))


class TestTuningRegistry:
    def test_geom_key_is_canonical(self):
        assert tuning.geom_key(h=1024, i=2816) == "h1024_i2816"
        assert tuning.geom_key(i=2816, h=1024) == "h1024_i2816"

    def test_lookup_and_reload(self, tmp_path, monkeypatch):
        path = tmp_path / "tuned.json"
        path.write_text(json.dumps(
            {"cpu": {"fused_swiglu_mlp": {"h64_i128": {"block_t": 64}},
                     "serving": {"k": {"page_size": 8}}}}))
        monkeypatch.setenv("PDTPU_TUNED_CONFIGS", str(path))
        tuning.reload()
        try:
            assert tuning.tuned_config("fused_swiglu_mlp",
                                       "h64_i128") == {"block_t": 64}
            assert tuning.tuned_config("fused_swiglu_mlp", "nope") == {}
            assert tuning.tuned_config("absent", "x") == {}
            assert tuning.tuned_config(
                "serving", "k", backend="cpu")["page_size"] == 8
        finally:
            monkeypatch.delenv("PDTPU_TUNED_CONFIGS")
            tuning.reload()

    def test_missing_file_means_defaults(self, monkeypatch):
        monkeypatch.setenv("PDTPU_TUNED_CONFIGS", "/nonexistent/x.json")
        tuning.reload()
        try:
            assert tuning.tuned_config("fused_swiglu_mlp", "any") == {}
        finally:
            monkeypatch.delenv("PDTPU_TUNED_CONFIGS")
            tuning.reload()

    def test_fusion_enabled_modes(self):
        assert tuning.fusion_enabled("off", "fused_swiglu_mlp") is False
        assert tuning.fusion_enabled("on", "fused_swiglu_mlp") is True
        # auto on CPU: the kernel dispatch is TPU-only → stays unfused
        assert tuning.fusion_enabled("auto", "fused_swiglu_mlp") is False
        with pytest.raises(ValueError):
            tuning.fusion_enabled("maybe", "fused_swiglu_mlp")

    def test_committed_configs_parse(self):
        """tools/tuned_configs.json (the committed winners) loads
        through the real registry path."""
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "tuned_configs.json")
        assert os.path.exists(path)
        with open(path) as f:
            data = json.load(f)
        assert "cpu" in data
        assert "serving" in data["cpu"]


class TestModelWiring:
    def test_llama_fused_matches_unfused(self):
        from paddle_tpu.models.llama import llama
        pt.seed(0)
        m_off = llama("tiny", fused_ops="off")
        pt.seed(0)
        m_on = llama("tiny", fused_ops="on")
        ids = jnp.asarray(R.integers(0, 256, size=(2, 13)))
        lo, ln = m_off(ids), m_on(ids)
        np.testing.assert_allclose(np.asarray(lo), np.asarray(ln),
                                   rtol=2e-4, atol=2e-4)

    def test_llama_auto_is_unfused_on_cpu(self):
        from paddle_tpu.models.llama import llama
        pt.seed(0)
        m_off = llama("tiny", fused_ops="off")
        pt.seed(0)
        m_auto = llama("tiny")    # default auto
        ids = jnp.asarray(R.integers(0, 256, size=(1, 9)))
        np.testing.assert_array_equal(np.asarray(m_off(ids)),
                                      np.asarray(m_auto(ids)))

    def test_fused_generate_and_train_step(self):
        from paddle_tpu import nn, optimizer
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.llama import causal_lm_loss, llama
        pt.seed(0)
        model = llama("tiny", fused_ops="on")
        ids = jnp.asarray(R.integers(0, 256, size=(1, 7)))
        out = model.generate(ids, max_new_tokens=3, temperature=0.0)
        assert out.shape == (1, 10)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = TrainStep(model, causal_lm_loss, opt)
        state = step.init_state(seed=0)
        batch = {"input_ids": jnp.asarray(R.integers(0, 256, size=(2, 16))),
                 "labels": jnp.asarray(R.integers(0, 256, size=(2, 16)))}
        state, met = step(state, batch)
        state, met = step(state, batch)
        assert np.isfinite(float(met["loss"]))


@pytest.fixture
def interpreted_mlp_kernel(monkeypatch):
    """The fused MLP's TPU dispatch (its gate, the compaction around the
    call and both loop orders) through the Pallas interpreter on the
    CPU; counts the calls that carried the step's live lanes."""
    from paddle_tpu.ops import dispatch
    from paddle_tpu.ops import pallas as P
    calls = {"live": 0, "every-lane": 0}

    def kernel(x, w_gate, w_up, w_down, live=None):
        calls["every-lane" if live is None else "live"] += 1
        return P._fused_swiglu_dispatch(x, w_gate, w_up, w_down, live=live,
                                        interpret=True)
    monkeypatch.setitem(dispatch._REGISTRY, "fused_swiglu_mlp", kernel)
    monkeypatch.setitem(dispatch._PLATFORM, "fused_swiglu_mlp", "cpu")
    return calls


def _mistral_shaped():
    from paddle_tpu.models.llama import llama
    pt.seed(0)
    model = llama("tiny", hidden_size=128, intermediate_size=256,
                  fused_ops="on")        # 4 heads over 2 kv heads, d 32
    return model, dict(max_batch=4, max_seq_len=128, page_size=8,
                       prefill_chunk=8)


def _evabyte_shaped():
    from paddle_tpu.models import evabyte as E
    pt.seed(0)
    model = E.EvaByteForCausalLM(E.EvaByteConfig(
        hidden_size=128, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=512, window_size=64,
        fused_ops="on"))
    model.eval()
    return model, dict(max_batch=4, max_seq_len=512, num_blocks=64,
                       enable_prefix_caching=False)


class TestEngineWiring:
    @pytest.mark.parametrize("family", [_mistral_shaped, _evabyte_shaped],
                             ids=["mistral-shaped", "evabyte"])
    def test_ragged_step_is_the_same_with_and_without_the_live_lanes(
            self, family, interpreted_mlp_kernel, monkeypatch):
        """One request decodes while a long prompt fans out over the free
        rows, and its tail leaves rows empty: with the step's live lanes
        handed to the MLP kernel (compacted, dead lanes zero) and without
        (every lane through the token-tiled kernel), the same tokens come
        out and the same rows are written to the pools."""
        from paddle_tpu import serving
        model, kw = family()
        vocab = model.cfg.vocab_size
        rng = np.random.default_rng(7)
        short = rng.integers(0, vocab, size=5).astype(np.int32)
        # two steps over all three free rows, then a tail in one row
        chunk = kw.get("prefill_chunk", 16)
        long = rng.integers(0, vocab, size=6 * chunk + 5).astype(np.int32)

        def serve():
            eng = serving.Engine(model, **kw).warmup()
            lens_seen, real = [], eng._step_fn

            def spy(params, caches, tokens, tables, starts, lens, *rest):
                lens_seen.append(np.asarray(lens))
                return real(params, caches, tokens, tables, starts, lens,
                            *rest)
            eng._step_fn = spy
            a = eng.add_request(short, max_new_tokens=14)
            eng.step()
            eng.step()
            b = eng.add_request(long, max_new_tokens=4)
            outs = eng.run()
            assert eng.kv_blocks_used == 0
            pools = [np.asarray(leaf)
                     for leaf in jax.tree.leaves(eng.kv.caches)]
            return [list(outs[a]), list(outs[b])], pools, lens_seen

        with_live = serve()
        assert interpreted_mlp_kernel == {"live": 2, "every-lane": 0}
        # steps held a decode row beside fan-out rows, and beside empty rows
        assert any((ln == 1).any() and (ln > 1).sum() > 1
                   for ln in with_live[2])
        assert any((ln == 1).any() and (ln > 1).any() and (ln == 0).any()
                   for ln in with_live[2])
        monkeypatch.setattr(
            IF, "fused_swiglu_mlp_live",
            lambda x, wg, wu, wd, live: IF.fused_swiglu_mlp(x, wg, wu, wd))
        without = serve()
        assert interpreted_mlp_kernel == {"live": 2, "every-lane": 2}
        assert with_live[0] == without[0]
        assert len(with_live[2]) == len(without[2])
        for got, want in zip(with_live[1], without[1]):
            np.testing.assert_array_equal(got, want)

    def test_weight_quant_fused_token_identity(self):
        from paddle_tpu import serving
        from paddle_tpu.models.llama import llama
        pt.seed(0)
        model = llama("tiny", fused_ops="on")
        eng = serving.Engine(model, max_batch=2, max_seq_len=48,
                             page_size=8, prefill_chunk=8,
                             weight_quant="int8").warmup()
        prompt = R.integers(0, 256, size=11).astype(np.int32)
        rid = eng.add_request(prompt, max_new_tokens=5)
        outs = eng.run()
        ref = np.asarray(model.generate(
            jnp.asarray(prompt)[None], max_new_tokens=5,
            temperature=0.0))[0, len(prompt):]
        assert list(outs[rid]) == list(ref)
        assert eng.kv_blocks_used == 0

    def test_quantized_model_keeps_scales_under_fused_on(self):
        """Review regression: the fused model paths read `.weight`
        directly, but weight-only quantized layers keep raw int8 codes
        there (scale in a separate buffer) — the fused branches must
        step aside for quantized projections or outputs silently lose
        the scales."""
        from paddle_tpu.models.llama import llama
        from paddle_tpu.nn.quant import quantize_linears
        ids = jnp.asarray(R.integers(0, 256, size=(1, 9)))
        outs = {}
        for mode in ("on", "off"):
            pt.seed(0)
            m = llama("tiny", fused_ops=mode)
            quantize_linears(m, algo="weight_only_int8")
            outs[mode] = np.asarray(m(ids))
        np.testing.assert_allclose(outs["on"], outs["off"],
                                   rtol=1e-4, atol=1e-4)

    def test_auto_serving_knobs_resolve_from_tuned_configs(
            self, tmp_path, monkeypatch):
        from paddle_tpu import serving
        from paddle_tpu.models.llama import llama
        pt.seed(0)
        model = llama("tiny")
        cfg = model.cfg
        key = tuning.geom_key(h=cfg.hidden_size, l=cfg.num_hidden_layers,
                              kv=cfg.num_key_value_heads,
                              hd=cfg.head_dim)
        path = tmp_path / "tuned.json"
        path.write_text(json.dumps(
            {"cpu": {"serving": {key: {"page_size": 4,
                                       "prefill_chunk": 12}}}}))
        monkeypatch.setenv("PDTPU_TUNED_CONFIGS", str(path))
        tuning.reload()
        try:
            eng = serving.Engine(model, max_batch=2, max_seq_len=48,
                                 page_size="auto", prefill_chunk="auto")
            assert eng.page_size == 4
            assert eng.prefill_chunk == 12
        finally:
            monkeypatch.delenv("PDTPU_TUNED_CONFIGS")
            tuning.reload()

    def test_auto_knobs_default_without_configs(self, monkeypatch):
        from paddle_tpu import serving
        from paddle_tpu.models.llama import llama
        monkeypatch.setenv("PDTPU_TUNED_CONFIGS", "")
        tuning.reload()
        try:
            pt.seed(0)
            eng = serving.Engine(llama("tiny"), max_batch=2,
                                 max_seq_len=48, page_size="auto",
                                 prefill_chunk="auto")
            assert eng.page_size == 16
            assert eng.prefill_chunk == min(16, 48)
        finally:
            monkeypatch.delenv("PDTPU_TUNED_CONFIGS")
            tuning.reload()


class TestBenchPlumbing:
    def test_measure_with_fused_on(self):
        import bench
        mfu, stats = bench.measure("tiny", 2, 32, 1, 1, fused_ops="on")
        assert mfu > 0
        assert stats["fused"] == "on"
        assert np.isfinite(stats["loss"])

    def _tools(self):
        import sys
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        if tools not in sys.path:
            sys.path.insert(0, tools)

    def test_telemetry_report_folds_fused(self):
        import importlib
        self._tools()
        tr = importlib.import_module("telemetry_report")
        agg = tr.summarize([
            {"event": "run_meta", "kind": "bench", "fused": "on"},
            {"event": "step", "site": "train", "interval_ms": 10.0},
        ])
        assert tr._fused_mode(agg) == "on"
        assert "| on |" in tr.render(agg)
