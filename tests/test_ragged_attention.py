"""Ragged paged attention: interpret-mode kernel vs the XLA gather
fallback vs a NumPy oracle.

The serving engine dispatches between the Pallas kernel (TPU) and the
XLA fallback (CPU/other) per backend, so a drift here would make TPU and
CPU CI disagree about what the engine decodes.  The batch under test is
the engine's real shape: chunked-prefill spans, single decode tokens and
dead slots side by side in one fixed-shape dispatch.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.ops.pallas import ragged_attention as RA

R = np.random.default_rng(0)


def _oracle(q, kp, vp, tables, starts, lens):
    """Row j of slot b (position starts[b]+j) attends pool positions
    [0, starts[b]+j]; rows >= lens[b] are garbage (not compared)."""
    B, C, H, D = q.shape
    NB, BS, HKV, _ = kp.shape
    MB = tables.shape[1]
    g = H // HKV
    out = np.zeros((B, C, H, D), "float32")
    for b in range(B):
        ks = kp[np.clip(tables[b], 0, NB - 1)].reshape(MB * BS, HKV, D)
        vs = vp[np.clip(tables[b], 0, NB - 1)].reshape(MB * BS, HKV, D)
        for j in range(lens[b]):
            ctx = starts[b] + j + 1
            for h in range(H):
                hk = h // g
                s = (ks[:ctx, hk] @ q[b, j, h]) / np.sqrt(D)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, j, h] = p @ vs[:ctx, hk]
    return out


def _case(B=4, C=8, H=4, HKV=2, D=128, BS=16, NB=32, MB=4,
          starts=None, lens=None):
    q = R.normal(size=(B, C, H, D)).astype("float32")
    kp = R.normal(size=(NB, BS, HKV, D)).astype("float32")
    vp = R.normal(size=(NB, BS, HKV, D)).astype("float32")
    tables = R.integers(0, NB, size=(B, MB)).astype("int32")
    starts = np.asarray(starts if starts is not None else [0] * B, "int32")
    lens = np.asarray(lens if lens is not None else [C] * B, "int32")
    return q, kp, vp, tables, starts, lens


def _run_kernel(q, kp, vp, tables, starts, lens, dtype="float32"):
    out = RA.ragged_paged_attention(
        jnp.asarray(q, dtype), jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
        jnp.asarray(tables), jnp.asarray(starts), jnp.asarray(lens),
        interpret=True)
    assert out.dtype == jnp.dtype(dtype)
    return np.asarray(out.astype(jnp.float32))


def _assert_live_rows_close(got, want, lens, rtol=2e-4, atol=2e-5):
    for b in range(got.shape[0]):
        if lens[b]:
            np.testing.assert_allclose(got[b, :lens[b]], want[b, :lens[b]],
                                       rtol=rtol, atol=atol)


class TestRaggedKernelVsOracle:
    def test_mixed_prefill_decode_dead_slots(self):
        """The engine's real batch: a mid-prompt prefill chunk, a decode
        token, a dead slot and a fresh first chunk in ONE dispatch."""
        q, kp, vp, tables, starts, lens = _case(
            starts=[10, 33, 0, 0], lens=[6, 1, 0, 8])
        tables = tables.copy()
        tables[2, :] = -1                 # dead slot: padding table
        got = _run_kernel(q, kp, vp, tables, starts, lens)
        _assert_live_rows_close(got, _oracle(q, kp, vp, tables, starts,
                                             lens), lens)
        # dead slot: no page is ever visited → finalized to zeros
        assert np.abs(got[2]).max() == 0

    @pytest.mark.parametrize("h,hkv,starts,lens", [
        (4, 2, [0, 7, 30, 3], [8, 8, 2, 5]),     # GQA 2x, ragged spans
        (8, 2, [5, 0, 47, 12], [1, 8, 1, 4]),    # GQA 4x, decode mixed in
        (4, 4, [0, 21, 9, 0], [3, 8, 7, 1]),     # MHA
    ])
    def test_gqa_and_span_shapes(self, h, hkv, starts, lens):
        q, kp, vp, tables, starts, lens = _case(H=h, HKV=hkv,
                                                starts=starts, lens=lens)
        got = _run_kernel(q, kp, vp, tables, starts, lens)
        _assert_live_rows_close(got, _oracle(q, kp, vp, tables, starts,
                                             lens), lens)

    def test_page_boundary_spans(self):
        """Spans straddling page boundaries (start mid-page, end in the
        next page) read and mask the right positions."""
        q, kp, vp, tables, starts, lens = _case(
            C=8, BS=16, starts=[14, 15, 31, 62], lens=[8, 2, 8, 2])
        got = _run_kernel(q, kp, vp, tables, starts, lens)
        _assert_live_rows_close(got, _oracle(q, kp, vp, tables, starts,
                                             lens), lens)

    def test_fan_rows_share_one_table(self):
        """Span fan-out (serving/scheduler.py): the rows of one prompt
        carry ONE block table and consecutive starts, across a block
        boundary; each row reads its own prefix through it."""
        q, kp, vp, tables, starts, lens = _case(
            B=4, MB=16, NB=64, starts=[112, 120, 128, 136],
            lens=[8, 8, 8, 3])
        tables = np.repeat(tables[:1], 4, axis=0)
        got = _run_kernel(q, kp, vp, tables, starts, lens)
        _assert_live_rows_close(got, _oracle(q, kp, vp, tables, starts,
                                             lens), lens)

    # What the block schedule can get wrong.  A KV block is
    # ``_pages_per_block`` pages = 128 positions at these shapes (8 pages
    # of 16, 2 of 64), so MB = 16 pages of 16 is two blocks.
    SCHEDULE = {
        "ends-inside-second-block": dict(starts=[150, 0], lens=[8, 7]),
        "ends-on-block-boundary": dict(starts=[120, 248], lens=[8, 8]),
        "one-page-and-one-token": dict(starts=[8, 0], lens=[8, 1]),
        "table-full-to-mb": dict(starts=[248, 255], lens=[8, 1]),
        "span-crosses-block-boundary": dict(starts=[124, 127], lens=[8, 2]),
        "dead-between-live": dict(B=4, starts=[130, 0, 0, 17],
                                  lens=[3, 0, 0, 8]),
        "mha-g1": dict(H=4, HKV=4, starts=[125, 40], lens=[8, 8]),
        "gqa-g4": dict(H=8, HKV=2, starts=[125, 40], lens=[8, 8]),
        "page64": dict(BS=64, MB=4, NB=16, starts=[125, 200], lens=[8, 8]),
        "page64-one-page": dict(BS=64, MB=4, NB=16, starts=[0, 63],
                                lens=[5, 1]),
        "c1-decode": dict(C=1, starts=[127, 128], lens=[1, 1]),
        "c1-dead": dict(C=1, B=3, starts=[200, 0, 15], lens=[1, 0, 1]),
    }

    @pytest.mark.parametrize("dtype,rtol,atol", [
        ("float32", 2e-4, 2e-5), ("bfloat16", 3e-2, 3e-2)])
    @pytest.mark.parametrize("name", sorted(SCHEDULE))
    def test_block_schedule(self, name, dtype, rtol, atol):
        """Each case against the oracle, with every table entry past a
        slot's live pages pointing at a page of NaNs (a dead slot's at
        -1): a padding entry that is fetched poisons the output, and a
        dead slot comes out as zeros."""
        kw = dict(B=2, MB=16, NB=40)
        kw.update(self.SCHEDULE[name])
        q, kp, vp, tables, starts, lens = _case(**kw)
        if dtype == "bfloat16":       # the oracle sees what the kernel sees
            q, kp, vp = (np.array(jnp.asarray(x, dtype).astype("float32"))
                         for x in (q, kp, vp))
        page, nb = kp.shape[1], kp.shape[0]
        assert RA._pages_per_block(page, kp.shape[2], 128, dtype,
                                   tables.shape[1]) * page == 128
        tables = np.minimum(tables, nb - 2)
        kp[nb - 1] = vp[nb - 1] = np.nan
        for b in range(len(lens)):
            live_pages = -(-(starts[b] + lens[b]) // page)
            tables[b, live_pages:] = nb - 1 if lens[b] else -1
        got = _run_kernel(q, kp, vp, tables, starts, lens, dtype)
        assert np.isfinite(got[lens > 0]).all()
        _assert_live_rows_close(got, _oracle(q, kp, vp, tables, starts,
                                             lens), lens, rtol, atol)
        assert np.abs(got[lens == 0]).sum() == 0

    def test_supported_gating(self):
        import jax
        q, kp, vp, tables, starts, lens = _case()
        ok = RA.supported(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(tables), jnp.asarray(starts),
                          jnp.asarray(lens))
        assert ok == (jax.default_backend() == "tpu")
        # pathological page size always declines
        _, kp32, vp32, t32, s32, l32 = _case(BS=32, NB=16, MB=2)
        assert not RA.supported(jnp.asarray(q), jnp.asarray(kp32),
                                jnp.asarray(vp32), jnp.asarray(t32),
                                jnp.asarray(s32), jnp.asarray(l32))


class TestRaggedFunctionalOp:
    """incubate.nn.functional.ragged_paged_attend — the write+attend op
    the model families call in the unified serving step."""

    def test_write_then_attend_matches_kernel(self):
        """The op's XLA path (scatter + gather + attend) and the Pallas
        kernel reading the SAME written pools must agree on live rows."""
        q, kp, vp, tables, starts, lens = _case(
            B=3, C=4, H=4, HKV=2, starts=[8, 20, 0], lens=[4, 1, 3])
        new_k = R.normal(size=(3, 4, 2, 128)).astype("float32")
        new_v = R.normal(size=(3, 4, 2, 128)).astype("float32")
        out, (kc, vc) = IF.ragged_paged_attend(
            (jnp.asarray(kp), jnp.asarray(vp)), jnp.asarray(q),
            jnp.asarray(new_k), jnp.asarray(new_v), jnp.asarray(tables),
            jnp.asarray(starts), jnp.asarray(lens))
        kernel = np.asarray(RA.ragged_paged_attention(
            jnp.asarray(q), kc, vc, jnp.asarray(tables),
            jnp.asarray(starts), jnp.asarray(lens), interpret=True))
        _assert_live_rows_close(np.asarray(out), kernel, lens)
        # and the span scatter actually landed where the oracle expects
        kc_np = np.asarray(kc)
        for b in range(3):
            for j in range(lens[b]):
                pos = starts[b] + j
                blk = tables[b, pos // 16]
                np.testing.assert_array_equal(kc_np[blk, pos % 16],
                                              new_k[b, j])

    def test_decode_span_matches_paged_attention(self):
        """A C=1 ragged batch IS one decode step of the public paged op
        (``write_paged_kv`` + ``paged_attention``) — both must produce
        the same tokens' attention from the same pools."""
        q, kp, vp, tables, starts, lens = _case(
            B=3, C=1, H=4, HKV=2, starts=[30, 8, 55], lens=[1, 1, 1])
        new_k = R.normal(size=(3, 1, 2, 128)).astype("float32")
        new_v = R.normal(size=(3, 1, 2, 128)).astype("float32")
        ragged, _ = IF.ragged_paged_attend(
            (jnp.asarray(kp), jnp.asarray(vp)), jnp.asarray(q),
            jnp.asarray(new_k), jnp.asarray(new_v), jnp.asarray(tables),
            jnp.asarray(starts), jnp.asarray(lens))
        ctx = jnp.asarray(starts) + 1
        kc, vc = IF.write_paged_kv(
            jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(new_k[:, 0]),
            jnp.asarray(new_v[:, 0]), jnp.asarray(tables), ctx)
        paged = IF.paged_attention(jnp.asarray(q[:, 0]), kc, vc,
                                   jnp.asarray(tables), ctx)
        np.testing.assert_allclose(np.asarray(ragged[:, 0]),
                                   np.asarray(paged),
                                   rtol=2e-4, atol=2e-5)

    def test_int8_pools_equivalence(self):
        """int8 pools: the op attends over the dequantized pool — its
        output must equal the fp attend run on the pool it just wrote
        (same values, same formulation)."""
        q, kp, vp, tables, starts, lens = _case(
            B=3, C=4, H=4, HKV=2, starts=[5, 16, 0], lens=[4, 2, 1])
        cache8 = (jnp.zeros(kp.shape, jnp.int8),
                  jnp.zeros(vp.shape, jnp.int8),
                  jnp.ones(kp.shape[:3], jnp.float32),
                  jnp.ones(vp.shape[:3], jnp.float32))
        # pre-populate the prefix positions through the quantized span
        # write itself (the engine's own prefill path)
        pre_k = R.normal(size=(3, 16, 2, 128)).astype("float32")
        pre_v = R.normal(size=(3, 16, 2, 128)).astype("float32")
        cache8 = IF._paged_span_write(
            cache8, jnp.asarray(pre_k), jnp.asarray(pre_v),
            jnp.asarray(tables), jnp.asarray(np.zeros(3, np.int32)),
            jnp.asarray(starts))
        new_k = R.normal(size=(3, 4, 2, 128)).astype("float32")
        new_v = R.normal(size=(3, 4, 2, 128)).astype("float32")
        out, cache8 = IF.ragged_paged_attend(
            cache8, jnp.asarray(q), jnp.asarray(new_k),
            jnp.asarray(new_v), jnp.asarray(tables), jnp.asarray(starts),
            jnp.asarray(lens))
        # equivalence: the op's output is exactly the fp reference
        # formulation applied to the dequantized pool state it produced
        kc, vc, ks, vs = cache8
        kd, vd = IF._paged_gather_dense(kc, vc, jnp.asarray(tables),
                                        ks, vs)
        want = IF._ragged_attend_dense(jnp.asarray(q), kd, vd,
                                       jnp.asarray(starts),
                                       1.0 / np.sqrt(128))
        _assert_live_rows_close(np.asarray(out), np.asarray(want), lens,
                                rtol=1e-5, atol=1e-6)
        # and the quantized write used THE quantizer (shared formula)
        k_q, ks_ref = IF.quantize_kv(jnp.asarray(new_k[0, 0]))
        pos = int(starts[0])
        blk, off = tables[0, pos // 16], pos % 16
        np.testing.assert_array_equal(np.asarray(kc)[blk, off],
                                      np.asarray(k_q))

    def test_dead_slot_inertness(self):
        """A dead slot (len 0, OOB table) writes NOTHING — bitwise pool
        identity — and its presence leaves live slots' outputs alone."""
        q, kp, vp, tables, starts, lens = _case(
            B=2, C=4, H=4, HKV=2, starts=[12, 0], lens=[4, 0])
        oob = kp.shape[0]
        tables = tables.copy()
        tables[1, :] = oob                 # dead slot: all-OOB table
        new_k = R.normal(size=(2, 4, 2, 128)).astype("float32")
        new_v = R.normal(size=(2, 4, 2, 128)).astype("float32")
        out, (kc, vc) = IF.ragged_paged_attend(
            (jnp.asarray(kp), jnp.asarray(vp)), jnp.asarray(q),
            jnp.asarray(new_k), jnp.asarray(new_v), jnp.asarray(tables),
            jnp.asarray(starts), jnp.asarray(lens))
        # only slot 0's span landed: undo it and the pool is untouched
        kc_np = np.asarray(kc).copy()
        for j in range(4):
            pos = starts[0] + j
            kc_np[tables[0, pos // 16], pos % 16] = \
                kp[tables[0, pos // 16], pos % 16]
        np.testing.assert_array_equal(kc_np, kp)
        # live slot unperturbed by the dead one: same single-slot result
        solo, _ = IF.ragged_paged_attend(
            (jnp.asarray(kp), jnp.asarray(vp)), jnp.asarray(q[:1]),
            jnp.asarray(new_k[:1]), jnp.asarray(new_v[:1]),
            jnp.asarray(tables[:1]), jnp.asarray(starts[:1]),
            jnp.asarray(lens[:1]))
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(solo[0]),
                                   rtol=2e-5, atol=2e-6)


class TestPagedCopyBlocks:
    def test_copy_and_oob_padding(self):
        kp = R.normal(size=(8, 4, 2, 8)).astype("float32")
        vp = R.normal(size=(8, 4, 2, 8)).astype("float32")
        src = jnp.asarray(np.asarray([1, 5, 8, 8], np.int32))  # 8 = OOB pad
        dst = jnp.asarray(np.asarray([3, 0, 8, 8], np.int32))
        kc, vc = IF.paged_copy_blocks((jnp.asarray(kp), jnp.asarray(vp)),
                                      src, dst)
        kc, vc = np.asarray(kc), np.asarray(vc)
        np.testing.assert_array_equal(kc[3], kp[1])
        np.testing.assert_array_equal(vc[0], vp[5])
        # untouched rows bitwise-identical (incl. everything the OOB
        # padding entries pointed at)
        for i in (1, 2, 4, 5, 6, 7):
            np.testing.assert_array_equal(kc[i], kp[i])
