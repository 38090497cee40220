"""EvaByte (``models/evabyte.py``) and the window+summary cache kind
(``serving/block_allocator.py`` ``WindowSummarySpec``) on the CPU, at a
small size: W = 64, c = 16 as published, 4 heads x d 16 or 32, 2 layers;
``phi`` and ``mu`` drawn at unit scale so that the summariser's softmax is
far from uniform.  The yardstick is the benchmark's plain reference
(``benchmark/reference/evabyte_ref.py``), which imports nothing of the
program; logits are compared, not tokens.
"""

import http.client
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.models import evabyte as E
from paddle_tpu.nn.layer import _swapped_params, functional_call, raw_params
from paddle_tpu.ops.pallas import ragged_attention as RA
from paddle_tpu.serving.block_allocator import (BlockAllocator,
                                                WindowSummarySpec)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmark.reference import common, evabyte_ref  # noqa: E402

W, C = 64, 16
F32 = common.Precision("f32")


def ref_config(cfg):
    return {"hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_attention_heads": cfg.num_attention_heads,
            "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
            "num_pred_heads": cfg.num_pred_heads,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
            "window_size": cfg.window_size, "chunk_size": cfg.chunk_size}


def make_model(head_dim=16, window=W, layers=2, max_pos=512, seed=0):
    """Matrices at 0.08 (logits of order 1), norm offsets at 0.1, phi and
    mu at unit scale."""
    cfg = E.EvaByteConfig(hidden_size=4 * head_dim, intermediate_size=128,
                          num_hidden_layers=layers, num_attention_heads=4,
                          max_position_embeddings=max_pos, window_size=window)
    pt.seed(seed)
    model = E.EvaByteForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    for name, leaf in raw_params(model).items():
        if name.endswith(("adaptive_phi", "adaptive_mu_k")):
            std = 1.0
        else:
            std = 0.1 if leaf.ndim == 1 else 0.08
        model._assign_by_path(name, jnp.asarray(
            std * rng.normal(size=leaf.shape), jnp.float32))
    return model


@pytest.fixture(scope="module")
def model():
    return make_model()


def reference_logits(model, ids):
    """All heads' logits ``(S, 8, 320)`` of the plain reference."""
    rc = ref_config(model.cfg)
    params = dict(raw_params(model))
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == evabyte_ref.param_shapes(rc, model.cfg.num_hidden_layers)
    x = common.sequence_hidden(evabyte_ref, params, jnp.asarray(ids), rc,
                               F32, model.cfg.num_hidden_layers)
    return np.asarray(evabyte_ref.all_heads(x, params, rc, F32))


class LogitTap:
    """The engine's own step program with the logits kept: every live
    position's ``(8, 320)`` logits by position (one request at a time)."""

    def __init__(self, eng):
        self.seen = {}
        model = eng.model

        def step(params, caches, tokens, tables, starts, lens, temps, key,
                 seeds, emit, lora_ab, adapters, aux):
            mp = {k[6:]: v for k, v in params.items()
                  if k.startswith("model.")}
            hidden, caches = functional_call(
                model.model, mp, tokens, caches=caches, seq_lens=lens,
                block_tables=tables, span_starts=starts, cache_aux=aux,
                training=False)
            with _swapped_params(model, params):
                lg = model.all_heads_logits(hidden)
            last = jnp.clip(lens - 1, 0, tokens.shape[1] - 1)
            lg0 = jnp.take_along_axis(lg[:, :, 0], last[:, None, None],
                                      axis=1)[:, 0]
            return jnp.argmax(lg0, -1).astype(jnp.int32), caches, lg

        jitted = jax.jit(step, donate_argnums=(1,))

        def tapped(*args):
            nxt, caches, lg = jitted(*args)
            starts, lens = np.asarray(args[4]), np.asarray(args[5])
            lg = np.asarray(lg)
            for r in range(len(lens)):
                for j in range(lens[r]):
                    self.seen[int(starts[r]) + j] = lg[r, j]
            return nxt, caches

        eng._step_fn = tapped

    def upto(self, n):
        return np.stack([self.seen[t] for t in range(n)])


def engine(model, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_seq_len", 512)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("enable_prefix_caching", False)
    return serving.Engine(model, **kw)


def prompt_of(n, seed=1):
    return np.random.default_rng(seed).integers(0, 320, n).tolist()


# -- (1) the uncached forward -------------------------------------------------

@pytest.mark.parametrize("head_dim", [16, 32])
def test_uncached_forward_matches_reference(head_dim):
    """>= 3 windows and a ragged tail, all 8 heads' logits."""
    model = make_model(head_dim)
    ids = np.asarray(prompt_of(3 * W + 27)).reshape(1, -1)
    got = model.all_heads_logits(model.model(jnp.asarray(ids)))[0]
    ref = reference_logits(model, ids[0])
    assert ref.shape == (3 * W + 27, 8, 320) and np.abs(ref).max() > 0.5
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(model(jnp.asarray(ids)))[0],
                               ref[:, 0], rtol=2e-5, atol=1e-4)


def test_generate_recomputes_and_matches_engine(model):
    p = prompt_of(70, seed=5)
    out = np.asarray(model.generate(jnp.asarray([p]), max_new_tokens=6))[0]
    eng = engine(model)
    rid = eng.add_request(p, max_new_tokens=6)
    eng.run()
    assert out[-6:].tolist() == eng.output_ids(rid)


# -- (2) prefill then decode through Engine ----------------------------------

# a bfloat16 pool rounds every key, value and summary to 8 bits of
# mantissa (relative 2**-9 = 0.002); logits of order 1-3 over two layers
# of 4 heads then move by a hundredth (0.0113 is the widest gap read
# here; the float32 pool's is 3e-6)
BF16_POOL_ATOL = 0.05


@pytest.mark.parametrize("kw,atol,rtol", [
    ({}, 1e-4, 2e-5),
    ({"prefill_chunk": 32}, 1e-4, 2e-5),
    ({"prefill_token_budget": 24}, 1e-4, 2e-5),
    ({"kv_cache_dtype": "bfloat16"}, BF16_POOL_ATOL, 0.0),
], ids=["float32", "chunk32", "budget24", "bfloat16-pool"])
def test_engine_prefill_then_decode_matches_reference(model, kw, atol, rtol):
    """Chunked prefill whose fan-out rows straddle a window boundary in
    one step (3 rows x 16: the second step holds 48..95), then more than
    two windows of decoding, against the reference's one full forward."""
    eng = engine(model, **kw)
    tap = LogitTap(eng)
    p = prompt_of(100)
    rid = eng.add_request(p, max_new_tokens=140)
    straddled = False
    while eng.has_work():
        plan, *_ = pending = eng.step_begin()
        windows = {sp.start // W for sp in plan if sp.is_prefill}
        straddled = straddled or len(windows) > 1
        eng.step_finish(pending)
    assert straddled or "prefill_token_budget" in kw
    full = p + eng.output_ids(rid)
    assert len(full) == 240 and eng.kv_blocks_used == 0
    ref = reference_logits(model, full)
    np.testing.assert_allclose(tap.upto(239), ref[:239], rtol=rtol,
                               atol=atol)
    if "kv_cache_dtype" in kw:
        assert np.abs(tap.upto(239) - ref[:239]).max() > 1e-4


# -- (3) the step's XLA composition, the kernel's oracle ----------------------

def _paged_attention_of(q, k, v, phi, mu, t0, n, window=W, interpret=False):
    """Attention output of the span ``[t0, t0 + n)`` through
    ``eva_paged_attend`` (or the interpreted kernel), the prefix written
    by earlier calls of the same function, a chunk a row."""
    spec = WindowSummarySpec(window, C, 512)
    alloc = BlockAllocator(48)
    h, d = q.shape[1:]

    class St:
        kv_len, blocks, pages, table = 0, [], None, None
    st = St()
    spec.seat(st)
    cache = (jnp.zeros((48, C, h, d), jnp.float32),) * 2
    B = 4

    class Sp:
        def __init__(self, row, start, n):
            self.row, self.st, self.start, self.n = row, st, start, n

    def step(spans):
        end = spans[-1].start + spans[-1].n
        spec.grow(st, end, alloc)
        tables = np.full((B, spec.table_width), 48, np.int32)
        qs = np.zeros((B, C, h, d), np.float32)
        ks, vs = np.zeros_like(qs), np.zeros_like(qs)
        lens = np.zeros((B,), np.int32)
        for sp in spans:
            tables[sp.row] = spec.table_row(st, sp.start, 48)
            sl = slice(sp.start, sp.start + sp.n)
            qs[sp.row, :sp.n], ks[sp.row, :sp.n], vs[sp.row, :sp.n] = \
                q[sl], k[sl], v[sl]
            lens[sp.row] = sp.n
        aux = {k_: jnp.asarray(v_) for k_, v_ in
               spec.step_aux(spans, B, C, 48).items()}
        out, new = IF.eva_paged_attend(
            cache, jnp.asarray(qs), jnp.asarray(ks), jnp.asarray(vs),
            jnp.asarray(tables), jnp.asarray(lens), aux, jnp.asarray(phi),
            jnp.asarray(mu))
        if interpret:
            out = RA.ragged_paged_attention(
                jnp.asarray(qs), new[0], new[1], jnp.asarray(tables),
                aux["cache_starts"], jnp.asarray(lens),
                skips=aux["summary_rows"], interpret=True,
                name="eva_ragged_paged_attention")
        st.kv_len = end
        spec.close(st, alloc)
        return np.asarray(out), new

    pos = 0
    while pos < t0:                     # the prefix, up to 4 rows a step
        spans = []
        while pos < t0 and len(spans) < B:
            m = min(C, t0 - pos, spec.span_room(pos))
            spans.append(Sp(len(spans), pos, m))
            pos += m
        _, cache = step(spans)
    out, _ = step([Sp(2, t0, n)])
    return out[2, :n]


def _qkv(seed, s=4 * W, h=4, d=16):
    r = np.random.default_rng(seed)
    return tuple(r.normal(size=(s, h, d)).astype(np.float32)
                 for _ in range(3)) + tuple(
        r.normal(size=(h, d)).astype(np.float32) for _ in range(2))


@pytest.mark.parametrize("t0,n", [
    (15, 1), (16, 1), (W - 1, 1), (W, 1), (2 * W - 1, 1), (2 * W, 1),
    (2 * W, 16), (2 * W - 16, 16), (W + 5, 11), (3 * W - 7, 7)])
def test_paged_composition_matches_reference_attention(t0, n):
    """Spans whose positions hit ``t % 16`` in {0, 15} and ``t % W`` in
    {0, W - 1}: a chunk's last position, a window's first and last."""
    q, k, v, phi, mu = _qkv(t0)
    ref = np.asarray(evabyte_ref.eva_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(phi),
        jnp.asarray(mu), W, C)).reshape(-1, 4, 16)
    got = _paged_attention_of(q, k, v, phi, mu, t0, n)
    np.testing.assert_allclose(got, ref[t0:t0 + n], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t0,n", [(W, 1), (2 * W + 16, 16), (3 * W - 1, 1)])
def test_interpreted_kernel_matches_composition(t0, n):
    """The ragged kernel with ``skips`` (the Pallas interpreter) against
    the XLA composition: with W / c = 4 summaries a window, a query's
    last summary page holds rows that are not for it."""
    q, k, v, phi, mu = _qkv(100 + t0, d=128)
    got = _paged_attention_of(q, k, v, phi, mu, t0, n, interpret=True)
    want = _paged_attention_of(q, k, v, phi, mu, t0, n)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- (4) page accounting -------------------------------------------------------

def _held_should_be(kv_len):
    return -(-(kv_len % W) // C) + -(-(kv_len // C) // C)


def test_pages_held_at_every_step_and_freed_at_each_close(model):
    eng = engine(model)
    rid = eng.add_request(prompt_of(100), max_new_tokens=140)
    st = eng._states[rid]
    closes, before = 0, 0
    while eng.has_work():
        pending = eng.step_begin()
        grown = eng.kv_blocks_used
        eng.step_finish(pending)
        if st.finished:
            break
        win, summ = eng.cache_spec.counts(st)
        assert win + summ == eng.kv_blocks_used == _held_should_be(st.kv_len)
        assert win == -(-(st.kv_len % W) // C)
        assert summ == -(-(st.kv_len // C) // C)
        if st.kv_len // W > before // W:
            # a window closed in this step_finish: its W / c pages went
            closes += st.kv_len // W - before // W
            assert grown - eng.kv_blocks_used >= W // C
        before = st.kv_len
    assert closes == 3 and eng.kv_blocks_used == 0
    assert eng.kv.allocator.free_blocks == 64


def test_admission_reckons_window_and_summary_pages(model):
    eng = engine(model)
    need = eng.scheduler.blocks_for
    assert need(40) == 3 + 1 and need(64) == 4 + 1
    assert need(300) == 4 + 2 and need(512) == 4 + 2
    spec = WindowSummarySpec(2048, 16, 32768)
    assert spec.blocks_for(4608) == 128 + 18
    assert spec.table_width == 128 + 128
    with pytest.raises(serving.errors.BudgetUnsatisfiable):
        engine(model, num_blocks=5).add_request(prompt_of(100),
                                                max_new_tokens=200)


@pytest.mark.parametrize("at", [40, 70, 130],
                         ids=["mid-window", "after-a-close", "decoding"])
def test_preempt_and_restore_is_token_identical(model, at):
    p = prompt_of(100, seed=3)
    eng = engine(model)
    want_id = eng.add_request(p, max_new_tokens=60)
    eng.run()
    want = eng.output_ids(want_id)

    eng = engine(model, max_batch=2)
    rid = eng.add_request(p, max_new_tokens=60)
    st = eng._states[rid]
    while st.kv_len < at:
        eng.step()
    held = eng.kv_blocks_used
    assert eng.preempt(rid) and eng.kv_blocks_used == 0
    assert st.swapped[0] == held == _held_should_be(st.kv_len)
    eng.run()
    assert eng.output_ids(rid) == want and st.preempts == 1
    assert eng.kv_blocks_used == 0


def test_pool_too_small_forces_a_preemption(model):
    """Two requests whose peaks each fit the pool and together do not:
    both are admitted (nothing is reserved ahead), the pool runs dry as
    they grow, the younger goes to host and comes back; both finish
    with the tokens they have alone; nothing leaks."""
    ps = [prompt_of(90, seed=7), prompt_of(80, seed=8)]
    want = []
    for p in ps:
        solo = engine(model)
        rid = solo.add_request(p, max_new_tokens=120)
        solo.run()
        want.append(solo.output_ids(rid))
    eng = engine(model, max_batch=2, num_blocks=8)
    rids = [eng.add_request(p, max_new_tokens=120) for p in ps]
    eng.run()
    assert [eng.output_ids(r) for r in rids] == want
    assert eng._states[rids[0]].preempts == 0
    assert eng._states[rids[1]].preempts >= 1
    assert eng.kv_blocks_used == 0 and eng.kv.allocator.free_blocks == 8


def test_a_lone_request_gives_up_fan_out_rows_before_its_pool_runs_dry(model):
    """A pool of exactly the request's peak: the step that straddles a
    window boundary would hold the old window and the new one; the
    request advances by fewer rows instead, and is never preempted."""
    p = prompt_of(200, seed=9)
    eng = engine(model, max_batch=4, num_blocks=6)
    assert eng.scheduler.blocks_for(230) == 5
    rid = eng.add_request(p, max_new_tokens=30)
    eng.run()
    solo = engine(model, max_batch=4)
    want = solo.add_request(p, max_new_tokens=30)
    solo.run()
    assert eng.output_ids(rid) == solo.output_ids(want)
    assert eng._states[rid].preempts == 0 and eng.kv_blocks_used == 0


def test_max_queue_is_typed(model):
    eng = engine(model, max_batch=1, max_queue=1)
    eng.add_request(prompt_of(20), max_new_tokens=4)
    with pytest.raises(serving.errors.QueueFull):
        eng.add_request(prompt_of(20), max_new_tokens=4)
    eng.run()
    assert eng.kv_blocks_used == 0


# -- (5) what does not serve this cache kind is refused by name ---------------

@pytest.mark.parametrize("kw,named", [
    ({"enable_prefix_caching": True}, "prefix caching"),
    ({"role": "prefill"}, "role='prefill'"),
    ({"role": "decode"}, "role='decode'"),
    ({"spec_decode": True}, "spec_decode"),
    ({"lora": object()}, "lora"),
    ({"weight_quant": "int8"}, "weight_quant"),
    ({"kv_cache_dtype": "int8"}, "int8 pools"),
    ({"mesh": object()}, "mesh"),
])
def test_refused_features_raise_at_construction(model, kw, named):
    kw = dict({"enable_prefix_caching": False}, **kw)
    with pytest.raises(NotImplementedError) as e:
        serving.Engine(model, max_batch=2, max_seq_len=128, **kw)
    assert named in str(e.value) and "window+summary" in str(e.value)
    # nothing was done to the model on the way to the refusal
    assert not any(hasattr(l, "weight_scale") for l in model.sublayers())


@pytest.mark.parametrize("kw,named", [
    ({"page_size": 8}, "page_size=8"),
    ({"prefill_chunk": 24}, "prefill_chunk=24"),
])
def test_other_page_and_chunk_sizes_are_refused_by_name(model, kw, named):
    with pytest.raises(ValueError) as e:
        serving.Engine(model, max_batch=2, max_seq_len=128,
                       enable_prefix_caching=False, **kw)
    assert named in str(e.value) and "16" in str(e.value)


# -- (6) a summary of the query's own window never enters its softmax ---------

def _decode_logits_after_perturbing(model, rows, upto=188):
    """Prefill 100 and decode to position 150 (window 2 is open, chunks 8
    and 9 of it are summarised at rows 8 and 9 of summary page 0), add
    100 to the given summary rows in every layer's pools, decode on to
    ``upto`` (window 2 closes at 192): the logits of 150 .. upto."""
    eng = engine(model)
    tap = LogitTap(eng)
    rid = eng.add_request(prompt_of(100, seed=11), max_new_tokens=120)
    st = eng._states[rid]
    while st.kv_len < 150:
        eng.step()
    page = st.pages.summaries[0]
    eng.kv.caches = [tuple(pool.at[page, jnp.asarray(rows)].add(100.0)
                           for pool in layer) for layer in eng.kv.caches]
    while st.kv_len < upto:
        eng.step()
    return np.stack([tap.seen[t] for t in range(150, upto)])


def test_own_window_summaries_are_not_seen(model):
    clean = _decode_logits_after_perturbing(model, [15])   # an unused row
    own = _decode_logits_after_perturbing(model, [8, 9])
    np.testing.assert_array_equal(own, clean)
    earlier = _decode_logits_after_perturbing(model, [5])  # window 1's
    assert np.abs(earlier - clean).max() > 1e-2


# -- the tracing ---------------------------------------------------------------

def test_counters_gauges_and_trace_point_after_two_closes():
    """At the published window: a request of 4,100 prompt bytes closes
    two windows of 2,048: 2 closes, 256 pages freed, >= 256 summary rows."""
    model = make_model(window=2048, layers=1, max_pos=8192, seed=2)
    tel = obs.enable(crash_hooks=False)
    try:
        eng = serving.Engine(model, max_batch=32, max_seq_len=4352,
                             num_blocks=192, enable_prefix_caching=False)
        rid = eng.add_request(prompt_of(4100, seed=4), max_new_tokens=3)
        st = eng._states[rid]
        peak = {"window": 0, "summary": 0}
        reg = tel.registry
        while eng.has_work():
            eng.step()
            if not st.finished:
                peak["window"] = max(peak["window"], reg.gauge(
                    "serve.eva.window_blocks").value)
                peak["summary"] = max(peak["summary"], reg.gauge(
                    "serve.eva.summary_blocks").value)
                assert reg.gauge("serve.kv_blocks_used").value \
                    == reg.gauge("serve.eva.window_blocks").value \
                    + reg.gauge("serve.eva.summary_blocks").value
        assert reg.counter("serve.eva.windows_closed").value == 2
        assert reg.counter("serve.eva.pages_freed").value == 256
        assert reg.counter("serve.eva.summary_rows").value == 4102 // 16
        # the gauges are set when a step is accounted, after its closes:
        # a step takes 32 rows x 16 positions, so the open window is
        # seen at 512, 1,024 and 1,536 positions (96 pages), never full
        assert peak["window"] == 96 and peak["summary"] == 16
        points = [e for e in
                  obs.get_request_tracer().timeline(rid)["events"]
                  if e["phase"] == "window_close"]
        assert len(points) == 2
        assert [p["pages_freed"] for p in points] == [128, 128]
        assert eng.kv_blocks_used == 0
    finally:
        obs.disable()


# -- the serving host ----------------------------------------------------------

def test_streams_through_serving_server(model):
    p = prompt_of(70, seed=13)
    eng = engine(model)
    rid = eng.add_request(p, max_new_tokens=8)
    eng.run()
    want = eng.output_ids(rid)

    eng = engine(model).warmup()
    srv = serving.ServingServer(eng, port=0)
    host, port = srv.start()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": p, "max_tokens": 8,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        raw = r.read().decode()
        assert r.status == 200
        toks = [json.loads(line[6:])["choices"][0]["token_id"]
                for line in raw.splitlines()
                if line.startswith("data: ") and line != "data: [DONE]"]
        assert toks == want and "data: [DONE]" in raw
        srv.begin_drain()
        assert srv.wait_drained(timeout=30)
    finally:
        srv.close()
    assert eng.kv_blocks_used == 0


def test_zero_compiles_after_warmup(model):
    """One step program whatever the mix: decode rows, fan-out rows, a
    window close, a preemption and its restore."""
    from paddle_tpu.observability.recompile import RecompileSentinel

    eng = engine(model, max_batch=3).warmup()
    sent = RecompileSentinel()
    sent.install()
    try:
        c0 = sent.compiles()
        a = eng.add_request(prompt_of(100, seed=21), max_new_tokens=40)
        for _ in range(4):
            eng.step()
        b = eng.add_request(prompt_of(50, seed=22), max_new_tokens=30)
        for _ in range(6):
            eng.step()
        assert eng.preempt(b)
        eng.run()
        assert sent.compiles() == c0
    finally:
        sent.uninstall()
    assert len(eng.output_ids(a)) == 40 and len(eng.output_ids(b)) == 30


def test_regions_and_scopes_reach_the_compiled_step(model):
    """The model's six regions and no other; the summariser and the
    attention are both ``attn_core``, told apart by two named scopes."""
    import re

    import chip_smoke
    from paddle_tpu.observability.regions import REGIONS

    paths = set(re.findall(r'op_name="([^"]*)"',
                           chip_smoke.serve_step_hlo(engine(model))))
    found = {part for p in paths for part in p.split("/")} & set(REGIONS)
    assert found == {"embed", "norm", "attn_proj", "attn_core", "mlp",
                     "lm_head_loss"}
    assert any("attn_core/eva_summarise" in p for p in paths)
    assert any("attn_core/eva_attend" in p for p in paths)


# -- the kernel's gate ----------------------------------------------------------

@pytest.mark.parametrize("c,h,h_kv,ok", [
    (16, 32, 32, True),      # the EvaByte cell's step
    (16, 32, 8, True),       # the Mistral cell's step
    (128, 32, 8, True),      # compiles for v5e (PR 26)
    (128, 32, 32, False),    # refused by a deviceless compile
    (256, 32, 8, False),     # refused by a deviceless compile
])
def test_ragged_kernel_declines_what_vmem_cannot_hold(monkeypatch, c, h, h_kv,
                                                      ok):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((32, c, h, 128), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((64, 16, h_kv, 128), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((32, 256), jnp.int32)
    assert RA.supported(q, pool, pool, tables, None, None) is ok
