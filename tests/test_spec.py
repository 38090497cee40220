"""Speculative decoding inside the one compiled serving step
(paddle_tpu.serving.spec + the engine's verify path).

The load-bearing guarantees (docs/SERVING.md "Speculative decoding"):

- greedy outputs are TOKEN-IDENTICAL to the non-speculative engine (and
  therefore to ``model.generate()``) under every composition — chunked
  prefill churn, prefix-cache hits, int8 KV pools, preemption→restore,
  mid-verify faults, TP meshes, DP replica sets;
- ZERO compiles after warmup under draft-hit/draft-miss churn: draft
  length rides the one compiled ``(B, C)`` step as span-length DATA;
- rejection rollback is kv_len bookkeeping only — no frees, no copies;
- temperature streams are reproducible across spec-on/spec-off (PRNG
  keys derive per emitted-token index, never per step);
- acceptance telemetry lands in ``serve.spec.*`` and on ``serve_trace``
  retire events, and the bench plumbing shows > 1 token per verify
  step on a repetitive workload.

Runs on CPU (conftest forces an 8-device virtual mesh for the TP/DP
composition tests).
"""

import os
import sys
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu import resilience as rs
from paddle_tpu import serving
from paddle_tpu.serving.spec import NgramProposer

R = np.random.default_rng(0)


def _prompt(n):
    return R.integers(0, 256, size=n).astype(np.int32)


def _motif_prompt(motif_len=5, reps=3, rng=None):
    rng = rng or R
    return np.tile(rng.integers(0, 256, size=motif_len).astype(np.int32),
                   reps)


def _tiny():
    from paddle_tpu.models.llama import llama
    pt.seed(0)
    return llama("tiny")


def _engine(model=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return serving.Engine(model if model is not None else _tiny(), **kw)


def _serve(eng, prompts, max_new=16, **kw):
    rids = [eng.add_request(p, max_new_tokens=max_new, **kw)
            for p in prompts]
    outs = eng.run()
    return [outs[r] for r in rids]


class _St:
    """Minimal RequestState stand-in for proposer unit tests."""

    def __init__(self, prompt, output=()):
        class _Req:
            pass
        self.request = _Req()
        self.request.request_id = "r0"
        self.request.prompt_ids = np.asarray(prompt, np.int32)
        self.output_ids = list(output)


# ---------------------------------------------------------------------------
# the proposer
# ---------------------------------------------------------------------------

class TestNgramProposer:
    def test_basic_suffix_match(self):
        p = NgramProposer(depth=4)
        #        0  1  2  3  4  5  6  7
        st = _St([1, 2, 3, 9, 8, 1, 2, 3])
        # suffix [1,2,3] matched at position 2 → continuation [9,8,1,2]
        assert p.propose(st, 4) == [9, 8, 1, 2]
        assert p.draft_hits == 1

    def test_longest_ngram_wins(self):
        p = NgramProposer(depth=2, min_ngram=1, max_ngram=3)
        # [5,6] occurs earlier followed by 7; the bare [6] occurs
        # later followed by 0 — the longer match must win
        st = _St([5, 6, 7, 4, 6, 0, 5, 6])
        assert p.propose(st, 2) == [7, 4]

    def test_miss_returns_empty(self):
        p = NgramProposer(depth=4)
        st = _St([1, 2, 3, 4, 5, 6, 7, 8])
        assert p.propose(st, 4) == []
        assert p.draft_misses == 1

    def test_cap_bounds_draft(self):
        p = NgramProposer(depth=8)
        st = _St([1, 2, 3, 9, 8, 7, 6, 1, 2, 3])
        assert len(p.propose(st, 2)) == 2
        assert p.propose(st, 0) == []

    def test_incremental_growth_and_self_match(self):
        p = NgramProposer(depth=3)
        st = _St([4, 4, 4], output=[])
        # the current suffix's own occurrence is never its own match,
        # and the proposer prefers the longest available continuation
        # (the [4]-gram at position 0 drafts two tokens; the [4,4]-gram
        # match would draft one)
        d = p.propose(st, 3)
        assert d == [4, 4]
        st.output_ids.extend([4, 4])
        assert p.propose(st, 3) == [4, 4, 4]

    def test_rollback_rebuilds(self):
        p = NgramProposer(depth=4)
        st = _St([1, 2], output=[3, 1, 2])
        assert p.propose(st, 4) == [3, 1, 2]
        # fault-isolation rewind: output truncated below the watermark
        del st.output_ids[1:]
        d = p.propose(st, 4)      # must not crash or read stale state
        assert isinstance(d, list)

    def test_drop_and_lru_bound(self):
        p = NgramProposer(depth=2, max_requests=2)
        for i in range(4):
            st = _St([1, 2, 1, 2])
            st.request.request_id = f"r{i}"
            p.propose(st, 2)
        assert len(p) == 2        # LRU-bounded
        p.drop("r3")
        assert len(p) == 1
        p.drop("unknown")         # no-op

    def test_validation(self):
        with pytest.raises(ValueError, match="depth"):
            NgramProposer(depth=0)
        with pytest.raises(ValueError, match="min_ngram"):
            NgramProposer(depth=2, min_ngram=3, max_ngram=2)


# ---------------------------------------------------------------------------
# the speculative engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    return _tiny()


@pytest.fixture(scope="module")
def mixed_prompts():
    rng = np.random.default_rng(7)
    return [_motif_prompt(5, 3, rng), _prompt(3), _prompt(17),
            _motif_prompt(4, 4, rng), _prompt(9)]


@pytest.fixture(scope="module")
def baseline(tiny_model, mixed_prompts):
    """Non-speculative greedy outputs for the shared prompt mix."""
    return _serve(_engine(tiny_model).warmup(), mixed_prompts)


class TestSpecEngine:
    def test_greedy_token_identity_and_acceptance(self, tiny_model,
                                                  mixed_prompts,
                                                  baseline):
        eng = _engine(tiny_model, spec_decode=True, draft_depth=4).warmup()
        got = _serve(eng, mixed_prompts)
        assert got == baseline
        st = eng.spec_stats()
        assert st["proposed"] > 0 and st["accepted"] > 0
        assert 0.0 < st["accept_rate"] <= 1.0
        assert eng.kv_blocks_used == 0

    def test_draft_depth_widens_span(self, tiny_model):
        eng = _engine(tiny_model, prefill_chunk=2, spec_decode=True,
                      draft_depth=6)
        assert eng.prefill_chunk == 7      # max(chunk, depth + 1)
        with pytest.raises(ValueError, match="draft_depth"):
            _engine(tiny_model, spec_decode=True, draft_depth=0)

    def test_zero_compiles_under_hit_miss_churn(self, tiny_model,
                                                mixed_prompts):
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
        try:
            eng = _engine(tiny_model, spec_decode=True,
                          draft_depth=4).warmup()
            c0 = tel.sentinel.compiles()
            for p in mixed_prompts:          # staggered: churn
                eng.add_request(p, max_new_tokens=12)
                eng.step()
            eng.run()
            assert tel.sentinel.compiles() - c0 == 0
            assert eng._step_fn._cache_size() == 1
            assert eng._cow_fn._cache_size() == 1
        finally:
            obs.disable()

    def test_identity_with_prefix_cache_hits(self, tiny_model):
        common = _prompt(16)                 # 2 full pages
        prompts = [np.concatenate([common, _prompt(t)])
                   for t in (5, 9, 3)] + [common]
        base_eng = _engine(tiny_model)
        base = []
        for p in prompts:                    # serially: later ones hit
            base.extend(_serve(base_eng.warmup() if p is prompts[0]
                               else base_eng, [p], max_new=8))
        eng = _engine(tiny_model, spec_decode=True, draft_depth=4).warmup()
        got = []
        for p in prompts:
            got.extend(_serve(eng, [p], max_new=8))
        assert got == base
        assert eng.prefix_stats()["hits"] > 0
        assert eng.kv_blocks_used == 0

    def test_identity_with_int8_pools(self, tiny_model, mixed_prompts):
        base = _serve(_engine(tiny_model,
                              kv_cache_dtype="int8").warmup(),
                      mixed_prompts)
        eng = _engine(tiny_model, kv_cache_dtype="int8",
                      spec_decode=True, draft_depth=4).warmup()
        assert _serve(eng, mixed_prompts) == base
        assert eng.spec_stats()["proposed"] > 0

    def test_identity_across_preemption(self, tiny_model):
        prompts = [_motif_prompt(5, 3, np.random.default_rng(3)),
                   _prompt(9)]
        base = _serve(_engine(tiny_model, spec_decode=True,
                              draft_depth=4).warmup(), prompts,
                      max_new=14)
        eng = _engine(tiny_model, spec_decode=True, draft_depth=4).warmup()
        rids = [eng.add_request(p, max_new_tokens=14) for p in prompts]
        for _ in range(4):
            eng.step()
        # preempt a DECODING slot mid-speculation: the swap must round-
        # trip exactly the accepted prefix (kv_len), nothing speculative
        victim = None
        for _ in range(40):
            for _slot, st in eng.scheduler.active():
                if not st.prefilling:
                    victim = st.request.request_id
                    break
            if victim is not None:
                break
            eng.step()
        assert victim is not None and eng.preempt(victim)
        eng.run()
        assert [eng.output_ids(r) for r in rids] == base
        assert eng.kv_blocks_used == 0

    def test_mid_verify_fault_rolls_back_token_identical(
            self, tiny_model, mixed_prompts):
        base = _serve(_engine(tiny_model, spec_decode=True,
                              draft_depth=4).warmup(), mixed_prompts)
        eng = _engine(tiny_model, spec_decode=True, draft_depth=4).warmup()
        rs.clear_faults()
        rs.install_faults("serve.step@2x2")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                got = _serve(eng, mixed_prompts)
        finally:
            rs.clear_faults()
        assert got == base
        assert eng.kv_blocks_used == 0

    def test_draft_fault_degrades_not_isolates(self, tiny_model,
                                               mixed_prompts, baseline):
        """A serve.spec fault costs that slot its draft for the step —
        never the request, never an isolation."""
        eng = _engine(tiny_model, spec_decode=True, draft_depth=4).warmup()
        rs.clear_faults()
        rs.install_faults("serve.spec@0x3")
        try:
            got = _serve(eng, mixed_prompts)
        finally:
            rs.clear_faults()
        assert got == baseline
        assert eng.spec_stats()["errors"] == 3

    def test_temperature_stream_reproducible_spec_on_off(self,
                                                         tiny_model):
        """The PRNG satellite: keys derive per emitted-token index, so
        the sampled stream is invariant to how many tokens each step
        accepted — spec-on and spec-off engines draw identical
        temperature streams."""
        p = _prompt(6)

        def stream(spec):
            eng = _engine(tiny_model, spec_decode=spec, seed=11).warmup()
            rid = eng.add_request(p, max_new_tokens=10, temperature=0.9)
            eng.run()
            return eng.output_ids(rid)

        a, b = stream(False), stream(True)
        assert a == b
        assert len(set(a)) > 1       # actually sampling, not degenerate

    def test_duplicate_prompts_sample_distinct_streams(self, tiny_model):
        """Best-of-n must not collapse: the per-request seed folds the
        submission ordinal, so identical prompts submitted to one
        engine draw DIFFERENT temperature streams — while re-driving
        an identical engine the same way reproduces both."""
        p = _prompt(6)

        def streams():
            eng = _engine(tiny_model, seed=3).warmup()
            rids = [eng.add_request(p, max_new_tokens=8, temperature=0.9)
                    for _ in range(3)]
            eng.run()
            return [eng.output_ids(r) for r in rids]

        a, b = streams(), streams()
        assert a == b                      # reproducible per engine
        assert len({tuple(s) for s in a}) > 1   # but not collapsed

    def test_temperature_slots_never_draft(self, tiny_model):
        eng = _engine(tiny_model, spec_decode=True, draft_depth=4).warmup()
        rid = eng.add_request(_motif_prompt(4, 4), max_new_tokens=10,
                              temperature=0.8)
        eng.run()
        assert len(eng.output_ids(rid)) == 10
        assert eng.spec_stats()["proposed"] == 0

    def test_eos_mid_acceptance_truncates(self, tiny_model):
        """An accepted draft token that IS the eos finishes the request
        there — the rest of the accepted span is dropped, exactly like
        the one-token-at-a-time engine would have stopped."""
        p = _motif_prompt(5, 3, np.random.default_rng(5))
        ref = _serve(_engine(tiny_model).warmup(), [p], max_new=16)[0]
        eos = ref[len(ref) // 2]             # a token mid-stream
        base = _serve(_engine(tiny_model).warmup(), [p], max_new=16,
                      eos_token_id=int(eos))[0]
        got = _serve(_engine(tiny_model, spec_decode=True,
                             draft_depth=4).warmup(), [p], max_new=16,
                     eos_token_id=int(eos))[0]
        assert got == base
        assert got[-1] == eos

    def test_tight_budget_caps_draft(self, tiny_model):
        """max_new_tokens=2: at most 1 draft ever makes sense, and the
        speculative engine must not overshoot the budget."""
        prompts = [_motif_prompt(5, 3), _prompt(7)]
        base = _serve(_engine(tiny_model).warmup(), prompts, max_new=2)
        got = _serve(_engine(tiny_model, spec_decode=True,
                             draft_depth=4).warmup(), prompts, max_new=2)
        assert got == base
        assert all(len(o) == 2 for o in got)

    def test_spec_off_by_default(self, tiny_model):
        eng = _engine(tiny_model)
        assert eng.spec is None and eng.draft_depth == 0
        assert eng.spec_stats()["proposed"] == 0


# ---------------------------------------------------------------------------
# composition: TP meshes and DP replica sets
# ---------------------------------------------------------------------------

class TestSpecSharded:
    def test_tp2_token_identity(self, tiny_model, mixed_prompts,
                                baseline):
        mesh = serving.serving_mesh(tp=2)
        eng = serving.Engine(_tiny(), max_batch=4, max_seq_len=96,
                             page_size=8, prefill_chunk=8, mesh=mesh,
                             spec_decode=True, draft_depth=4).warmup()
        got = _serve(eng, mixed_prompts)
        assert got == baseline
        assert eng.spec_stats()["accepted"] > 0
        assert eng.kv_blocks_used == 0

    def test_replica_set_aggregate_stats_and_identity(self,
                                                      mixed_prompts,
                                                      baseline):
        rset = serving.EngineReplicaSet(
            [_engine(spec_decode=True, draft_depth=4)
             for _ in range(2)]).warmup()
        rids = [rset.add_request(p, max_new_tokens=16)
                for p in mixed_prompts]
        outs = rset.run()
        assert [outs[r] for r in rids] == baseline
        st = rset.spec_stats()
        assert st["proposed"] > 0 and "accept_rate" in st

    def test_evacuation_rebuilds_draft_state(self, mixed_prompts,
                                             baseline):
        """A replica failure mid-churn migrates requests whose n-gram
        state lives on the FAILED replica's proposer — the destination
        rebuilds it lazily from prompt+output and greedy outputs stay
        token-identical."""
        rset = serving.EngineReplicaSet(
            [_engine(spec_decode=True, draft_depth=4)
             for _ in range(2)]).warmup()
        rs.clear_faults()
        rs.install_faults("serve.replica@4")
        try:
            rids = []
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for p in mixed_prompts:
                    rids.append(rset.add_request(p, max_new_tokens=16))
                    rset.step()
                outs = rset.run()
        finally:
            rs.clear_faults()
        assert [outs[r] for r in rids] == baseline
        assert rset.failures == 1
        for rep in rset.replicas:
            assert rep.kv_blocks_used == 0


# ---------------------------------------------------------------------------
# telemetry + tooling plumbing
# ---------------------------------------------------------------------------

class TestSpecTelemetry:
    def test_counters_histogram_and_trace(self, tiny_model):
        sink = obs.InMemorySink()
        tel = obs.enable(sinks=[sink], crash_hooks=False)
        try:
            eng = _engine(tiny_model, spec_decode=True,
                          draft_depth=4).warmup()
            rid = eng.add_request(
                _motif_prompt(5, 3, np.random.default_rng(42)),
                max_new_tokens=12)
            eng.run()
            snap = tel.registry.snapshot()
            # the counters hold what the engine counted: a random tiny
            # model owes the motif no acceptance, and an acceptance of
            # nought is a count too (both counters exist, so a rate reads)
            assert snap["serve.spec.proposed"] == eng.spec.proposed > 0
            assert snap["serve.spec.accepted"] == eng.spec.accepted
            assert "serve.spec.accept_len" in snap
            tracer = obs.get_request_tracer()
            tl = tracer.timeline(rid)
            retire = [e for e in tl["events"]
                      if e["phase"] == "retire"][0]
            assert retire["spec_accepted"] == \
                eng._states[rid].spec_accepted
            assert retire["spec_proposed"] > 0
        finally:
            obs.disable()

    def test_non_spec_trace_carries_no_spec_fields(self, tiny_model):
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
        try:
            eng = _engine(tiny_model).warmup()
            rid = eng.add_request(_prompt(5), max_new_tokens=4)
            eng.run()
            tl = obs.get_request_tracer().timeline(rid)
            retire = [e for e in tl["events"]
                      if e["phase"] == "retire"][0]
            assert "spec_accepted" not in retire
        finally:
            obs.disable()

    def test_report_folds_acceptance(self, tiny_model, tmp_path):
        jl = tmp_path / "t.jsonl"
        tel = obs.enable(sinks=[obs.JsonlSink(str(jl))],
                         crash_hooks=False)
        try:
            eng = _engine(tiny_model, spec_decode=True,
                          draft_depth=4).warmup()
            # fixed rng with verified acceptance (see test above)
            eng.add_request(_motif_prompt(5, 3, np.random.default_rng(13)),
                            max_new_tokens=12)
            eng.run()
        finally:
            obs.disable()
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import telemetry_report
        events, _malformed = telemetry_report.load_events([str(jl)])
        agg = telemetry_report.summarize(events)
        md = telemetry_report.render(agg)
        assert "spec drafts proposed / accepted" in md
        # the serve_trace fold carries per-request acceptance
        assert any(t.get("spec_accepted") is not None
                   for t in agg["traces"])


class TestSpecBenchPlumbing:
    def test_bench_serve_spec_cpu(self):
        """The acceptance bar: on the repetitive workload the
        speculative engine emits MORE than one token per verify step
        (mean accepted tokens/step > 1.0) with outputs identical to
        the plain engine (asserted inside the bench)."""
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from decode_bench import bench_serve_spec
        r = bench_serve_spec(preset="tiny", max_batch=4, n_requests=6,
                             max_new=24, motif_len=6, motif_reps=3,
                             draft_depth=4, page_size=8)
        assert r["metric"] == "serve_spec_decode"
        assert r["tokens_per_verify_step"] > 1.0
        assert r["accept_rate"] > 0
        assert r["steps"] < r["base_steps"]
