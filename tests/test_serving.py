"""Continuous-batching serving engine (paddle_tpu.serving).

The load-bearing guarantee: under greedy decoding, every request served
through the shared paged pools is TOKEN-IDENTICAL to a standalone
``model.generate()`` call — continuous batching is a throughput
optimization, not an accuracy trade.  Plus the allocator/scheduler
invariants the engine's safety rests on (reservation at admission,
reclaim at finish, inert inactive slots).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.serving.block_allocator import (BlockAllocator,
                                                PagedKVCache, PrefixCache)
from paddle_tpu.serving.scheduler import Request, Scheduler

R = np.random.default_rng(0)


def _prompt(n):
    return R.integers(0, 256, size=n).astype(np.int32)


@pytest.fixture(scope="module")
def tiny_llama():
    from paddle_tpu.models.llama import llama
    pt.seed(0)
    return llama("tiny")


# ---------------------------------------------------------------------------
# allocator / pools
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_allocate_free_roundtrip(self):
        a = BlockAllocator(8)
        ids = a.allocate(5)
        assert len(set(ids)) == 5 and a.used_blocks == 5
        assert not a.can_allocate(4)
        a.free(ids[:2])
        assert a.free_blocks == 5
        a.free(ids[2:])
        assert a.used_blocks == 0 and a.free_blocks == 8

    def test_exhaustion_raises(self):
        a = BlockAllocator(2)
        a.allocate(2)
        with pytest.raises(RuntimeError, match="exhausted"):
            a.allocate(1)

    def test_double_free_raises(self):
        a = BlockAllocator(2)
        ids = a.allocate(1)
        a.free(ids)
        with pytest.raises(ValueError, match="double free"):
            a.free(ids)

    def test_unknown_id_free_raises(self):
        """Regression: freeing an id outside [0, num_blocks) — or one
        that was never allocated — must raise instead of silently
        appending garbage to the free list (which a later allocate
        would hand to a request as a 'valid' page)."""
        a = BlockAllocator(4)
        ids = a.allocate(2)
        for bad in (-1, 4, 99):
            with pytest.raises(ValueError, match="unknown KV block"):
                a.free([bad])
        with pytest.raises(ValueError, match="double free"):
            a.free([3])          # in range but never allocated
        # the failed frees corrupted nothing: state still consistent
        assert a.used_blocks == 2 and a.free_blocks == 2
        a.free(ids)
        assert a.used_blocks == 0 and a.free_blocks == 4

    def test_share_refcounts(self):
        a = BlockAllocator(4)
        (bid,) = a.allocate(1)
        a.share(bid)
        assert a.refcount(bid) == 2
        a.free([bid])
        assert a.used_blocks == 1     # one reference still out
        a.free([bid])
        assert a.used_blocks == 0 and a.free_blocks == 4
        with pytest.raises(ValueError, match="neither live nor cached"):
            a.share(bid)

    def test_pool_shapes_and_int8(self):
        kv = PagedKVCache(num_layers=2, num_blocks=4, page_size=8,
                          num_kv_heads=2, head_dim=16)
        assert len(kv.caches) == 2
        assert kv.caches[0][0].shape == (4, 8, 2, 16)
        assert kv.oob_block == 4
        kv8 = PagedKVCache(2, 4, 8, 2, 16, dtype="int8")
        assert kv8.quantized and len(kv8.caches[0]) == 4
        assert kv8.caches[0][2].shape == (4, 8, 2)
        assert kv8.nbytes() < kv.nbytes()


class TestPrefixCache:
    def test_page_keys_chain(self):
        """Chained digests: a shared head gives shared keys; the first
        divergent page changes ITS key and every later one."""
        page = 4
        a = np.arange(12, dtype=np.int32)
        b = a.copy()
        b[5] += 1                      # diverge inside page 1
        ka, kb = (PrefixCache.page_keys(x, page) for x in (a, b))
        assert len(ka) == 3
        assert ka[0] == kb[0]
        assert ka[1] != kb[1] and ka[2] != kb[2]
        # partial trailing page is not hashable
        assert len(PrefixCache.page_keys(a[:11], page)) == 2
        assert len(PrefixCache.page_keys(a[:3], page)) == 0

    def test_register_lookup_and_first_writer_wins(self):
        a = BlockAllocator(8)
        pc = PrefixCache(a, 4)
        keys = PrefixCache.page_keys(np.arange(8, dtype=np.int32), 4)
        ids = a.allocate(2)
        assert pc.register(keys[0], ids[0])
        assert pc.register(keys[1], ids[1])
        assert not pc.register(keys[0], 7)    # duplicate: first wins
        assert pc.lookup(keys) == ids
        # longest-prefix semantics: a miss stops the match
        other = PrefixCache.page_keys(np.arange(1, 9, dtype=np.int32), 4)
        assert pc.lookup([keys[0]] + other[1:]) == [ids[0]]

    def test_refcount_zero_blocks_become_evictable_then_lru_evict(self):
        a = BlockAllocator(2)
        pc = PrefixCache(a, 4)
        ids = a.allocate(2)
        k1, k2 = PrefixCache.page_keys(np.arange(8, dtype=np.int32), 4)
        pc.register(k1, ids[0])
        pc.register(k2, ids[1])
        a.free(ids)                    # refcounts 0 → cached, not free
        assert a.used_blocks == 0 and a.cached_blocks == 2
        assert a.free_blocks == 2      # still allocatable via eviction
        assert pc.lookup([k1, k2]) == ids
        # allocation pressure evicts LRU-first and drops its hash entry
        got = a.allocate(1)
        assert got == [ids[0]] and a.evictions == 1
        assert pc.lookup([k1, k2]) == []   # chain broken at page 0
        a.free(got)
        assert len(pc) == 1                # k2's entry survives the evict

    def test_share_revives_cached_block(self):
        a = BlockAllocator(2)
        pc = PrefixCache(a, 4)
        (bid,) = a.allocate(1)
        (key,) = PrefixCache.page_keys(np.arange(4, dtype=np.int32), 4)
        pc.register(key, bid)
        a.free([bid])
        assert a.cached_blocks == 1
        a.share(bid)                   # a later request hits the page
        assert a.refcount(bid) == 1 and a.cached_blocks == 0
        assert pc.lookup([key]) == [bid]   # registration survives
        a.free([bid])
        assert a.cached_blocks == 1


class TestScheduler:
    def test_fixed_shapes_and_inert_slots(self):
        a = BlockAllocator(16)
        s = Scheduler(max_batch=3, page_size=8, max_blocks_per_seq=4,
                      allocator=a, oob_block=16)
        s.submit(Request(prompt_ids=_prompt(5), max_new_tokens=3))
        st = s.admit_next()
        st.pending_token, st.kv_len = 7, 5
        plan = s.plan_spans(chunk=4)
        tokens, tables, starts, lens, temps, seeds, emit, adapters = \
            s.span_arrays(plan, 4)
        assert tokens.shape == (3, 4) and tables.shape == (3, 4)
        assert adapters.shape == (3,) and (adapters == 0).all()
        # inactive slots carry the OOB sentinel everywhere
        assert (tables[1:] == 16).all() and lens[1] == 0
        # prompt fully written → a single decode-token span at kv_len
        assert tokens[0, 0] == 7 and starts[0] == 5 and lens[0] == 1
        # reservation covers prompt + max_new (5+3 → 1 block of 8)
        assert a.used_blocks == 1
        s.finish(st, "length")
        assert a.used_blocks == 0 and s.slots[0] is None

    def test_admission_gates_on_blocks_fifo(self):
        a = BlockAllocator(2)
        s = Scheduler(max_batch=4, page_size=8, max_blocks_per_seq=2,
                      allocator=a, oob_block=2)
        s.submit(Request(prompt_ids=_prompt(10), max_new_tokens=6))  # 2 blk
        s.submit(Request(prompt_ids=_prompt(3), max_new_tokens=2))   # 1 blk
        first = s.admit_next()
        assert first is not None and a.free_blocks == 0
        # pool empty: the small request WAITS (no starvation reorder)
        assert s.admit_next() is None and s.queue_depth() == 1
        s.finish(first, "length")
        assert s.admit_next() is not None


class TestSpanFanOutPlan:
    """``plan_spans`` alone: a prefilling request takes the rows of
    slots that hold no request (ISSUE 31)."""

    def _sched(self, max_batch, prompts, page=8, mb=32):
        a = BlockAllocator(256)
        s = Scheduler(max_batch=max_batch, page_size=page,
                      max_blocks_per_seq=mb, allocator=a, oob_block=256)
        sts = []
        for n in prompts:
            s.submit(Request(prompt_ids=_prompt(n), max_new_tokens=4))
            sts.append(s.admit_next())
        return s, sts

    @pytest.mark.parametrize("plen,free,budget,want", [
        # (prompt, free rows, budget) -> the fan's (start, n) in order
        (100, 3, None, [(0, 4), (4, 4), (8, 4), (12, 4)]),
        (10, 3, None, [(0, 4), (4, 4), (8, 2)]),      # ends with the prompt
        (100, 3, 10, [(0, 4), (4, 4), (8, 2)]),       # ends with the budget
        (100, 3, 4, [(0, 4)]),                        # one chunk: no fan-out
        (3, 3, None, [(0, 3)]),                       # fits its own row
        (100, 0, None, [(0, 4)]),                     # no free row
    ])
    def test_one_prompt_over_free_rows(self, plen, free, budget, want):
        s, (st,) = self._sched(free + 1, [plen])
        plan = s.plan_spans(chunk=4, budget=budget)
        assert [(sp.start, sp.n) for sp in plan] == want
        assert all(sp.st is st and sp.is_prefill for sp in plan)
        assert plan[0].row == st.slot
        assert len({sp.row for sp in plan}) == len(plan)
        assert sum(sp.n for sp in plan) <= (budget or (free + 1) * 4)
        assert plan[-1].start + plan[-1].n <= plen
        tokens, tables, starts, lens, temps, seeds, emit, adapters = \
            s.span_arrays(plan, 4)
        for sp in plan:
            assert (tables[sp.row] == st.table).all()
            assert starts[sp.row] == sp.start and lens[sp.row] == sp.n
            assert (tokens[sp.row, :sp.n] ==
                    st.request.prompt_ids[sp.start:sp.start + sp.n]).all()
            assert seeds[sp.row] == st.sample_seed
        idle = sorted(set(range(free + 1)) - {sp.row for sp in plan})
        assert (lens[idle] == 0).all() and (tables[idle] == 256).all()

    def test_decode_and_draft_rows_untouched(self):
        """Decode slots keep their own row, their draft and nothing
        else; only rows of EMPTY slots are dealt out, and the fan's
        sampling policy and adapter ride every row of it."""
        s, (dec, spec, pre) = self._sched(6, [5, 6, 40])
        dec.kv_len, dec.pending_token = 5, 7
        spec.kv_len, spec.pending_token, spec.draft = 6, 9, [1, 2]
        pre.request.temperature, pre.request.adapter_slot = 0.5, 2
        plan = s.plan_spans(chunk=4)
        by_row = {sp.row: sp for sp in plan}
        assert by_row[0] == (0, dec, 5, 1, False)
        assert by_row[1] == (1, spec, 6, 3, False)
        fan = [sp for sp in plan if sp.st is pre]
        assert [sp.row for sp in fan] == [2, 3, 4, 5]
        assert [sp.start for sp in fan] == [0, 4, 8, 12]
        assert [[sp.row for sp in g] for g in serving.scheduler.by_request(
            plan)] == [[0], [1], [2, 3, 4, 5]]
        tokens, _t, starts, lens, temps, _s, emit, adapters = \
            s.span_arrays(plan, 4, spec_emit=True)
        assert list(tokens[1, :3]) == [9, 1, 2] and lens[1] == 3
        assert (temps[2:] == 0.5).all() and (adapters[2:] == 2).all()
        assert (temps[:2] == 0).all() and (adapters[:2] == 0).all()

    @pytest.mark.parametrize("order", [(0, 1), (1, 0)])
    def test_two_prompts_oldest_admission_first(self, order):
        """The free rows finish the OLDEST prompt first (interleaving
        two prompts delays both first tokens), whatever slot it sits
        in; the younger one takes what is left."""
        s, sts = self._sched(6, [9, 9])       # 9 tokens: 3 rows each
        old, young = sts[order[0]], sts[order[1]]
        old.admit_seq, young.admit_seq = 0, 1
        plan = s.plan_spans(chunk=4)
        rows = {id(st): [(sp.row, sp.start, sp.n) for sp in plan
                         if sp.st is st] for st in sts}
        assert rows[id(old)] == [(old.slot, 0, 4), (2, 4, 4), (3, 8, 1)]
        assert rows[id(young)] == [(young.slot, 0, 4), (4, 4, 4),
                                   (5, 8, 1)]
        # one free row fewer: the younger prompt's tail waits
        s2, sts2 = self._sched(5, [9, 9])
        plan2 = s2.plan_spans(chunk=4)
        assert sum(sp.n for sp in plan2 if sp.st is sts2[0]) == 9
        assert sum(sp.n for sp in plan2 if sp.st is sts2[1]) == 8


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class TestEngine:
    def test_greedy_token_identity_vs_generate(self, tiny_llama):
        """The acceptance bar: every request in a mixed continuous batch
        decodes exactly what a standalone generate() would."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=4, max_seq_len=64,
                             page_size=8).warmup()
        prompts = [_prompt(n) for n in (3, 7, 12, 5, 9, 17)]
        new = [8, 5, 10, 3, 7, 6]
        rids = [eng.add_request(p, max_new_tokens=m)
                for p, m in zip(prompts, new)]
        outs = eng.run()
        assert eng.kv_blocks_used == 0
        for p, m, rid in zip(prompts, new, rids):
            ref = np.asarray(model.generate(
                jnp.asarray(p)[None], max_new_tokens=m,
                temperature=0.0))[0, len(p):]
            assert np.array_equal(ref, np.asarray(outs[rid])), rid

    def test_join_leave_mid_flight_identity(self, tiny_llama):
        """Requests entering a RUNNING batch must not perturb the ones
        already decoding (slot isolation through the paged pools)."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=3, max_seq_len=64,
                             page_size=8).warmup()
        p1, p2 = _prompt(6), _prompt(11)
        r1 = eng.add_request(p1, max_new_tokens=9)
        for _ in range(3):
            eng.step()
        r2 = eng.add_request(p2, max_new_tokens=4)   # joins mid-flight
        while eng.has_work():
            eng.step()
        for p, m, rid in ((p1, 9, r1), (p2, 4, r2)):
            ref = np.asarray(model.generate(
                jnp.asarray(p)[None], max_new_tokens=m,
                temperature=0.0))[0, len(p):]
            assert np.array_equal(ref, np.asarray(eng.output_ids(rid)))

    def test_eos_stops_and_reclaims(self, tiny_llama):
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8).warmup()
        p = _prompt(5)
        # find what greedy emits first, then use it as the eos id
        first = int(np.asarray(model.generate(
            jnp.asarray(p)[None], max_new_tokens=1, temperature=0.0))[0, -1])
        rid = eng.add_request(p, max_new_tokens=32, eos_token_id=first)
        eng.run()
        st = eng._states[rid]
        assert st.finish_reason == "eos"
        assert eng.output_ids(rid) == [first]
        assert eng.kv_blocks_used == 0

    def test_queueing_beyond_capacity(self, tiny_llama):
        """More requests than slots: the overflow waits, then joins as
        slots free — everything still drains token-identical."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=32,
                             page_size=8).warmup()
        prompts = [_prompt(n) for n in (4, 6, 3, 9, 5)]
        rids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        assert eng.scheduler.queue_depth() == 5
        outs = eng.run()
        assert len(outs) == 5 and eng.kv_blocks_used == 0
        for p, rid in zip(prompts, rids):
            ref = np.asarray(model.generate(
                jnp.asarray(p)[None], max_new_tokens=4,
                temperature=0.0))[0, len(p):]
            assert np.array_equal(ref, np.asarray(outs[rid]))

    def test_int8_pools_serve(self, tiny_llama):
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                             page_size=8, kv_cache_dtype="int8").warmup()
        assert eng.kv.quantized
        rid = eng.add_request(_prompt(7), max_new_tokens=6)
        outs = eng.run()
        assert len(outs[rid]) == 6 and eng.kv_blocks_used == 0

    def test_sampling_and_mixed_policies(self, tiny_llama):
        """Greedy and sampling requests share one compiled step; the
        sampled stream is deterministic per engine seed."""
        pg, ps = _prompt(5), _prompt(5)
        outs = []
        for _ in range(2):
            eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                                 page_size=8, seed=7).warmup()
            g = eng.add_request(pg, max_new_tokens=6)
            s = eng.add_request(ps, max_new_tokens=6,
                                temperature=0.8)
            o = eng.run()
            outs.append((o[g], o[s]))
        assert outs[0] == outs[1]

    def test_streaming_callbacks_and_detokenize(self, tiny_llama):
        got = []
        eng = serving.Engine(
            tiny_llama, max_batch=2, max_seq_len=64, page_size=8,
            detokenize=lambda ids: " ".join(str(i) for i in ids)).warmup()
        rid = eng.add_request(
            _prompt(4), max_new_tokens=3,
            on_token=lambda r, t, txt: got.append((r, t, txt)))
        events = [ev for ev in eng.stream()]
        assert [t for _, t, _ in got] == eng.output_ids(rid)
        # incremental text concatenates back to the full detokenization
        assert "".join(txt for _, _, txt in got) == \
            " ".join(str(i) for i in eng.output_ids(rid))
        assert events[-1].finished and events[-1].finish_reason == "length"

    def test_gpt_family(self):
        from paddle_tpu.models.gpt import gpt
        pt.seed(0)
        model = gpt("tiny")
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8).warmup()
        p = _prompt(9)
        rid = eng.add_request(p, max_new_tokens=6)
        outs = eng.run()
        ref = np.asarray(model.generate(
            jnp.asarray(p)[None], max_new_tokens=6,
            temperature=0.0))[0, len(p):]
        assert np.array_equal(ref, np.asarray(outs[rid]))
        assert eng.kv_blocks_used == 0

    def test_unsupported_configs_raise(self, tiny_llama):
        from paddle_tpu.models.mixtral import mixtral
        pt.seed(0)
        with pytest.raises(NotImplementedError, match="paged"):
            serving.Engine(mixtral("tiny"))
        with pytest.raises(ValueError, match="max_seq_len"):
            eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=32,
                                 page_size=8)
            eng.add_request(_prompt(30), max_new_tokens=8)

    def test_request_validation(self, tiny_llama):
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=32,
                             page_size=8)
        with pytest.raises(ValueError, match="empty"):
            eng.add_request(np.zeros((0,), np.int32))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request(_prompt(3), max_new_tokens=0)

    def test_unsatisfiable_budget_rejected_at_add(self, tiny_llama):
        """A request needing more blocks than the WHOLE pool could sit
        at the queue head forever (admit_next never succeeds, no slot
        active, has_work() true) — run()/stream() would spin.  It must
        be rejected at add_request."""
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                             page_size=8, num_blocks=2)
        with pytest.raises(ValueError, match="KV blocks"):
            eng.add_request(_prompt(20), max_new_tokens=20)  # 5 > 2
        # a satisfiable one still serves
        rid = eng.add_request(_prompt(5), max_new_tokens=3)
        outs = eng.run()
        assert len(outs[rid]) == 3 and eng.kv_blocks_used == 0

    def test_run_returns_requests_finished_in_manual_steps(self,
                                                           tiny_llama):
        """run()'s drain dict must include requests that finished during
        manual step() calls BEFORE run() (staggered admission), and a
        second run() must not re-report them."""
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                             page_size=8).warmup()
        r1 = eng.add_request(_prompt(4), max_new_tokens=1)
        eng.step()                       # r1 finishes right here
        assert eng._states[r1].finished
        r2 = eng.add_request(_prompt(7), max_new_tokens=3)
        outs = eng.run()
        assert set(outs) == {r1, r2}
        assert outs[r1] == eng.output_ids(r1)
        assert eng.run() == {}           # nothing new since

    def test_finished_state_retention_is_bounded(self, tiny_llama):
        """A long-running engine must not leak one RequestState per
        request served: only the `keep_finished` most recent stay
        queryable, older ones are evicted."""
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=32,
                             page_size=8, keep_finished=2).warmup()
        rids = [eng.add_request(_prompt(3), max_new_tokens=2)
                for _ in range(5)]
        outs = eng.run()
        assert set(outs) == set(rids)    # run() reported ALL of them
        assert len(eng._states) == 2     # ...but retains only the cap
        assert eng.output_ids(rids[-1])  # newest still queryable
        with pytest.raises(KeyError):
            eng.output_ids(rids[0])      # oldest evicted

    def test_run_burst_finish_beats_eviction(self, tiny_llama):
        """More requests than keep_finished retiring in ONE decode step:
        run() must still report every one of them (outputs are captured
        at finish time, before the retention cap evicts the state)."""
        eng = serving.Engine(tiny_llama, max_batch=4, max_seq_len=32,
                             page_size=8, keep_finished=1).warmup()
        rids = [eng.add_request(_prompt(3), max_new_tokens=2)
                for _ in range(4)]   # same budget → all 4 finish together
        outs = eng.run()
        assert set(outs) == set(rids)
        assert all(len(v) == 2 for v in outs.values())
        assert len(eng._states) == 1   # the cap still holds afterwards

    def test_duplicate_request_id_rejected(self, tiny_llama):
        """A user-supplied id colliding with a live or retained request
        must raise — a silent overwrite would lose the first request's
        output and double-count it in the retention deque."""
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=32,
                             page_size=8).warmup()
        eng.add_request(_prompt(3), max_new_tokens=2, request_id="x")
        with pytest.raises(ValueError, match="already in use"):
            eng.add_request(_prompt(4), max_new_tokens=2, request_id="x")
        eng.run()
        # still retained (finished) → still a collision
        with pytest.raises(ValueError, match="already in use"):
            eng.add_request(_prompt(4), max_new_tokens=2, request_id="x")

    def test_raising_on_token_callback_is_isolated(self, tiny_llama):
        """One request's broken callback must not tear down step() —
        the batch's OTHER requests' events would be lost mid-stream."""
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=32,
                             page_size=8).warmup()
        got = []
        def bad(r, t, txt):
            raise RuntimeError("consumer bug")
        r1 = eng.add_request(_prompt(3), max_new_tokens=3, on_token=bad)
        r2 = eng.add_request(_prompt(5), max_new_tokens=3,
                             on_token=lambda r, t, txt: got.append(t))
        with pytest.warns(RuntimeWarning, match="on_token"):
            outs = eng.run()
        assert len(outs[r1]) == 3 and len(outs[r2]) == 3
        assert got == outs[r2]           # healthy consumer saw everything
        assert eng.kv_blocks_used == 0

    def test_streaming_detok_window_stays_linear(self, tiny_llama,
                                                 monkeypatch):
        """The incremental text path re-detokenizes only a bounded tail
        window; across re-anchors the streamed pieces still concatenate
        to the full detokenization (compositional tokenizer)."""
        from paddle_tpu.serving import engine as engine_mod
        monkeypatch.setattr(engine_mod, "_DETOK_WINDOW", 4)
        calls = []
        detok = lambda ids: (calls.append(len(ids)),
                             " ".join(str(i) for i in ids))[1]
        eng = serving.Engine(tiny_llama, max_batch=1, max_seq_len=64,
                             page_size=8, detokenize=detok).warmup()
        rid = eng.add_request(_prompt(5), max_new_tokens=14)
        text = "".join(ev.text for ev in eng.stream())
        assert text == " ".join(str(i) for i in eng.output_ids(rid))
        assert max(calls) <= 4           # never the full 14-token list


class TestRaggedPrefixServing:
    """The PR-6 serving step: chunked prefill + decode in ONE compiled
    ragged dispatch, and prefix-cache block sharing with CoW — all
    still token-identical to model.generate()."""

    def _ref(self, model, p, m):
        return np.asarray(model.generate(
            jnp.asarray(p)[None], max_new_tokens=m,
            temperature=0.0))[0, len(p):]

    def test_chunked_prefill_identity(self, tiny_llama):
        """A prompt far longer than the chunk prefills across many
        ragged steps interleaved with another request's decode — both
        outputs must match generate()."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8, prefill_chunk=4).warmup()
        p_short, p_long = _prompt(3), _prompt(41)
        r1 = eng.add_request(p_short, max_new_tokens=12)
        for _ in range(2):
            eng.step()               # r1 is decoding when r2 arrives
        r2 = eng.add_request(p_long, max_new_tokens=5)
        eng.run()
        assert np.array_equal(self._ref(model, p_short, 12),
                              np.asarray(eng.output_ids(r1)))
        assert np.array_equal(self._ref(model, p_long, 5),
                              np.asarray(eng.output_ids(r2)))
        assert eng.kv_blocks_used == 0

    def test_prefill_token_budget_paces_chunks(self, tiny_llama):
        """A tight per-step budget slows prefill but never starves it
        (round-robin), and outputs stay identical."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=3, max_seq_len=64,
                             page_size=8, prefill_chunk=8,
                             prefill_token_budget=8).warmup()
        prompts = [_prompt(n) for n in (20, 17, 23)]   # all prefill at once
        rids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        outs = eng.run()
        for p, rid in zip(prompts, rids):
            assert np.array_equal(self._ref(model, p, 4),
                                  np.asarray(outs[rid]))
        assert eng.kv_blocks_used == 0

    def test_prefix_hits_reserve_fewer_blocks(self, tiny_llama):
        """Second request with the same 2-page prefix borrows those
        pages: fewer private blocks reserved, hit counters move, output
        identical."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=1, max_seq_len=64,
                             page_size=8).warmup()
        common = _prompt(16)                      # 2 full pages
        p1 = np.concatenate([common, _prompt(5)])
        p2 = np.concatenate([common, _prompt(7)])
        r1 = eng.add_request(p1, max_new_tokens=4)
        eng.run()
        peak1 = 0

        def track(*_a):
            nonlocal peak1
            peak1 = max(peak1, eng.kv_blocks_used)
        r2 = eng.add_request(p2, max_new_tokens=4, on_token=track)
        outs = eng.run()
        assert np.array_equal(self._ref(model, p2, 4),
                              np.asarray(outs[r2]))
        st = eng.prefix_stats()
        assert st["hits"] == 2 and st["hit_rate"] > 0
        # r2 held 2 borrowed + ceil((12-16+... ) private blocks: its 4
        # total pages minus the 2 shared = 2 private ⇒ peak used == 4,
        # of which only 2 were fresh allocations
        assert peak1 == 4
        assert eng.kv_blocks_used == 0            # refcounts all returned
        assert eng.kv.allocator.cached_blocks >= 2

    def test_fully_cached_prompt_triggers_cow_and_identity(self,
                                                           tiny_llama):
        """A page-aligned prompt fully covered by the cache re-prefills
        only its last token; that write lands in a SHARED page → CoW
        copy, then identical output."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8).warmup()
        p = _prompt(24)                           # exactly 3 pages
        r1 = eng.add_request(p, max_new_tokens=5)
        eng.run()
        assert eng.prefix_stats()["cow_copies"] == 0
        r2 = eng.add_request(p, max_new_tokens=5)
        outs = eng.run()
        assert np.array_equal(self._ref(model, p, 5),
                              np.asarray(outs[r2]))
        assert outs[r2] == eng.output_ids(r1)     # same prompt, same greedy
        st = eng.prefix_stats()
        assert st["hits"] == 3                    # all 3 pages hit
        assert st["cow_copies"] == 1              # last page copied
        # the serve.shared_blocks gauge derives from num_shared -
        # num_cowed: the privatized page no longer counts as shared
        rs = eng._states[r2]
        assert rs.num_shared == 3 and rs.num_cowed == 1
        assert eng.kv_blocks_used == 0

    def test_tight_pool_reserve_with_cached_hits_degrades(self,
                                                          tiny_llama):
        """Re-serving a cached prompt through a pool with NO slack must
        not crash admission: reviving refcount-0 cached hit pages
        consumes free capacity too, and the fully-cached prompt's CoW
        spare needs a block beyond blocks_for(total) — the scheduler
        degrades the hit until it fits instead of letting allocate()
        raise mid-step (which leaked the already-shared refs)."""
        model = tiny_llama
        # total budget = 5 blocks = the ENTIRE pool
        eng = serving.Engine(model, max_batch=1, max_seq_len=40,
                             page_size=8, num_blocks=5).warmup()
        p = _prompt(24)                           # exactly 3 pages
        r1 = eng.add_request(p, max_new_tokens=16)
        eng.run()
        assert eng.kv.allocator.cached_blocks == 3
        r2 = eng.add_request(p, max_new_tokens=16)   # full hit can't fit
        outs = eng.run()
        assert np.array_equal(np.asarray(outs[r2]),
                              np.asarray(eng.output_ids(r1)))
        st = eng.prefix_stats()
        assert 0 < st["hits"] < 3                 # degraded, not dropped
        assert eng.kv_blocks_used == 0
        assert eng.kv.allocator.free_blocks == 5

    def test_sharing_while_donor_still_decoding(self, tiny_llama):
        """A request may borrow pages from a donor that is STILL
        running — refcounts keep the blocks alive through both
        retirements, in either order."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8, prefill_chunk=16).warmup()
        common = _prompt(16)
        p1 = np.concatenate([common, _prompt(2)])
        p2 = np.concatenate([common, _prompt(3)])
        r1 = eng.add_request(p1, max_new_tokens=24)   # long decode
        eng.step(); eng.step()
        r2 = eng.add_request(p2, max_new_tokens=2)    # borrows, exits first
        eng.run()
        assert np.array_equal(self._ref(model, p1, 24),
                              np.asarray(eng.output_ids(r1)))
        assert np.array_equal(self._ref(model, p2, 2),
                              np.asarray(eng.output_ids(r2)))
        assert eng.prefix_stats()["hits"] == 2
        assert eng.kv_blocks_used == 0

    def test_eviction_under_pool_pressure(self, tiny_llama):
        """With a pool sized so cached pages must be evicted for new
        requests, serving still completes and reclaims everything."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=32,
                             page_size=8, num_blocks=8).warmup()
        for i in range(6):                       # distinct 2-page prompts
            rid = eng.add_request(_prompt(16), max_new_tokens=3)
            outs = eng.run()
            assert len(outs[rid]) == 3
        assert eng.kv.allocator.evictions > 0
        assert eng.kv_blocks_used == 0
        # cached + free always covers the whole pool
        assert eng.kv.allocator.free_blocks == 8

    def test_disable_prefix_caching(self, tiny_llama):
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8,
                             enable_prefix_caching=False).warmup()
        p = _prompt(16)
        r1 = eng.add_request(p, max_new_tokens=4)
        eng.run()
        r2 = eng.add_request(p, max_new_tokens=4)
        outs = eng.run()
        assert np.array_equal(self._ref(model, p, 4),
                              np.asarray(outs[r2]))
        st = eng.prefix_stats()
        assert st["hits"] == 0 and st["registered_pages"] == 0
        assert eng.kv.allocator.cached_blocks == 0
        assert eng.kv_blocks_used == 0

    def test_int8_pools_with_prefix_sharing(self, tiny_llama):
        """Sharing + CoW over quantized pools: the 4-tuple copies move
        values AND scales together."""
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                             page_size=8, kv_cache_dtype="int8").warmup()
        p = _prompt(16)
        r1 = eng.add_request(p, max_new_tokens=5)
        eng.run()
        r2 = eng.add_request(p, max_new_tokens=5)
        outs = eng.run()
        # int8 decode ≠ generate()'s fp prefill numerics, but the shared
        # path must agree with the unshared one bit-for-bit
        assert outs[r2] == eng.output_ids(r1)
        assert eng.prefix_stats()["hits"] == 2
        assert eng.prefix_stats()["cow_copies"] == 1
        assert eng.kv_blocks_used == 0


class TestPreemption:
    """preempt → swap → restore (serving.SwapManager): the front door's
    alternative to rejection.  The bar: a preempted request resumes
    TOKEN-IDENTICAL (the swap round-trips exact page bytes, int8 scales
    included), and refcounted prefix-shared pages are never swapped out
    from under the other slots reading them."""

    def _ref(self, model, p, m):
        return np.asarray(model.generate(
            jnp.asarray(p)[None], max_new_tokens=m,
            temperature=0.0))[0, len(p):]

    def test_preempt_swap_restore_token_identity(self, tiny_llama):
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8).warmup()
        p1, p2 = _prompt(6), _prompt(11)
        r1 = eng.add_request(p1, max_new_tokens=12)
        r2 = eng.add_request(p2, max_new_tokens=8)
        for _ in range(4):
            eng.step()
        used_before = eng.kv_blocks_used
        assert eng.preempt(r1)
        st = eng._states[r1]
        assert st.swapped is not None and st.slot is None
        assert eng.kv_blocks_used < used_before   # victim's blocks freed
        assert eng._swap.pages_out > 0
        eng.run()
        assert st.preempts == 1 and st.swapped is None
        assert eng._swap.pages_in > 0
        for p, m, rid in ((p1, 12, r1), (p2, 8, r2)):
            assert np.array_equal(self._ref(model, p, m),
                                  np.asarray(eng.output_ids(rid))), rid
        assert eng.kv_blocks_used == 0

    def test_preempt_mid_prefill_restores(self, tiny_llama):
        """A victim still chunk-prefilling swaps its written prefix and
        resumes prefill at kv_len — not from scratch."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8, prefill_chunk=4).warmup()
        p = _prompt(41)
        rid = eng.add_request(p, max_new_tokens=5)
        eng.step(); eng.step()                    # 8 of 41 prompt tokens
        st = eng._states[rid]
        assert st.prefilling and 0 < st.kv_len < 41
        kv_at_preempt = st.kv_len
        assert eng.preempt(rid)
        eng.run()
        assert st.kv_len > kv_at_preempt          # resumed, not reset
        assert np.array_equal(self._ref(model, p, 5),
                              np.asarray(eng.output_ids(rid)))
        assert eng.kv_blocks_used == 0

    def test_preempt_int8_pools_round_trips_scales(self, tiny_llama):
        """int8 pools: the swap must carry values AND scales — compare
        against an unpreempted int8 engine (generate() is fp, not the
        reference here)."""
        outs = []
        for do_preempt in (False, True):
            pt.seed(0)
            eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                                 page_size=8,
                                 kv_cache_dtype="int8").warmup()
            R2 = np.random.default_rng(7)
            p = R2.integers(0, 256, size=13).astype(np.int32)
            rid = eng.add_request(p, max_new_tokens=10)
            for _ in range(4):
                eng.step()
            if do_preempt:
                assert eng.preempt(rid)
            eng.run()
            outs.append(eng.output_ids(rid))
            assert eng.kv_blocks_used == 0
        assert outs[0] == outs[1]

    def test_preempt_with_shared_prefix_pages(self, tiny_llama):
        """Preempting a borrower must not disturb the donor (still
        decoding through the same physical pages) or the cache: the
        shared pages are copied, the victim's refs drop, and later
        requests still hit the cached pages."""
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8, prefill_chunk=16).warmup()
        common = _prompt(16)                      # 2 full pages
        p1 = np.concatenate([common, _prompt(3)])
        p2 = np.concatenate([common, _prompt(5)])
        r1 = eng.add_request(p1, max_new_tokens=20)   # donor, long decode
        eng.step(); eng.step()
        r2 = eng.add_request(p2, max_new_tokens=10)   # borrows the pages
        eng.step(); eng.step()
        st2 = eng._states[r2]
        assert st2.num_shared == 2                # the borrow happened
        assert eng.preempt(r2)                    # victim = the borrower
        eng.run()
        assert np.array_equal(self._ref(model, p1, 20),
                              np.asarray(eng.output_ids(r1)))
        assert np.array_equal(self._ref(model, p2, 10),
                              np.asarray(eng.output_ids(r2)))
        hits_before = eng.prefix_stats()["hits"]
        r3 = eng.add_request(np.concatenate([common, _prompt(2)]),
                             max_new_tokens=3)
        eng.run()
        assert eng.prefix_stats()["hits"] > hits_before   # cache intact
        assert eng.kv_blocks_used == 0

    def test_preempt_non_running_returns_false(self, tiny_llama):
        eng = serving.Engine(tiny_llama, max_batch=1, max_seq_len=32,
                             page_size=8).warmup()
        r1 = eng.add_request(_prompt(4), max_new_tokens=2)
        r2 = eng.add_request(_prompt(5), max_new_tokens=2)  # waits
        assert not eng.preempt("nope")            # unknown
        eng.step()
        assert not eng.preempt(r2)                # waiting, not in a slot
        eng.run()
        assert not eng.preempt(r1)                # finished
        assert eng.kv_blocks_used == 0


class TestTypedAdmissionErrors:
    """Satellite: add_request failure modes are a typed hierarchy
    (serving.errors), all ValueError subclasses so existing handlers
    keep working."""

    def test_budget_unsatisfiable(self, tiny_llama):
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=32,
                             page_size=8, num_blocks=2)
        with pytest.raises(serving.BudgetUnsatisfiable):
            eng.add_request(_prompt(20), max_new_tokens=20)
        with pytest.raises(serving.BudgetUnsatisfiable):
            eng.add_request(_prompt(30), max_new_tokens=8)
        assert issubclass(serving.BudgetUnsatisfiable, ValueError)

    def test_queue_full_typed(self, tiny_llama):
        eng = serving.Engine(tiny_llama, max_batch=1, max_seq_len=32,
                             page_size=8, max_queue=2).warmup()
        eng.add_request(_prompt(3), max_new_tokens=2)
        eng.add_request(_prompt(3), max_new_tokens=2)
        with pytest.raises(serving.QueueFull):
            eng.add_request(_prompt(3), max_new_tokens=2)
        outs = eng.run()
        assert len(outs) == 2 and eng.kv_blocks_used == 0
        eng.add_request(_prompt(3), max_new_tokens=2)   # room again

    def test_duplicate_id_is_admission_error(self, tiny_llama):
        eng = serving.Engine(tiny_llama, max_batch=1, max_seq_len=32,
                             page_size=8).warmup()
        eng.add_request(_prompt(3), max_new_tokens=2, request_id="dup")
        with pytest.raises(serving.AdmissionError):
            eng.add_request(_prompt(4), max_new_tokens=2,
                            request_id="dup")
        eng.run()


class TestSpanFanOut:
    """The engine with span fan-out: the same chunks against the same
    prefixes as an engine that cannot fan out
    (``prefill_token_budget=prefill_chunk``), in fewer steps."""

    KW = dict(max_batch=4, max_seq_len=128, page_size=8, prefill_chunk=4)

    def _ref(self, model, p, m):
        return list(np.asarray(model.generate(
            jnp.asarray(p)[None], max_new_tokens=m,
            temperature=0.0))[0, len(p):])

    def _churn(self, eng, prompts, new, **kw):
        """A decoding request, then a long prompt that meets free rows,
        then the rest; returns (outputs, steps)."""
        rids = [eng.add_request(prompts[0], max_new_tokens=new[0], **kw)]
        steps = 0
        for _ in range(3):
            eng.step()
            steps += 1
        rids += [eng.add_request(p, max_new_tokens=m, **kw)
                 for p, m in zip(prompts[1:], new[1:])]
        while eng.has_work():
            eng.step()
            steps += 1
        assert eng.kv_blocks_used == 0
        return [eng.output_ids(r) for r in rids], steps

    @pytest.mark.parametrize("kind", ["fp", "int8", "spec", "spec-int8",
                                      "lora", "temperature"])
    def test_greedy_identity_with_and_without_fan_out(self, kind):
        from paddle_tpu.models.llama import llama
        pt.seed(0)
        model = llama("tiny")
        kw, req_kw, ref_model = dict(self.KW), {}, model
        if "int8" in kind:
            kw["kv_cache_dtype"] = "int8"
        if "spec" in kind:
            kw.update(spec_decode=True, draft_depth=3)
        if kind == "lora":
            from paddle_tpu.serving import (LoRAPool, merge_adapter,
                                            random_adapter)
            ws = random_adapter(model, rank=8,
                                rng=np.random.default_rng(7), scale=0.05)
            kw["lora"] = LoRAPool(model, max_adapters=1, rank=8)
            kw["lora"].load("a", ws)
            req_kw = {"adapter": "a"}
            pt.seed(0)
            ref_model = llama("tiny")
            merge_adapter(ref_model, ws)
        if kind == "temperature":    # the stream is keyed per emit index
            req_kw = {"temperature": 0.8}
        motif = np.tile(_prompt(5), 6)        # drafts hit on this one
        prompts = [motif, _prompt(70), _prompt(9), _prompt(33)]
        new = [12, 6, 5, 4]
        fan, steps_fan = self._churn(
            serving.Engine(model, **kw).warmup(), prompts, new, **req_kw)
        one, steps_one = self._churn(
            serving.Engine(model, prefill_token_budget=4, **kw).warmup(),
            prompts, new, **req_kw)
        assert fan == one
        assert steps_fan < steps_one
        if "int8" not in kind and kind != "temperature":
            # (generate() keeps a float cache and draws another stream)
            for p, m, out in zip(prompts, new, fan):
                assert out == self._ref(ref_model, p, m)

    @pytest.mark.parametrize("plen,max_batch,chunk,steps", [
        (100, 4, 4, 7),              # ceil(100 / 16)
        (100, 1, 4, 25),             # no free row: one chunk a step
        (33, 8, 4, 2),               # 32 lanes, then the last token
        (7, 4, 8, 1),
    ])
    def test_steps_to_first_token(self, tiny_llama, plen, max_batch,
                                  chunk, steps):
        eng = serving.Engine(tiny_llama, max_batch=max_batch,
                             max_seq_len=128, page_size=8,
                             prefill_chunk=chunk).warmup()
        p = _prompt(plen)
        rid = eng.add_request(p, max_new_tokens=3)
        n = 0
        while not eng.output_ids(rid):
            eng.step()
            n += 1
        assert n == steps and eng._states[rid].prefill_steps == steps
        eng.run()
        assert eng.output_ids(rid) == self._ref(tiny_llama, p, 3)

    def test_fan_across_a_borrowed_page_copies_it_once(self, tiny_llama):
        """A fan whose rows all write into one borrowed page privatises
        it once: the request, not the row, owns the copy."""
        eng = serving.Engine(tiny_llama, max_batch=4, max_seq_len=64,
                             page_size=16, prefill_chunk=4).warmup()
        p = _prompt(32)                           # exactly 2 pages
        r1 = eng.add_request(p, max_new_tokens=5)
        eng.run()
        r2 = eng.add_request(p, max_new_tokens=5)
        eng._admit_all()
        st = eng._states[r2]
        assert st.borrowed == {1} and st.kv_len == 31
        # rewind to the borrowed page's start, as a re-run of its prefill
        # would: the fan's four rows all land in page 1
        st.kv_len = 16
        eng.step()
        assert st.kv_len == 32 and not st.borrowed
        assert eng.prefix_stats()["cow_copies"] == 1
        eng.run()
        assert eng.output_ids(r2) == eng.output_ids(r1) \
            == self._ref(tiny_llama, p, 5)
        assert eng.kv_blocks_used == 0

    @pytest.mark.parametrize("at", [0, 1, 2])
    def test_prefill_fault_rewinds_the_whole_fan(self, tiny_llama, at):
        from paddle_tpu import resilience as rs
        eng = serving.Engine(tiny_llama, **self.KW).warmup()
        p = _prompt(40)                           # 16 + 16 + 8
        inj = rs.install_faults(f"serve.prefill@{at}")
        try:
            rid = eng.add_request(p, max_new_tokens=6)
            for _ in range(at):
                eng.step()
            before = eng._states[rid].kv_len
            assert before == 16 * at
            with pytest.warns(RuntimeWarning, match="isolated"):
                eng.step()
            st = eng._states[rid]
            assert st.kv_len == before and st.preempts == 1
            eng.run()
        finally:
            rs.clear_faults()
        assert [s for s, _ in inj.fired] == ["serve.prefill"]
        assert eng.output_ids(rid) == self._ref(tiny_llama, p, 6)
        assert eng.kv_blocks_used == 0

    def test_zero_compiles_and_histograms(self, tiny_llama):
        """A mix that fans out compiles nothing after warmup(), and the
        two histograms say how often the fan engaged."""
        import paddle_tpu.observability as obs
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
        try:
            eng = serving.Engine(tiny_llama, **self.KW).warmup()
            c0 = tel.sentinel.compiles()
            prompts = [_prompt(6), _prompt(70), _prompt(9), _prompt(33),
                       _prompt(50)]
            new = [12, 6, 5, 4, 3]
            outs, _ = self._churn(eng, prompts, new)
            assert tel.sentinel.compiles() - c0 == 0
            assert eng._step_fn._cache_size() == 1
            assert eng._unpack_fn._cache_size() == 1
            snap = tel.registry.snapshot()
            rows, steps = snap["serve.prefill_rows"], \
                snap["serve.prefill_steps"]
            assert steps["count"] == len(prompts)
            assert rows["count"] == steps["sum"]
            assert rows["max"] > 1 and steps["max"] < -(-70 // 4)
            # every prompt token was dealt to exactly one row
            occ = snap["serve.ragged_occupancy"]
            n_dec = sum(new) - len(prompts)
            assert round(occ["sum"] * 16) == \
                sum(len(p) for p in prompts) + n_dec
        finally:
            obs.disable()
        for p, m, out in zip(prompts, new, outs):
            assert out == self._ref(tiny_llama, p, m)


    def test_mlp_live_tiles_histogram(self, tiny_llama, monkeypatch):
        """``serve.mlp_live_tiles`` is ``ceil(live tokens / tile)`` of
        every step, observed beside ``serve.ragged_occupancy``: at a
        tile of 4 lanes the 16-lane step reads 1 while one request
        decodes and 4 when a prompt fans out over every row."""
        import paddle_tpu.observability as obs
        from paddle_tpu.serving import engine as engine_mod
        assert engine_mod._MLP_LIVE_TILE == 128
        monkeypatch.setattr(engine_mod, "_MLP_LIVE_TILE", 4)
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
        try:
            eng = serving.Engine(tiny_llama, **self.KW).warmup()
            eng.add_request(_prompt(6), max_new_tokens=10)
            eng.step()
            eng.step()
            eng.add_request(_prompt(40), max_new_tokens=3)
            eng.run()
            snap = tel.registry.snapshot()
            tiles, occ = snap["serve.mlp_live_tiles"], \
                snap["serve.ragged_occupancy"]
            assert tiles["count"] == occ["count"]
            assert tiles["p50"] == 1 and tiles["max"] == 4
            # ceil(n / 4) summed over the steps is at least the live
            # tokens over 4, and under one more a step
            assert occ["sum"] * 4 <= tiles["sum"] < occ["sum"] * 4 \
                + tiles["count"]
        finally:
            obs.disable()


class TestPackedStepInputs:
    """The step's per-row inputs cross to the device as ONE int32 buffer
    (``Engine._step_args``), unpacked by ``_unpack_fn``: the same step
    program as separate arrays give, the same tokens, one host-to-device
    transfer a planned step."""

    @staticmethod
    def _engine(kind):
        """(warmed engine, request kwargs, prompt ids' modulus)."""
        from paddle_tpu.models.llama import llama
        pt.seed(0)
        if kind == "eva":
            from paddle_tpu.models import evabyte as E
            cfg = E.EvaByteConfig(hidden_size=64, intermediate_size=128,
                                  num_hidden_layers=2, num_attention_heads=4,
                                  max_position_embeddings=512, window_size=64)
            eng = serving.Engine(E.EvaByteForCausalLM(cfg), max_batch=3,
                                 max_seq_len=512, num_blocks=64,
                                 prefill_chunk=16,
                                 enable_prefix_caching=False)
            return eng.warmup(), {}, 320
        model = llama("tiny")
        kw, req = dict(max_batch=4, max_seq_len=128, page_size=8,
                       prefill_chunk=4), {}
        if kind == "spec":
            kw.update(spec_decode=True, draft_depth=3)
        if kind == "lora":
            from paddle_tpu.serving import LoRAPool, random_adapter
            kw["lora"] = LoRAPool(model, max_adapters=2, rank=8)
            kw["lora"].load("a", random_adapter(
                model, rank=8, rng=np.random.default_rng(7), scale=0.05))
            req = {"adapter": "a"}
        return serving.Engine(model, **kw).warmup(), req, 256

    @staticmethod
    def _churn(eng, req, mod):
        """A decoding request (a motif, so drafts hit), then a long prompt
        that fans out over the free rows, then a temperature request."""
        eng.add_request(np.tile(_prompt(5), 6) % mod, max_new_tokens=12)
        for _ in range(3):
            eng.step()
        # (150 closes an EVA window of 64)
        eng.add_request(_prompt(min(150, eng.max_seq_len - 8)) % mod,
                        max_new_tokens=4, **req)
        eng.step()
        eng.step()
        eng.add_request(_prompt(9) % mod, max_new_tokens=5,
                        temperature=0.8)
        eng.run()
        assert eng.kv_blocks_used == 0

    @pytest.mark.parametrize("kind", ["fp", "lora", "spec", "eva"])
    def test_packed_tokens_equal_separate_arrays(self, kind):
        """Every step's unpacked inputs are its host arrays, value, shape
        and dtype; the step on them gives the tokens and pools that the
        step on separate ``jnp.asarray`` arrays gives (the pools copied:
        the step donates them); and both hit the one compiled step, with
        nothing compiled after warmup."""
        import jax
        import paddle_tpu.observability as obs
        eng, req, mod = self._engine(kind)
        real_step, real_inputs = eng._step_fn, eng._host_inputs
        seen = []

        def host_inputs(plan):
            seen.append(real_inputs(plan))
            return seen[-1]

        def step(*args):
            host = seen[-1]
            aux = args[12]
            got = [*args[2:7], *args[8:10], args[11]] + \
                [aux[k] for k in eng._aux_keys]
            assert len(got) == len(host) == len(eng._layout)
            for g, h in zip(got, host):
                assert g.shape == h.shape and g.dtype == h.dtype
                np.testing.assert_array_equal(np.asarray(g), h)
            dev = [jnp.asarray(h) for h in host]
            separate = (args[0], jax.tree.map(jnp.copy, args[1]), *dev[:5],
                        args[7], *dev[5:7], args[10], dev[7],
                        dict(zip(eng._aux_keys, dev[8:])) if aux else None)
            want, want_caches = real_step(*separate)
            nxt, caches = real_step(*args)
            np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want))
            for a, b in zip(jax.tree.leaves(caches),
                            jax.tree.leaves(want_caches)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            return nxt, caches

        eng._host_inputs, eng._step_fn = host_inputs, step
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
        try:
            jax.tree.map(jnp.copy, eng.kv.caches)     # the test's own copy
            c0 = tel.sentinel.compiles()
            self._churn(eng, req, mod)
            assert tel.sentinel.compiles() - c0 == 0
        finally:
            obs.disable()
        assert real_step._cache_size() == 1
        assert eng._unpack_fn._cache_size() == 1
        lens = [h[3] for h in seen]
        # a decode row beside a prompt's fan-out rows, and sampling at a
        # temperature (float32 temps travel as their bits)
        assert any((ln == 1).any() and (ln > 1).sum() > 1 for ln in lens)
        assert any((h[4] > 0).any() for h in seen)
        if kind == "lora":
            assert any((h[7] > 0).any() for h in seen)
        if kind == "spec":
            assert eng.spec_stats()["proposed"] > 0
        if kind == "eva":
            assert eng._aux_keys and len(seen[0]) == 8 + len(eng._aux_keys)

    @pytest.mark.parametrize("kind", ["fp", "eva"])
    def test_one_transfer_a_planned_step(self, kind, monkeypatch):
        """A planned ``step_begin`` moves exactly one host buffer to the
        device, the packed one: any other host-to-device transfer raises
        under the guard."""
        import jax
        eng, req, mod = self._engine(kind)
        real_put, puts = jax.device_put, []

        def put(x, *a, **kw):
            puts.append(x.nbytes)
            with jax.transfer_guard_host_to_device("allow"):
                return real_put(x, *a, **kw)

        eng.add_request(_prompt(40) % mod, max_new_tokens=5, **req)
        eng.add_request(_prompt(7) % mod, max_new_tokens=3, **req)
        monkeypatch.setattr(jax, "device_put", put)
        planned = 0
        while eng.has_work():
            n = len(puts)
            with jax.transfer_guard_host_to_device("disallow_explicit"):
                pending = eng.step_begin()
            assert len(puts) - n == (1 if pending[0] else 0)
            planned += bool(pending[0])
            eng.step_finish(pending)
        assert planned > 1 and len(puts) == planned
        assert set(puts) == {eng._packed.nbytes}

    @pytest.mark.parametrize("kind", ["fp", "eva"])
    def test_the_benchmarks_lowering_of_the_step_is_unchanged(self, kind):
        """``_step_fn`` lowered with thirteen separate arguments, as the
        benchmark's serving runner lowers it after ``warmup``, gives the
        text that the packed path's arguments give, lowered and
        compiled."""
        eng, _, _ = self._engine(kind)
        b, mb, c = eng.max_batch, eng.max_blocks_per_seq, eng.prefill_chunk
        zeros = jnp.zeros((b,), jnp.int32)
        separate = eng._step_fn.lower(
            eng.params, eng.kv.caches, jnp.zeros((b, c), jnp.int32),
            jnp.full((b, mb), eng.kv.oob_block, jnp.int32), zeros, zeros,
            jnp.zeros((b,), jnp.float32), eng._key, zeros, zeros,
            eng._lora_stacks(), zeros, eng._device_aux([]))
        packed = eng._step_fn.lower(*eng._step_args(eng._host_inputs([])))
        assert separate.as_text() == packed.as_text()
        assert separate.compile().as_text() == packed.compile().as_text()


class TestFaultIsolation:
    """Injected serve.* faults are confined to the ONE affected request
    (rewind → preempt → re-admit): the compiled step and the other
    slots survive, outputs stay token-identical (the chaos-serving CI
    gate runs the full multi-site version of this)."""

    def _ref(self, model, p, m):
        return np.asarray(model.generate(
            jnp.asarray(p)[None], max_new_tokens=m,
            temperature=0.0))[0, len(p):]

    def test_step_and_prefill_faults_confined(self, tiny_llama):
        from paddle_tpu import resilience as rs
        model = tiny_llama
        eng = serving.Engine(model, max_batch=2, max_seq_len=64,
                             page_size=8, prefill_chunk=4).warmup()
        prompts = [_prompt(9), _prompt(14)]
        inj = rs.install_faults("serve.step@2,serve.prefill@1,"
                                "serve.admit@1")
        try:
            rids = [eng.add_request(p, max_new_tokens=6)
                    for p in prompts]
            with pytest.warns(RuntimeWarning, match="isolated"):
                eng.run()
        finally:
            rs.clear_faults()
        fired = {s for s, _ in inj.fired}
        assert {"serve.step", "serve.prefill", "serve.admit"} <= fired
        for p, rid in zip(prompts, rids):
            assert np.array_equal(self._ref(model, p, 6),
                                  np.asarray(eng.output_ids(rid))), rid
        assert eng.kv_blocks_used == 0
        # the victims went through the preempt/restore machinery
        assert any(eng._states[r].preempts > 0 for r in rids)

    def test_isolation_emits_events(self, tiny_llama):
        import paddle_tpu.observability as obs
        from paddle_tpu import resilience as rs
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
        inj = rs.install_faults("serve.step@1")
        try:
            eng = serving.Engine(tiny_llama, max_batch=1, max_seq_len=32,
                                 page_size=8).warmup()
            rid = eng.add_request(_prompt(4), max_new_tokens=4)
            with pytest.warns(RuntimeWarning, match="isolated"):
                eng.run()
            assert len(eng.output_ids(rid)) == 4
            sink = tel.sinks[0]
            iso = sink.events("serve_isolated_failure")
            assert iso and iso[0]["exc"] == "InjectedFault"
            assert sink.events("serve_preempt") \
                and sink.events("serve_restore")
            snap = tel.registry.snapshot()
            assert snap["serve.isolated_failures"] == 1
            assert snap["serve.preemptions"] == 1
            assert snap["serve.restores"] == 1
        finally:
            rs.clear_faults()
            obs.disable()


class TestServingTelemetry:
    def test_metrics_and_events(self, tiny_llama):
        import paddle_tpu.observability as obs
        tel = obs.enable(sinks=[obs.InMemorySink()], crash_hooks=False)
        try:
            eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                                 page_size=8).warmup()
            eng.add_request(_prompt(5), max_new_tokens=4)
            eng.run()
            snap = tel.registry.snapshot()
            assert snap["serve.requests"] == 1
            assert snap["serve.finished"] == 1
            assert snap["serve.kv_blocks_used"] == 0
            assert snap["serve.tokens"] == 4
            assert snap["serve.ttft_ms"]["count"] == 1
            sink = tel.sinks[0]
            assert len(sink.events("serve_request")) == 1
            fin = sink.events("serve_finish")
            assert fin and fin[0]["reason"] == "length"
            assert sink.events("serve_step")
        finally:
            obs.disable()

    def test_disabled_telemetry_is_silent(self, tiny_llama):
        """With observability off (default), serving never touches the
        registry — same zero-overhead contract as the train step."""
        import paddle_tpu.observability as obs
        assert not obs.enabled()

        def boom(self, *a, **kw):
            raise AssertionError("serving touched the registry while "
                                 "telemetry is disabled")
        saved = {}
        for name in ("counter", "gauge", "histogram"):
            saved[name] = getattr(obs.MetricsRegistry, name)
            setattr(obs.MetricsRegistry, name, boom)
        try:
            eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                                 page_size=8).warmup()
            eng.add_request(_prompt(4), max_new_tokens=3)
            # the preempt/swap/restore path rides the same contract
            rid = eng.add_request(_prompt(6), max_new_tokens=6)
            eng.step(); eng.step()
            eng.preempt(rid)
            eng.run()
        finally:
            for name, fn in saved.items():
                setattr(obs.MetricsRegistry, name, fn)


class TestBenchServePlumbing:
    def test_bench_serve_runs_on_cpu(self):
        """The aggregate serving metric bench.py reports
        (tools/decode_bench.bench_serve) runs end-to-end on CPU — the
        acceptance bar here is plumbing only; throughput numbers come
        from TPU BENCH rounds."""
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from decode_bench import bench_serve
        r = bench_serve(preset="tiny", max_batch=2, n_requests=3,
                        max_new=4, prompt_lens=(4, 9, 6), page_size=8,
                        repeats=1)
        assert r["metric"] == "serve_continuous_batching_tok_s"
        assert r["gen_tokens"] == 3 * 4
        assert r["agg_tokens_per_sec"] > 0

    def test_bench_serve_prefix_runs_on_cpu(self):
        """Shared-prefix / bursty-admission workload: TTFT-under-load
        p95 recorded, and the warm pass actually hits the prefix cache
        (hit-rate metric > 0 — the acceptance bar for the workload)."""
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from decode_bench import bench_serve_prefix
        r = bench_serve_prefix(preset="tiny", max_batch=2, n_requests=4,
                               shared_prefix=16, tail_lens=(4, 9),
                               max_new=6, page_size=8, prefill_chunk=8)
        assert r["metric"] == "serve_shared_prefix_ttft"
        assert r["cold_ttft_p95_ms"] > 0 and r["warm_ttft_p95_ms"] > 0
        assert r["warm_agg_tokens_per_sec"] > 0
        assert r["warm_prefix_hits"] > 0 and r["prefix_hit_rate"] > 0

    def test_bench_serve_burst_runs_on_cpu(self):
        """Overload workload (offered > capacity through the bounded
        front door): goodput, shed rate and admitted-TTFT all recorded;
        every shed carried a retry-after answer (asserted inside)."""
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from decode_bench import bench_serve_burst
        r = bench_serve_burst(preset="tiny", max_batch=2, offered=8,
                              max_queue_depth=3, prompt_lens=(5, 11, 8),
                              max_new=6, page_size=8)
        assert r["metric"] == "serve_burst_goodput"
        assert r["admitted"] + r["shed"] == 8 and r["shed"] > 0
        assert 0 < r["shed_rate"] < 1
        assert r["goodput_tok_s"] > 0
        assert r["admitted_ttft_p95_ms"] > 0


class TestPredictorWarmup:
    def test_aot_compile_and_shape_key(self):
        from paddle_tpu import nn
        from paddle_tpu.inference import Config, create_predictor
        pt.seed(0)
        net = nn.Linear(4, 3)
        x = jnp.ones((2, 4))
        p = create_predictor(Config(model=net, example_args=(x,)))
        assert p._compiled is None
        p.warmup()
        assert p._compiled is not None
        key = p._compiled_key
        out = p.run(x)
        assert p._compiled_key == key      # same geometry: no re-lower
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(net(x)), rtol=1e-6)
        p.run(jnp.ones((5, 4)))            # new geometry: re-lowers
        assert p._compiled_key != key

    def test_alternating_geometries_compile_once_each(self):
        """run() keeps one executable PER input geometry (like the jit
        cache it replaces) — alternating shapes must not re-lower."""
        from paddle_tpu import nn
        from paddle_tpu.inference import Config, create_predictor
        pt.seed(0)
        p = create_predictor(Config(model=nn.Linear(4, 3)))
        a, b = jnp.ones((2, 4)), jnp.ones((5, 4))
        p.run(a), p.run(b)
        assert len(p._executables) == 2
        exe_a = p._executables[p._arg_key((a,))]
        p.run(a), p.run(b), p.run(a)
        assert len(p._executables) == 2            # no re-lower
        assert p._executables[p._arg_key((a,))] is exe_a

    def test_first_run_compiles_lazily(self):
        from paddle_tpu import nn
        from paddle_tpu.inference import Config, create_predictor
        pt.seed(0)
        p = create_predictor(Config(model=nn.Linear(4, 3)))
        with pytest.raises(ValueError, match="example"):
            p.warmup()
        out = p.run(jnp.ones((2, 4)))
        assert p._compiled is not None and np.asarray(out[0]).shape == (2, 3)

    def test_arg_key_distinguishes_pytree_structure(self):
        """run(x, y) and run((x, y)) flatten to the same leaves; the AOT
        dispatch key must include the treedef or the wrong executable is
        handed arguments of the wrong structure."""
        import jax
        from paddle_tpu.inference import Config, create_predictor
        p = create_predictor(
            Config(model=lambda *a: sum(jax.tree.leaves(list(a)))))
        a, b = jnp.ones((2, 4)), jnp.full((2, 4), 2.0)
        out1 = p.run(a, b)
        out2 = p.run((a, b))           # same leaves, different structure
        assert len(p._executables) == 2
        np.testing.assert_allclose(np.asarray(out1[0]),
                                   np.asarray(out2[0]))


# ---------------------------------------------------------------------------
# the KV the ragged attention kernel walks, counted on the host
# ---------------------------------------------------------------------------

class TestAttentionWalk:
    """``ragged_attention.positions_walked`` and the two registry
    histograms the engine observes from it (``serve.attn_positions_walked``
    / ``serve.attn_positions_held``) while the registry is on."""

    def test_positions_walked_by_hand(self):
        from paddle_tpu.ops.pallas.ragged_attention import positions_walked
        # a dead row, an exact multiple of the block (128 positions), one
        # past it, and three fan-out rows of one prompt: each walks the
        # prefix from position 0
        starts = [0, 0, 128, 0, 16, 48]
        lens = [0, 128, 1, 16, 16, 16]
        got = positions_walked(np.array(starts), np.array(lens), page=16,
                               ppb=8)
        assert got.tolist() == [0, 128, 256, 128, 128, 128]
        assert [positions_walked(s, n, page=16, ppb=8)
                for s, n in zip(starts, lens)] == got.tolist()
        assert positions_walked(64, 16, page=16, ppb=2) == 96

    def test_a_traced_kernel_notes_its_block(self):
        """``traced_blocks`` collects the block of each kernel call traced
        inside it (interpret mode), and nothing outside it."""
        from paddle_tpu.ops.pallas import ragged_attention as RA
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(2, 4, 2, 128)).astype(np.float32))
        kp = jnp.asarray(rng.normal(size=(8, 16, 2, 128)).astype(np.float32))
        tables = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4))
        starts = jnp.asarray(np.array([20, 0], np.int32))
        lens = jnp.asarray(np.array([4, 0], np.int32))
        with RA.traced_blocks() as seen:
            out = RA.ragged_paged_attention(q, kp, kp, tables, starts, lens,
                                            interpret=True)
        assert np.isfinite(np.asarray(out)[0]).all()
        assert seen == [RA._pages_per_block(16, 2, 128, jnp.float32, 4)]
        RA.ragged_paged_attention(q, kp, kp, tables, starts, lens,
                                  interpret=True)
        assert len(seen) == 1

    @staticmethod
    def _count_by_hand(plans, spec, blk):
        walked, held = [], []
        for plan in plans:
            per = {}
            for sp in plan:
                at = sp.start if spec.kind == "kv" else \
                    spec.cache_start(sp.start)
                per.setdefault(id(sp.st), []).append(
                    -(-(at + sp.n) // blk) * blk)
            walked.append(sum(sum(v) for v in per.values()))
            held.append(sum(max(v) for v in per.values()))
        return walked, held

    def _serve_counted(self, eng, prompts, new, ppb):
        """Serve under the registry, the count forced on at ``ppb`` pages
        a block unless None; (histograms' sums and counts, the plans
        taken)."""
        import paddle_tpu.observability as obs
        if ppb is not None:
            eng._attn_ppb = ppb
        plans, plan_spans = [], eng.scheduler.plan_spans

        def keep(*a, **kw):
            plan = plan_spans(*a, **kw)
            if plan:
                plans.append(list(plan))
            return plan

        eng.scheduler.plan_spans = keep
        reg = obs.enable(crash_hooks=False).registry
        try:
            for p, m in zip(prompts, new):
                eng.add_request(p, max_new_tokens=m)
            eng.run()
            got = {n: (reg.get(n).sum, reg.get(n).count) for n in
                   ("serve.attn_positions_walked",
                    "serve.attn_positions_held")}
        finally:
            obs.disable()
        return got, plans

    def _check(self, eng, prompts, new, ppb=2):
        got, plans = self._serve_counted(eng, prompts, new, ppb)
        walked, held = self._count_by_hand(
            plans, eng.cache_spec, eng._attn_ppb * eng.page_size)
        assert got["serve.attn_positions_walked"] == (sum(walked),
                                                      len(plans))
        assert got["serve.attn_positions_held"] == (sum(held), len(plans))
        # a prompt's fan-out rows read its prefix again
        assert sum(walked) > sum(held) > 0

    def test_histograms_of_a_full_attention_engine(self, tiny_llama):
        eng = serving.Engine(tiny_llama, max_batch=4, max_seq_len=128,
                             page_size=8, prefill_chunk=4).warmup()
        self._check(eng, [_prompt(40), _prompt(9)], [6, 4])

    def test_histograms_of_an_eva_engine(self):
        from paddle_tpu.models import evabyte as E
        cfg = E.EvaByteConfig(hidden_size=64, intermediate_size=128,
                              num_hidden_layers=2, num_attention_heads=4,
                              max_position_embeddings=512, window_size=64)
        pt.seed(0)
        model = E.EvaByteForCausalLM(cfg)
        eng = serving.Engine(model, max_batch=3, max_seq_len=512,
                             num_blocks=64, prefill_chunk=16,
                             enable_prefix_caching=False).warmup()
        # the long prompt closes a window: its later rows walk summary
        # pages before their window's
        self._check(eng, [_prompt(150) % 320, _prompt(20) % 320], [8, 4])

    def test_the_engine_takes_the_block_its_step_traced(self, tiny_llama,
                                                        monkeypatch):
        """Where the dispatch hands the step the kernel (here the
        interpreter's, registered for the CPU), the block is the one the
        step's trace built, and the histograms count with it."""
        from paddle_tpu.ops import dispatch
        from paddle_tpu.ops.pallas import ragged_attention as RA

        def kernel(q, k, v, tables, starts, lens, scale=None):
            return RA.ragged_paged_attention(q, k, v, tables, starts, lens,
                                             scale=scale, interpret=True)

        monkeypatch.setitem(dispatch._REGISTRY, "ragged_paged_attention",
                            kernel)
        monkeypatch.setitem(dispatch._PLATFORM, "ragged_paged_attention",
                            "cpu")
        eng = serving.Engine(tiny_llama, max_batch=4, max_seq_len=128,
                             page_size=8, prefill_chunk=4).warmup()
        pool = eng.kv.caches[0][0]
        ppb = RA._pages_per_block(8, pool.shape[2], pool.shape[3],
                                  pool.dtype, eng.max_blocks_per_seq)
        assert eng._attn_ppb == ppb
        self._check(eng, [_prompt(40), _prompt(9)], [3, 2], ppb=None)

    def test_no_count_off_the_kernel(self, tiny_llama):
        """On the CPU the step attends through the XLA composition: no
        block size, and no histogram under the registry."""
        import paddle_tpu.observability as obs
        eng = serving.Engine(tiny_llama, max_batch=2, max_seq_len=64,
                             page_size=8).warmup()
        assert eng._attn_ppb is None
        reg = obs.enable(crash_hooks=False).registry
        try:
            eng.add_request(_prompt(12), max_new_tokens=3)
            eng.run()
            assert reg.get("serve.attn_positions_walked") is None
            assert reg.get("serve.ragged_occupancy").count > 0
        finally:
            obs.disable()
