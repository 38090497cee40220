"""The reduction from a trace to numbers, pinned on a hand-built trace."""

import pytest

from benchmark import trace_reduce as tr


def test_busy_idle_union(events):
    bw = tr.busy_and_window(events)
    # window: 1.000 .. 1.018; idle: 1.007-1.0075, 1.008-1.010, 1.017-1.0175
    assert bw["window_s"] == pytest.approx(0.018)
    assert bw["busy_s"] == pytest.approx(0.018 - 0.0005 - 0.002 - 0.0005)
    assert bw["devices"] == 1


def test_union_counts_nested_once():
    evs = [["a", 0.0, 1.0], ["b", 0.2, 0.3], ["c", 0.9, 0.4], ["d", 2.0, 0.5]]
    assert tr.union_seconds(evs) == pytest.approx(1.3 + 0.5)


def test_kernel_time_by_pattern(events):
    ops = events["devices"][0]["ops"]
    adam = tr.leaf_ops(tr.matching(ops, [r"fused_adamw"]))
    assert len(adam) == 4
    assert sum(e[2] for e in adam) == pytest.approx(0.008)
    # a scope path names the work where the instruction's name does not
    scoped = tr.matching(ops, [r"mlp"], {"fusion.2": "jit(step)/mlp/dot"})
    assert [e[0] for e in scoped] == ["fusion.2", "fusion.2"]


def test_containers_are_not_leaves(events):
    names = {e[0] for e in tr.leaf_ops(events["devices"][0]["ops"])}
    assert "while.3" not in names and "fused_adamw.7" in names
    top = dict(tr.top_ops(events))
    assert top["fused_adamw.7"] == pytest.approx(0.004)
    assert "while.3" not in top


def test_gap_attribution(events):
    ops = events["devices"][0]["ops"]
    gaps = tr.idle_gaps(ops)
    assert [(round(a, 4), round(b, 4)) for a, b in gaps] == [
        (1.007, 1.0075), (1.008, 1.01), (1.017, 1.0175)]
    owners = dict(tr.attribute_gaps(gaps, events["host"]))
    # next_batch spans 1.0079-1.0094; the second gap opens at 1.008
    assert owners["bench.next_batch"] == pytest.approx(0.0014)
    assert owners["bench.fetch_loss"] == pytest.approx(0.0002)
    assert owners["unattributed"] == pytest.approx(0.003 - 0.0016)


LOOP, HANDLER = "/host:CPU/3:python", "/host:CPU/5:python"


def serve_gaps(trace):
    """The device's idle gaps, a binary fraction's rounding apart."""
    return [(a, b) for a, b in tr.idle_gaps(trace["devices"][0]["ops"])
            if b - a > 1e-9]


def test_self_segments_give_a_parent_what_its_children_leave():
    spans = [["parent", 0.0, 10.0], ["a", 2.0, 2.0], ["b", 6.0, 2.0],
             ["b.inner", 6.5, 1.0], ["late", 12.0, 1.0]]
    assert tr.self_segments(spans) == [
        (0.0, 2.0, "parent"), (2.0, 4.0, "a"), (4.0, 6.0, "parent"),
        (6.0, 6.5, "b"), (6.5, 7.5, "b.inner"), (7.5, 8.0, "b"),
        (8.0, 10.0, "parent"), (12.0, 13.0, "late")]
    # a child that outlasts its parent by a tick keeps what it covers
    assert tr.self_segments([["p", 0.0, 1.0], ["c", 0.5, 0.7]]) == [
        (0.0, 0.5, "p"), (0.5, 1.2, "c")]


def test_gaps_go_to_the_loop_threads_leaves_by_self_time(serve_events):
    gaps = serve_gaps(serve_events)
    assert [(round(a, 4), round(b, 4)) for a, b in gaps] == [
        (2.01, 2.015), (2.025, 2.03)]
    thread, rows = tr.gap_owners(gaps, serve_events["host"])
    assert thread == LOOP
    # the first gap straddles sync's tail, emit ... dispatch: milliseconds
    want = {"pdtpu.serve.step.sync": 0.1, "pdtpu.serve.step.emit": 0.5,
            "pdtpu.serve.step.account": 0.2, "pdtpu.serve.step.finish": 0.1,
            "pdtpu.serve.stream.route": 0.3, "pdtpu.serve.loop.wait": 0.1,
            "pdtpu.serve.pump": 0.2, "pdtpu.serve.step": 0.3,
            "pdtpu.serve.step.admit": 0.2, "pdtpu.serve.step.plan": 0.8,
            "pdtpu.serve.step.dispatch": 1.9, "unattributed": 0.3}
    for (a, b), row in rows:
        assert {k: round(1e3 * v, 6) for k, v in row.items()} == want
        # owners + unattributed = the gap's seconds
        assert sum(row.values()) == pytest.approx(b - a, abs=1e-12)
    owners = dict(tr.attribute_gaps(gaps, serve_events["host"], n=20))
    assert sum(owners.values()) == pytest.approx(0.010, abs=1e-12)
    assert owners["pdtpu.serve.step.dispatch"] == pytest.approx(0.0038)
    # the parents own what their leaves leave, next to nothing
    assert owners["pdtpu.serve.step"] == pytest.approx(0.0006)
    assert owners["pdtpu.serve.step.finish"] == pytest.approx(0.0002)


def test_one_thread_owns_the_gaps(serve_events):
    gaps = serve_gaps(serve_events)
    owners = dict(tr.attribute_gaps(gaps, serve_events["host"], n=20))
    # the handler's write lies over each gap for 2 ms: credited, it would
    # count those seconds twice
    assert "pdtpu.serve.stream.write" not in owners
    handler = [e for e in serve_events["host"] if e[3] == HANDLER]
    assert tr.gap_owners(gaps, handler)[0] == HANDLER
    alone = dict(tr.attribute_gaps(gaps, handler))
    assert alone == {"pdtpu.serve.stream.write": pytest.approx(0.004),
                     "unattributed": pytest.approx(0.006)}
    # no span at all: everything is unattributed
    assert tr.attribute_gaps(gaps, []) == [["unattributed",
                                            pytest.approx(0.010)]]


def test_the_smallest_owners_fold_into_other(serve_events):
    gaps = serve_gaps(serve_events)
    rows = tr.attribute_gaps(gaps, serve_events["host"], n=4)
    assert [r[0] for r in rows] == [
        "pdtpu.serve.step.dispatch", "pdtpu.serve.step.plan",
        "pdtpu.serve.step.emit", "other"]
    assert sum(r[1] for r in rows) == pytest.approx(0.010, abs=1e-12)
    assert len(tr.breakdown(serve_events)["idle_gaps"]) == 10


def test_the_longest_gaps_name_their_owners(serve_events):
    ops = serve_events["devices"][0]["ops"]
    # a pause of 40 ms in ``plan`` after the last step
    ops = ops + [["fusion.1", 2.082, 0.003]]
    host = serve_events["host"] + [
        ["pdtpu.serve.step.plan", 2.043, 0.038, LOOP]]
    got = tr.longest_idle_gaps({"devices": [{"ops": ops}], "host": host}, 2)
    assert [g["ms"] for g in got] == [40.0, 5.0]
    assert got[0]["start_s"] == 2.042
    assert got[0]["owners"] == {"pdtpu.serve.step.plan": 38.0,
                                "unattributed": 2.0}
    assert list(got[1]["owners"])[0] == "pdtpu.serve.step.dispatch"
    assert [g["ms"] for g in tr.longest_idle_gaps(serve_events)[:2]] == [
        5.0, 5.0]


def test_step_periods(events):
    per = tr.step_periods(events, r"^jit__step")
    assert per["periods"] == 1 and per["seconds"] == pytest.approx(0.010)
    assert tr.step_periods(events, r"^jit_other") is None


def test_short_name():
    long = "%fusion.3 = bf16[8,128]{1,0} fusion(bf16[8]{0} %p), kind=kLoop"
    assert tr.short_name(long) == "fusion.3"
    assert tr.short_name("flash_attention_bwd.3") == "flash_attention_bwd.3"


def test_readers_on_the_hand_built_trace(events):
    from benchmark import run as brun

    cell = brun.load_json("workloads", "mistral-7b.train-8k.json")
    config = brun.load_json("configs", "mistral-7b.json")
    ctx = {"trace": events, "scopes": {}, "cell": cell, "config": config,
           "layers": 2, "notes": [],
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "counters": {"compiles_in_window": 0, "n_params": 1000}}
    idle = brun.load_metric("device_idle_pct.train").read(ctx)
    assert idle == pytest.approx(100 * 0.003 / 0.018)
    adam = brun.load_metric("adamw_roofline").read(ctx)
    # 2 steps x 1000 params x 28 B at 819 GB/s over 8 ms of kernel time
    assert adam == pytest.approx(100 * (2 * 28000 / 819e9) / 0.008)
    mfu = brun.load_metric("mfu.train").read(ctx)
    flops = 6 * 567279616 + 6 * 2 * 4096 * 4096
    assert mfu == pytest.approx(100 * flops * 8192 / 0.010 / 197e12)
    # a reader that finds nothing to read returns nothing, never 0
    events["devices"][0]["ops"] = [e for e in events["devices"][0]["ops"]
                                   if "adamw" not in e[0]]
    assert brun.load_metric("adamw_roofline").read(ctx) is None
