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
