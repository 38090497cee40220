"""The three readers of regions on a hand-built trace and scope map: a
region's events inside and outside the traced steps, a container
operation, an operation no region owns."""

import pytest

from benchmark import run as brun
from benchmark.work import regions

STEP = "jit(_step)/jit(main)/"
SCOPES = {
    "fusion.1": STEP + "jvp(forward)/mlp/dot_general",
    "fused_swiglu_mlp.2": STEP + "jvp(forward)/mlp/jit(fused_swiglu_mlp)/"
                                 "pallas_call",
    "fusion.3": STEP + "transpose(jvp(forward))/mlp/dot_general",
    "fusion.4": STEP + "transpose(jvp(forward))/lm_head_loss/dot_general",
    "fusion.5": STEP + "optimizer/clip/global_norm/reduce_sum",
    "fusion.6": STEP + "jvp(forward)/mlp/norm/mul",
    "fusion.7": STEP + "add",
    "while.8": STEP + "optimizer/while",
    "fusion.9": STEP + "optimizer/while/body/mul",
    "fusion.10": STEP + "jvp(forward)/normalize/mul",
}


def trace():
    """Two runs of the step program, 1.000-1.010 and 1.010-1.020 s; one
    ``mlp`` event before the first run and one after the last."""
    step = [["fusion.1", 0.0000, 0.0020], ["fused_swiglu_mlp.2", 0.0020, 0.0010],
            ["fusion.3", 0.0030, 0.0030], ["fusion.4", 0.0060, 0.0010],
            ["fusion.5", 0.0070, 0.0005], ["fusion.6", 0.0075, 0.0005],
            ["fusion.7", 0.0080, 0.0004], ["while.8", 0.0085, 0.0010],
            ["fusion.9", 0.0086, 0.0008], ["fusion.10", 0.0096, 0.0002],
            ["copy.11", 0.0098, 0.0002]]
    ops = [["fusion.1", 0.9900, 0.0020]]
    for t0 in (1.000, 1.010):
        ops += [[n, t0 + a, d] for n, a, d in step]
    ops.append(["fusion.3", 1.0250, 0.0030])
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": [["jit__step(1)", 1.000, 0.010],
                                     ["jit__step(1)", 1.010, 0.010]]}],
            "host": []}


def context(scopes=SCOPES):
    cell = brun.load_json("workloads", "mistral-7b.train-8k.json")
    config = brun.load_json("configs", "mistral-7b.json")
    return {"trace": trace(), "scopes": scopes, "cell": cell,
            "config": config, "layers": 2, "notes": [],
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": {}}


def test_innermost_region_of_a_path():
    assert regions.region_of(SCOPES["fusion.3"]) == "mlp"
    assert regions.region_of(SCOPES["fused_swiglu_mlp.2"]) == "mlp"
    # clip/global_norm is clip, not norm; a norm under mlp is norm
    assert regions.region_of(SCOPES["fusion.5"]) == "clip"
    assert regions.region_of(SCOPES["fusion.6"]) == "norm"
    # a whole component, not a part of one
    assert regions.region_of(SCOPES["fusion.10"]) is None
    assert regions.region_of(STEP + "transpose(jvp(mlp))/dot") == "mlp"
    # a call of jnp.clip is no gradient clip
    assert regions.region_of(STEP + "jvp(forward)/jit(clip)/max") is None
    assert regions.region_of(None) is None and regions.region_of("") is None


def test_table_counts_leaves_inside_the_steps_only():
    ctx = context()
    table = regions.region_table(ctx)
    rows = table["regions"]
    assert table["steps"] == 2
    # the events at 0.990 and 1.025 lie outside the runs
    assert rows["mlp"] == [pytest.approx(2 * 0.006), 6]
    assert rows["lm_head_loss"] == [pytest.approx(2 * 0.001), 2]
    assert rows["clip"][0] == pytest.approx(2 * 0.0005)
    assert rows["norm"][0] == pytest.approx(2 * 0.0005)
    # the while is a container: its body's leaf is counted, it is not
    assert rows["optimizer"] == [pytest.approx(2 * 0.0008), 2]
    # no region: fusion.7, fusion.10 and the copy that no text names
    assert rows["unscoped"] == [pytest.approx(2 * 0.0008), 6]
    assert table["seconds"] == pytest.approx(sum(r[0] for r in rows.values()))
    assert table["seconds"] == pytest.approx(2 * 0.0096)
    # the whole table is logged, once
    regions.region_table(ctx)
    assert sum("by region" in n for n in ctx["notes"]) == 1
    assert len(ctx["notes"]) == 2
    assert "lm_head_loss 0.002000 s / 2 events" in ctx["notes"][0]
    # and each region's seconds by kind of instruction
    assert "mlp: fusion 0.010000, fused_swiglu_mlp 0.002000" in ctx["notes"][1]
    assert "unscoped: fusion 0.001200, copy 0.000400" in ctx["notes"][1]


def test_the_three_readers():
    ctx = context()
    tokens = 8192
    mlp = brun.load_metric("mlp_roofline").read(ctx)
    flops = 2 * 6 * tokens * 2 * 3 * 4096 * 14336
    assert mlp == pytest.approx(100 * flops / 197e12 / 0.012)
    head = brun.load_metric("lm_head_loss_roofline").read(ctx)
    flops = 2 * 6 * tokens * 32000 * 4096
    assert head == pytest.approx(100 * flops / 197e12 / 0.002)
    unscoped = brun.load_metric("unscoped_device_pct.train").read(ctx)
    assert unscoped == pytest.approx(100 * 0.0016 / 0.0192)


def test_gpt_counts_two_matrices():
    gpt = brun.load_json("configs", "gpt3-6.7b.json")
    assert regions.mlp_train_flops(gpt, 2, 8192) == \
        6 * 8192 * 2 * 2 * 4096 * 16384
    assert regions.lm_head_train_flops(gpt, 8192) == 6 * 8192 * 50304 * 4096


def test_a_program_without_regions_reads_nothing():
    # the parent's step: scopes that name no region, or none at all
    for scopes in ({}, {k: STEP + "jvp(forward)/dot" for k in SCOPES}):
        ctx = context(scopes)
        for name in ("mlp_roofline", "lm_head_loss_roofline",
                     "unscoped_device_pct.train"):
            assert brun.load_metric(name).read(ctx) is None
    # and no run of the step program at all
    ctx = context()
    ctx["trace"]["devices"][0]["modules"] = []
    assert brun.load_metric("unscoped_device_pct.train").read(ctx) is None
    assert brun.load_metric("mlp_roofline").read(ctx) is None
