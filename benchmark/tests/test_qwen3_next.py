"""Qwen3-Next in the benchmark: the reference's leaves against the
program's through the builder, the work functions against hand counts,
the cell's and the configuration's files, and the training runner's
phases end to end at a tiny size with the control."""

import copy
import time

import jax
import pytest

from benchmark import run as brun
from benchmark.reference import qwen3_next_ref
from benchmark.runners import train
from benchmark.work import qwen3_next as work

CELL = "qwen3-next-80b-a3b.train-8k"
CONFIG = brun.load_json("configs", "qwen3-next-80b-a3b.json")

# every ratio of the published model at widths a CPU run can hold
TINY = {"hidden_size": 64, "vocab_size": 256, "head_dim": 16,
        "num_attention_heads": 8, "num_key_value_heads": 1,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "router_width": 16, "num_experts": 8, "first_expert": 4,
        "num_experts_per_tok": 4, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32,
        "max_position_embeddings": 512}
# at this size on the CPU (48 tokens a step), seeds 2**31+41..43 and 77:
# the program / the float8 control (a state left unchanged reads 1 in the
# change).  ``None``: the two sides read alike here, read and not compared
TINY_LIMITS = {"loss1_gap": None,           # 4.3e-4..2.4e-3 / 2.3e-3..4e-3
               "loss2_gap": None, "loss3_gap": None,
               "grad1_norm_gap": 0.03,      # 0.010..0.016 / 0.048..0.082
               "change_norm_gap": 0.1}      # 0.0075..0.020 / 0.021..0.035


def tiny_config() -> dict:
    config = copy.deepcopy(CONFIG)
    config.update(TINY)
    return config


def tiny_ctx(seed: int, seconds: float = 0.5) -> dict:
    cell = copy.deepcopy(brun.load_json("workloads", CELL + ".json"))
    cell["traffic"].update(batch=1, seq=48)
    cell["limits"] = dict(TINY_LIMITS)
    return {"name": CELL, "cell": cell, "config": tiny_config(), "seed": seed,
            "seconds": seconds, "trace": False,
            "t_start": time.perf_counter(),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "end_to_end": {"train_tokens_per_s": "tokens/s", "setup_s": "s"},
            "per_layer": {}, "load_metric": brun.load_metric,
            "device_report": lambda: brun.device_report(jax.devices(), 1)}


@pytest.mark.parametrize("layers", [4, 8])
def test_param_shapes_are_the_programs_leaves(layers):
    import importlib

    from paddle_tpu import nn
    from paddle_tpu.nn.layer import raw_params

    config = tiny_config()
    builder = importlib.import_module("benchmark.builders."
                                      + config["builder"])
    with nn.meta_init():
        model = builder.build_model(config, layers, 512)
    got = {k: tuple(v.shape) for k, v in raw_params(model).items()}
    assert got == qwen3_next_ref.param_shapes(config, layers)
    kinds = [qwen3_next_ref.is_full_attention(config, i)
             for i in range(layers)]
    assert kinds == [False, False, False, True] * (layers // 4)


def test_published_sizes_and_the_cut():
    """The cut's arithmetic at the published widths: 625,667,136
    parameters on this chip."""
    shapes = qwen3_next_ref.param_shapes(CONFIG, 4)
    n = 0
    for s in shapes.values():
        k = 1
        for d in s:
            k *= d
        n += k
    assert n == 625_667_136
    delta = {k: s for k, s in shapes.items()
             if k.startswith("model.layers.0.linear_attn.")}
    assert delta["model.layers.0.linear_attn.in_proj_qkvz.weight"] \
        == (2048, 12288)
    assert delta["model.layers.0.linear_attn.conv1d.weight"] == (8192, 4)
    assert shapes["model.layers.3.self_attn.q_proj.weight"] == (2048, 8192)
    assert shapes["model.layers.3.mlp.experts.down_proj"] == (32, 512, 2048)
    assert shapes["lm_head.weight"] == (2048, 18992)


def test_work_functions_at_hand_counted_sizes():
    c = CONFIG
    assert work.layer_kinds(c, 4) == (3, 1)
    assert work.layer_kinds(c, 48) == (36, 12)
    # 2048 x 12288 + 2048 x 64 + 4096 x 2048
    assert work.delta_mixer_params(c) == 25165824 + 131072 + 8388608
    # 2048 x 8192 + 2 x 2048 x 512 + 4096 x 2048
    assert work.full_mixer_params(c) == 16777216 + 2097152 + 8388608
    assert work.held_share(c) == 32 / 512
    # router 2048 x 512, shared 3 x 2048 x 512 and its gate, 10 / 16 of a
    # routed expert of 3 x 2048 x 512
    assert work.sparse_params_per_token(c) == pytest.approx(
        1048576 + 3145728 + 2048 + 0.625 * 3145728)
    per_token = 3 * 33685504 + 27262976 + 4 * (4196352 + 1966080) \
        + 2048 * 18992
    assert work.matmul_params_per_token(c, 4) == pytest.approx(per_token)
    assert work.delta_rule_flops_per_token(c) == 6 * 128 * 128 * 32
    # q, k 2 x 16 x 128 x 2 B; v 32 x 128 x 2 B; g, beta 2 x 32 x 4 B
    inputs = 8192 + 8192 + 256
    assert work.delta_rule_bytes_per_token(c) == 3 * inputs + 2 * 8192
    att = 6 * 2.0 * 8192 * 8192 * 4096 / 2 / 8192        # one full layer
    assert work.train_flops_per_token(c, 4, 8192) == pytest.approx(
        6 * per_token + att + 3 * 3 * 6 * 128 * 128 * 32)
    assert work.expert_rows(c, 8192) == 5120
    assert work.experts_flops(c, 4, 8192) == pytest.approx(
        3 * 5120 * 3 * 2 * 2048 * 512 * 4)
    assert work.experts_bytes(c, 4) == 2 * 32 * 3 * 2048 * 512 * 2 * 4


def test_the_files_name_what_the_runner_reads():
    cell = brun.load_json("workloads", CELL + ".json")
    assert cell["runner"] == "train" and cell["chips"] == 1
    assert cell["config"] == CONFIG["name"] == "qwen3-next-80b-a3b"
    assert cell["num_hidden_layers"] == CONFIG["num_hidden_layers"]["train"]
    assert cell["traffic"] == {"kind": "token_batches", "batch": 1,
                               "seq": 8192, "fetch_loss_every": 10}
    assert set(cell) == {"name", "config", "runner", "chips",
                         "num_hidden_layers", "stands_for", "traffic",
                         "optimizer", "step_program", "trace", "limits"}
    assert set(cell["limits"]) == {"loss1_gap", "loss2_gap", "loss3_gap",
                                   "grad1_norm_gap", "change_norm_gap"}
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    # every published width unchanged
    for key, value in {"hidden_size": 2048, "head_dim": 256,
                       "num_attention_heads": 16, "num_key_value_heads": 2,
                       "linear_num_key_heads": 16,
                       "linear_num_value_heads": 32,
                       "linear_key_head_dim": 128,
                       "linear_value_head_dim": 128,
                       "linear_conv_kernel_dim": 4,
                       "moe_intermediate_size": 512, "router_width": 512,
                       "num_experts_per_tok": 10,
                       "shared_expert_intermediate_size": 512,
                       "rope_theta": 10000000,
                       "partial_rotary_factor": 0.25,
                       "max_position_embeddings": 262144}.items():
        assert CONFIG[key] == value, key
    bench = brun.load_json("..", "BENCHMARK.json")
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == {"compiles_in_window.train", "device_idle_pct.train",
                    "unscoped_device_pct.train", "lm_head_loss_roofline",
                    "mfu.train_qwen3next", "gated_delta_roofline",
                    "moe_experts_roofline"}
    for name in mine:
        assert hasattr(brun.load_metric(name), "read")


def test_readers_find_nothing_on_a_program_without_the_scopes():
    """The parent's program has no such cell and no such scope: every new
    reader returns None and does not raise."""
    ctx = {"trace": {"devices": [], "host": []}, "scopes": {},
           "cell": brun.load_json("workloads", CELL + ".json"),
           "config": CONFIG, "layers": 4, "notes": [],
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "counters": {}}
    for name in ("mfu.train_qwen3next", "gated_delta_roofline",
                 "moe_experts_roofline"):
        assert brun.load_metric(name).read(ctx) is None


@pytest.mark.parametrize("seed", [2**31 + 41, 2**31 + 42, 2**31 + 43])
def test_run_is_correct_and_the_control_is_not(seed):
    """The runner's own phases on the cell's own files at a tiny size: the
    program bf16 under amp O2 against the float32 reference, and the
    reference in float8 in the program's place."""
    ctx = tiny_ctx(seed)
    rows = {r["side"]: r for r in train.limit_readings(ctx, [seed], {seed})}
    assert rows["program"]["correct"] is True, rows["program"]
    assert rows["control_fp8"]["correct"] is False, rows["control_fp8"]
    assert rows["fault_state_unchanged"]["correct"] is False
    assert rows["fault_state_unchanged"]["numbers"]["change_norm_gap"] == \
        pytest.approx(1.0)
    # the cell's batch is one row: the fault leaves half its positions out
    assert rows["fault_half_batch"]["correct"] is False, \
        rows["fault_half_batch"]


def test_run_prints_the_cells_line():
    res = train.run(tiny_ctx(2**31 + 44))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
