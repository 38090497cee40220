"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
(not part of ``tests/``: the benchmark's own checks of its yardstick)."""

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "head_dim": 16, "vocab_size": 256}


# The training cells' limits at this size, set as the cells' own are: above
# the largest the program read and below the smallest the float8 control or
# a fault read, on seeds 2**31+31..33 and 77 at this size on the CPU
# (program / control / half batch; a state left unchanged reads 1 in the
# change).  ``None``: no upper reading at this size, read and not compared.
TINY_LIMITS = {
    "mistral-7b.train-8k": {
        "loss1_gap": 6e-4,          # 1.7e-4 / 8.9e-4 / 1.2e-3
        "loss2_gap": None, "loss3_gap": None,
        "grad1_norm_gap": 2.5e-3,   # 6.5e-4 / 5.9e-3 / 0.09
        "change_norm_gap": 0.02},   # 1.5e-3 / 3.6e-3 / 0.23
    "gpt3-6.7b.train-8k": {
        "loss1_gap": 3e-4,          # 1.1e-4 / 4.8e-4 / 1.1e-4
        "loss2_gap": None,
        "loss3_gap": 5e-4,          # 2.2e-4 / 8.3e-4 / 1.1e-3
        "grad1_norm_gap": 0.02,     # 4.1e-3 / 6.2e-3 / 0.118
        "change_norm_gap": 0.05},   # 0.0159 / 0.0165 / 0.085
}


def tiny_config(name: str) -> dict:
    from benchmark import run as brun

    config = brun.load_json("configs", name + ".json")
    config.update(TINY)
    if "num_key_value_heads" in config:
        config["num_key_value_heads"] = 2
    if config["builder"] == "gpt":
        config["intermediate_size"] = 256
        config["max_position_embeddings"] = 64
    return config


def tiny_ctx(cell_name: str, seed: int, seconds: float = 0.5, **cell_over):
    """A run's context without the look for a chip: the cell's own files,
    cut to a size a test run can hold."""
    import jax

    from benchmark import run as brun

    cell = copy.deepcopy(brun.load_json("workloads", cell_name + ".json"))
    config = tiny_config(cell["config"])
    if cell["runner"] == "train":
        cell["traffic"].update(batch=2, seq=32)
        cell["limits"] = dict(TINY_LIMITS[cell_name])
        e2e = {"train_tokens_per_s": "tokens/s", "setup_s": "s"}
    else:
        cell["num_hidden_layers"] = 2
        cell["engine"] = {"max_batch": 4, "max_seq_len": 128,
                          "num_blocks": 64}
        cell["traffic"].update(
            rate_rps=4.0, lead_in_s=0.5, grace_s=30.0,
            prompt_tokens={"median": 24, "sigma": 0.5, "min": 8, "max": 64},
            output_tokens={"median": 12, "sigma": 0.5, "min": 4, "max": 32})
        cell["limits"] = {"served_logit_gap": 4e-3, "requests_failed": 0.0}
        e2e = {"ttft_p95_ms": "ms", "itl_p95_ms": "ms",
               "serve_tokens_per_s": "tokens/s", "setup_s": "s"}
    cell.update(cell_over)
    return {"name": cell_name, "cell": cell, "config": config, "seed": seed,
            "seconds": seconds, "trace": False,
            "t_start": time.perf_counter(),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "end_to_end": e2e, "per_layer": {},
            "load_metric": brun.load_metric,
            "device_report": lambda: brun.device_report(jax.devices(), 1)}


@pytest.fixture
def events():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_events.json")
    with open(path) as f:
        return json.load(f)


@pytest.fixture
def serve_events(events):
    """The file's second trace: a serving loop's thread and a handler's."""
    return events["serve"]
