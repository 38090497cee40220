"""EvaByte in the benchmark: the plain reference against the program's
forward at tiny size through the builder, the work functions against hand
counts, the cell's file and its schedule, and the serving runner's phases
end to end on the cell's own cache kind (windows of 64 here, so that they
close inside a test-sized request)."""

import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY

from benchmark import run as brun
from benchmark import weights
from benchmark.reference import common, evabyte_ref
from benchmark.runners import serve
from benchmark.work import eva

CELL = "evabyte.serve-doc-bytes"
CONFIG = brun.load_json("configs", "evabyte.json")


def tiny_config() -> dict:
    config = copy.deepcopy(CONFIG)
    config.update(TINY, vocab_size=320, window_size=64,
                  max_position_embeddings=512)
    return config


def tiny_ctx(seed: int, seconds: float = 3.0) -> dict:
    cell = copy.deepcopy(brun.load_json("workloads", CELL + ".json"))
    cell["num_hidden_layers"] = 2
    cell["engine"].update(max_batch=4, max_seq_len=512, num_blocks=64)
    cell["traffic"].update(
        rate_rps=4.0, lead_in_s=0.5, grace_s=30.0,
        prompt_tokens={"median": 90, "sigma": 0.5, "min": 20, "max": 200},
        output_tokens={"median": 24, "sigma": 0.5, "min": 8, "max": 80})
    cell["limits"] = {"served_logit_gap": 4e-3, "requests_failed": 0.0}
    return {"name": CELL, "cell": cell, "config": tiny_config(), "seed": seed,
            "seconds": seconds, "trace": False,
            "t_start": time.perf_counter(),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "end_to_end": {"ttft_p95_ms": "ms", "itl_p95_ms": "ms",
                           "serve_tokens_per_s": "tokens/s", "setup_s": "s"},
            "per_layer": {}, "load_metric": brun.load_metric,
            "device_report": lambda: brun.device_report(jax.devices(), 1)}


def test_reference_matches_program_forward():
    """Over three windows and a ragged tail, all 8 heads; the seed's phi
    and mu are scaled up so that the summariser's softmax is far from
    uniform."""
    import importlib

    from paddle_tpu.nn.layer import _swapped_params, functional_call, \
        raw_params

    config = tiny_config()
    builder = importlib.import_module("benchmark.builders."
                                      + config["builder"])
    model = builder.build_model(config, 2, 512)
    shapes = {k: tuple(v.shape) for k, v in raw_params(model).items()}
    assert shapes == evabyte_ref.param_shapes(config, 2)
    params = weights.make_weights(shapes, 2**31 + 3, dtype="float32")
    params = {k: v * 50.0 if "adaptive" in k else v
              for k, v in params.items()}
    ids = np.random.default_rng(0).integers(0, 320, (2, 3 * 64 + 27))
    hidden = functional_call(
        model.model, {k[6:]: v for k, v in params.items()
                      if k.startswith("model.")},
        jnp.asarray(ids), training=False)
    with _swapped_params(model, params):
        got = model.all_heads_logits(hidden)
    for row in range(2):
        x = common.sequence_hidden(evabyte_ref, params,
                                   jnp.asarray(ids[row]), config,
                                   common.Precision("f32"), 2)
        ref = evabyte_ref.all_heads(x, params, config,
                                    common.Precision("f32"))
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)
        np.testing.assert_array_equal(
            np.asarray(evabyte_ref.head(x, params, config,
                                        common.Precision("f32"))),
            np.asarray(ref[:, 0]))


def test_reference_is_the_long_hand_sum():
    """Steps 2 and 3 term by term in numpy, one query at a time."""
    r = np.random.default_rng(5)
    s, h, d, win, c = 150, 2, 8, 64, 16
    q, k, v = (r.normal(size=(s, h, d)) for _ in range(3))
    phi, mu = r.normal(size=(h, d)), r.normal(size=(h, d))
    got = np.asarray(evabyte_ref.eva_attention(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, v, phi, mu)), win, c)
    ).reshape(s, h, d)
    sc = d ** -0.5
    for t in (0, 15, 16, 63, 64, 100, 127, 128, 149):
        for hh in range(h):
            keys = [k[m, hh] for m in range(t // win * win, t + 1)]
            vals = [v[m, hh] for m in range(t // win * win, t + 1)]
            for j in range((win // c) * (t // win)):
                kc, vc = k[c * j:c * j + c, hh], v[c * j:c * j + c, hh]
                w = np.exp(sc * kc @ phi[hh])
                w /= w.sum()
                keys.append(w @ kc + mu[hh])
                vals.append(w @ vc)
            e = np.exp(sc * np.asarray(keys) @ q[t, hh])
            np.testing.assert_allclose(got[t, hh], e @ np.asarray(vals)
                                       / e.sum(), rtol=2e-5, atol=2e-6)


def test_work_counts():
    cfg = CONFIG
    # a query at 5,000: 904 keys of its window (4096..5000) + 2 x 128
    assert eva.keys_seen(5000, 2048, 16) == 905 + 256
    assert eva.keys_seen(2047, 2048, 16) == 2048
    assert eva.keys_seen(2048, 2048, 16) == 1 + 128
    # 5,000 positions written: 904 window rows + 312 completed chunks
    assert eva.rows_held(5000, 2048, 16) == 904 + 312
    # k and v of one row: 2 x 32 x 128 x 2 B = 16 KiB a layer
    assert eva.row_bytes(cfg, 1) == 16384
    assert eva.row_bytes(cfg, 16) == 256 * 1024
    # q, k, v, o 4096 x 4096 and three 4096 x 11008: 202.4 M a layer
    assert eva.layer_matmul_params(cfg, 1) == 202_375_168
    assert eva.head_params(cfg) == 4096 * 320
    assert eva.attention_flops_per_token(cfg, 16, 1000) == 4 * 4096e3 * 16
    assert eva.summariser_flops_per_token(cfg, 16) == 6 * 4096 * 16
    assert eva.serve_flops(cfg, 16, 10, 2, 1000) == 10 * (
        2 * 16 * 202_375_168 + 4 * 4096e3 * 16 + 6 * 4096 * 16) \
        + 2 * 2 * 4096 * 320


def test_cell_file_and_schedule():
    cell = brun.load_json("workloads", CELL + ".json")
    t = cell["traffic"]
    assert cell["config"] == "evabyte" and cell["chips"] == 1
    assert cell["runner"] == "serve" and cell["num_hidden_layers"] == 16
    assert (t["prompt_tokens"], t["output_tokens"]) == (
        {"median": 4096, "sigma": 0.8, "min": 512, "max": 16384},
        {"median": 512, "sigma": 0.7, "min": 64, "max": 1024})
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"])
    assert (t["lead_in_s"], t["grace_s"], t["shuffle_block"]) == (20, 60, 4)
    assert cell["engine"]["enable_prefix_caching"] is False
    ctx = {"cell": cell, "config": CONFIG, "seconds": 50.0,
           "seed": 2**31 + 7}
    schedule = serve.make_schedule(ctx)
    sample = [r for r in schedule if r["sample"]]
    assert len(sample) == round(t["rate_rps"] * 50.0)
    assert len(schedule) - len(sample) == round(t["rate_rps"] * 20.0)
    assert all(512 <= len(r["prompt"]) <= 16384
               and 64 <= r["max_tokens"] <= 1024
               and max(r["prompt"]) < 320 for r in schedule)
    bench = brun.load_json("..", "BENCHMARK.json")
    mine = {m["name"] for g in ("end_to_end", "per_layer")
            for m in brun.cell_metrics(bench, CELL, g)}
    assert {"eva_attention_roofline", "mfu.serve_eva", "itl_p95_ms",
            "setup_s"} <= mine
    assert not {"ragged_attention_roofline", "mfu.serve"} & mine


def test_run_is_correct():
    res = serve.run(tiny_ctx(2**31 + 41))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 12 and res["failed"] == 0
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_control_is_not_correct():
    seeds = [2**31 + 51, 2**31 + 52, 2**31 + 53]
    ctx = tiny_ctx(seeds[0], seconds=2.0)
    ctx["cell"]["limits_window_s"] = 2.0
    rows = list(serve.limit_readings(ctx, seeds, set(seeds)))
    prog = [r["correct"] for r in rows if r["side"] == "program"]
    ctl = [r["correct"] for r in rows if r["side"] == "control_fp8"]
    assert prog == [True] * 3 and ctl == [False] * 3, rows


def test_readers_find_nothing_on_a_program_without_the_kernel():
    """As on the parent commit: no event of the kernel's name, no
    histogram: both readers return None and do not raise."""
    rctx = {"trace": {"devices": [{"ops": [], "modules": []}]},
            "scopes": {}, "cell": brun.load_json("workloads", CELL + ".json"),
            "config": CONFIG, "layers": 16,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "engine": {"max_batch": 32, "prefill_chunk": 16, "page_size": 16},
            "counters": {"traced": {}, "kv_blocks_polls": []}, "notes": []}
    for name in ("eva_attention_roofline", "mfu.serve_eva"):
        assert brun.load_metric(name).read(rctx) is None
