"""ZeRO group-sharded tests (SURVEY.md §4: parallel == serial numerics).

Reference pattern: test/collective/fleet/hybrid_parallel_sharding_model.py
— train under each sharding stage and compare losses to the unsharded run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import fleet, group_sharded_parallel
from paddle_tpu.distributed.sharding import (DygraphShardingOptimizer,
                                             GroupShardedOptimizerStage2,
                                             zero_stage_of)
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.llama import causal_lm_loss, llama


@pytest.fixture(autouse=True)
def _fleet_reset():
    yield
    fleet._reset()


def _run(level=None, steps=4):
    fleet._reset()
    pt.seed(0)
    mesh = None
    if level is not None:
        s = fleet.DistributedStrategy()
        s.hybrid_configs = {"sharding_degree": 4, "dp_degree": 2}
        hcg = fleet.init(strategy=s)
        mesh = hcg.mesh
    model = llama("tiny")
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    scaler = None
    if level is not None:
        model, opt, scaler = group_sharded_parallel(model, opt, level)
    step = TrainStep(model, causal_lm_loss, opt, mesh=mesh)
    state = step.init_state(seed=0)
    ids = np.random.default_rng(0).integers(0, 256, size=(8, 32))
    batch = {"input_ids": jnp.asarray(ids, jnp.int32),
             "labels": jnp.asarray(np.roll(ids, -1, 1), jnp.int32)}
    losses = []
    for _ in range(steps):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses, step, state


def test_all_stages_match_serial():
    serial, _, _ = _run(None)
    for level in ("os", "os_g", "p_g_os"):
        sharded, step, _ = _run(level)
        np.testing.assert_allclose(serial, sharded, rtol=2e-4,
                                   err_msg=f"level={level}")


def test_stage_recorded_on_optimizer():
    pt.seed(0)
    model = llama("tiny", num_hidden_layers=1)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"sharding_degree": 8}
    fleet.init(strategy=s)
    for level, want in (("os", 1), ("os_g", 2), ("p_g_os", 3)):
        m2, o2, _ = group_sharded_parallel(model, opt, level)
        assert zero_stage_of(o2, None) == want
        # wrapper still exposes the inner optimizer API
        assert o2.apply is not None and o2.init is not None
    with pytest.raises(ValueError):
        group_sharded_parallel(model, opt, "bogus")


def test_stage3_param_storage_is_sharded():
    """p_g_os must actually shard parameter storage over the zero axes."""
    _, step, state = _run("p_g_os", steps=1)
    assert step.zero_stage == 3
    mesh = step.mesh
    big = {k: v for k, v in state["params"].items() if v.ndim >= 2}
    sharded = 0
    for k, v in big.items():
        spec = step.param_specs()[k]
        if any(e in ("sharding", "dp") or
               (isinstance(e, tuple) and
                any(a in ("sharding", "dp") for a in e))
               for e in spec if e is not None):
            sharded += 1
    assert sharded >= len(big) // 2, (
        f"only {sharded}/{len(big)} big params zero-sharded")


def test_stage2_grads_use_zero_sharded_specs():
    """ZeRO-2's signature: large grads carry the zero-axis sharding (XLA
    then reduce-scatters them; the CPU partitioner lowers that as
    all-reduce + slice, so assert on the specs, not HLO strings)."""
    _, step, state = _run("os_g", steps=1)
    assert step.zero_stage == 2
    pspecs = step.param_specs()
    gspecs = step.grad_specs(state["params"], pspecs)
    zeroed = [k for k, spec in gspecs.items()
              if any(e in ("sharding", "dp") for e in spec if e is not None)
              and spec != pspecs[k]]
    big = [k for k, v in state["params"].items() if v.size >= 2048]
    assert len(zeroed) >= len(big) // 2, (
        f"only {len(zeroed)} grads zero-sharded of {len(big)} big params")
    # stage 1 must NOT shard grads beyond the param spec
    _, step1, state1 = _run("os", steps=1)
    g1 = step1.grad_specs(state1["params"], step1.param_specs())
    assert g1 == step1.param_specs()


@pytest.mark.parametrize("level", ["os", "os_g", "p_g_os"])
def test_state_keeps_its_layout_and_the_step_compiles_once(level):
    """The state leaves the step laid out as it went in.  Left to
    itself XLA returns ZeRO-1's updated params in the optimizer's
    sharded layout: the second call then compiles another program
    (13 s at Llama-2-7B widths on four real chips, PR 21) and every
    later step runs with a layout nobody asked for."""
    from paddle_tpu.observability.recompile import RecompileSentinel

    fleet._reset()
    pt.seed(0)
    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"sharding_degree": 2, "mp_degree": 2,
                        "dp_degree": 2}
    mesh = fleet.init(strategy=s).mesh
    model = llama("tiny")
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    model, opt, _ = group_sharded_parallel(model, opt, level)
    step = TrainStep(model, causal_lm_loss, opt, mesh=mesh)
    state = step.init_state(seed=0)
    ids = np.random.default_rng(0).integers(0, 256, size=(8, 32))
    batch = {"input_ids": jnp.asarray(ids, jnp.int32),
             "labels": jnp.asarray(np.roll(ids, -1, 1), jnp.int32)}

    def layout(st):
        return {jax.tree_util.keystr(path): leaf.sharding
                for path, leaf in jax.tree_util.tree_leaves_with_path(st)}

    before = layout(state)
    state, _ = step(state, batch)
    after = layout(state)
    assert after.keys() == before.keys()
    moved = [k for k in before if after[k] != before[k]]
    assert not moved, f"layout changed across one step: {moved[:4]}"
    sentinel = RecompileSentinel()
    sentinel.install()
    try:
        for _ in range(2):
            state, m = step(state, batch)
        float(m["loss"])
    finally:
        sentinel.uninstall()
    assert sentinel.compiles() == 0
