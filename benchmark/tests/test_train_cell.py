"""The training runner's phases at tiny size end to end, the control, and
the faults a training cell can have: each must come out as not correct."""

import pytest

from conftest import tiny_ctx

from benchmark.runners import train

CELLS = ["mistral-7b.train-8k", "gpt3-6.7b.train-8k"]


@pytest.mark.parametrize("cell", CELLS)
def test_run_is_correct(cell):
    res = train.run(tiny_ctx(cell, 2**31 + 21))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert all({"value", "limit"} <= set(c) for c in res["checks"].values())


def test_fault_state_unchanged(monkeypatch):
    """A step that returns its state unchanged."""
    from paddle_tpu.jit import TrainStep

    real = TrainStep._run

    def stuck(self, state, batch, accumulate):
        import jax
        import jax.numpy as jnp

        rng = state.pop("rng")          # a typed key: not copied, not used
        old = jax.tree.map(jnp.copy, state)
        new, metrics = real(self, dict(state, rng=rng), batch, accumulate)
        return dict(old, rng=new["rng"]), metrics

    monkeypatch.setattr(TrainStep, "_run", stuck)
    res = train.run(tiny_ctx(CELLS[0], 2**31 + 22))
    assert res["correct"] is False
    assert res["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from paddle_tpu.jit import TrainStep

    real = TrainStep._run

    def half(self, state, batch, accumulate):
        n = batch["input_ids"].shape[0] // 2
        return real(self, state, {k: v[:n] for k, v in batch.items()},
                    accumulate)

    monkeypatch.setattr(TrainStep, "_run", half)
    res = train.run(tiny_ctx(CELLS[0], 2**31 + 23))
    assert res["correct"] is False
    failing = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert "grad1_norm_gap" in failing or "change_norm_gap" in failing


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [2**31 + 31, 2**31 + 32, 2**31 + 33])
def test_control_and_faults_are_not_correct(cell, seed):
    """The reference in the program's place, computed in float8, and the
    reference with each fault planted, go through the run's own judge with
    the cell's limits (at this size: conftest.TINY_LIMITS): ``correct``
    comes out true of the program and false of each of them."""
    ctx = tiny_ctx(cell, seed)
    rows = {r["side"]: r for r in train.limit_readings(ctx, [seed], {seed})}
    assert rows["program"]["correct"] is True, rows["program"]
    for side in ("control_fp8", "fault_half_batch", "fault_state_unchanged"):
        assert rows[side]["correct"] is False, rows[side]
        assert rows[side]["fails"]
    assert rows["fault_state_unchanged"]["numbers"]["change_norm_gap"] == \
        pytest.approx(1.0)
