"""The decoder layer of both model families on the serving path that
remains: the unified ragged step over paged KV pools, against the same
layer's uncached forward, and the inputs the layer refuses.

``tests/test_ragged_attention.py`` pins the bare op and the serving
tests pin whole engines against ``generate()``; here the unit is one
``LlamaDecoderLayer`` / ``GPTDecoderLayer``: norm, projections, RoPE,
span write, ragged attention, output projection, MLP, both residuals.
"""

import dataclasses
import importlib

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.nn import functional as F

# the package exports functions under the modules' names
G = importlib.import_module("paddle_tpu.models.gpt")
L = importlib.import_module("paddle_tpu.models.llama")

PAGE, NUM_BLOCKS, MAX_BLOCKS, CHUNK = 4, 16, 4, 4
# three sequences walked in chunks of CHUNK: whole chunks, a ragged last
# chunk, and single-token (decode-shaped) spans at the end
SEQ_LENS = (11, 6, 9)


def _layer(family):
    """(layer, config, kv heads, call(x, **paged) -> out or (out, cache))
    for one decoder layer of ``family`` at the tiny preset's widths."""
    pt.seed(0)
    if family == "llama":
        cfg = L.PRESETS["tiny"]
        layer = L.LlamaDecoderLayer(cfg)
        kv_heads = cfg.num_key_value_heads

        def call(x, starts=None, **paged):
            s = x.shape[1]
            pos = None if starts is None else \
                starts[:, None] + jnp.arange(s)[None, :]
            cos, sin = F.rope_cos_sin(s, cfg.head_dim, base=cfg.rope_theta,
                                      dtype=x.dtype, position_ids=pos)
            if starts is not None:
                paged["span_starts"] = starts
            return layer(x, cos, sin, **paged)
    else:
        cfg = G.PRESETS["tiny"]
        layer = G.GPTDecoderLayer(cfg)
        kv_heads = cfg.num_attention_heads

        def call(x, starts=None, **paged):
            if starts is not None:
                paged["span_starts"] = starts
            return layer(x, **paged)
    layer.eval()
    return layer, cfg, kv_heads, call


def _pools(cfg, kv_heads, int8, rng):
    shape = (NUM_BLOCKS, PAGE, kv_heads, cfg.head_dim)
    if int8:
        return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.ones(shape[:3], jnp.float32),
                jnp.ones(shape[:3], jnp.float32))
    # stale values in every page: a row that attends past its prefix, or
    # a span written to the wrong page, shows
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def _tables(rng, slots):
    return jnp.asarray(rng.permutation(NUM_BLOCKS)[:slots * MAX_BLOCKS]
                       .reshape(slots, MAX_BLOCKS).astype(np.int32))


@pytest.mark.parametrize("pool", ["fp", "int8"])
@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_ragged_steps_match_the_uncached_forward(family, pool):
    """Sequences fed through the layer span by span over paged pools give
    the rows the uncached causal forward gives over the whole sequence."""
    rng = np.random.default_rng(7)
    layer, cfg, kv_heads, call = _layer(family)
    b, s_max = len(SEQ_LENS), max(SEQ_LENS)
    x = jnp.asarray(rng.normal(size=(b, s_max, cfg.hidden_size)),
                    jnp.float32)
    want = np.asarray(call(x))
    cache = _pools(cfg, kv_heads, pool == "int8", rng)
    tables = _tables(rng, b)
    done = np.zeros(b, np.int32)
    total = np.asarray(SEQ_LENS, np.int32)
    got = np.zeros_like(want)
    while (done < total).any():
        # the last two tokens of every sequence go one at a time
        lens = np.where(total - done > 2,
                        np.minimum(CHUNK, total - done - 2),
                        np.minimum(1, total - done)).astype(np.int32)
        span = np.zeros((b, CHUNK, cfg.hidden_size), np.float32)
        for i in range(b):
            span[i, :lens[i]] = np.asarray(x[i, done[i]:done[i] + lens[i]])
        out, cache = call(jnp.asarray(span), starts=jnp.asarray(done),
                          cache=cache, seq_lens=jnp.asarray(lens),
                          block_tables=tables)
        for i in range(b):
            got[i, done[i]:done[i] + lens[i]] = np.asarray(out)[i, :lens[i]]
        done = done + lens
    tol = dict(rtol=3e-2, atol=3e-2) if pool == "int8" \
        else dict(rtol=2e-5, atol=2e-5)
    for i, n in enumerate(SEQ_LENS):
        np.testing.assert_allclose(got[i, :n], want[i, :n], **tol)
    if pool == "int8":
        assert len(cache) == 4 and cache[0].dtype == jnp.int8


def test_dead_slots_leave_the_pool_unchanged_through_a_layer():
    """A slot with no span (length 0, out-of-range block table: the
    scheduler's sentinel for an idle slot) writes nothing: every page the
    live slot does not own comes back bitwise as it went in, and the live
    slot's rows are what it computes alone."""
    rng = np.random.default_rng(11)
    layer, cfg, kv_heads, call = _layer("llama")
    cache = _pools(cfg, kv_heads, False, rng)
    live = _tables(rng, 1)
    tables = jnp.concatenate(
        [live, jnp.full((2, MAX_BLOCKS), NUM_BLOCKS, jnp.int32)])
    x = jnp.asarray(rng.normal(size=(3, CHUNK, cfg.hidden_size)),
                    jnp.float32)
    starts = jnp.asarray([5, 0, 9], jnp.int32)
    lens = jnp.asarray([3, 0, 0], jnp.int32)
    out, new = call(x, starts=starts, cache=cache, seq_lens=lens,
                    block_tables=tables)
    alone, new_alone = call(x[:1], starts=starts[:1], cache=cache,
                            seq_lens=lens[:1], block_tables=live)
    np.testing.assert_array_equal(np.asarray(out)[0, :3],
                                  np.asarray(alone)[0, :3])
    assert np.isfinite(np.asarray(out)).all()
    others = np.setdiff1d(np.arange(NUM_BLOCKS), np.asarray(live))
    for before, after, after_alone in zip(cache, new, new_alone):
        np.testing.assert_array_equal(np.asarray(after)[others],
                                      np.asarray(before)[others])
        np.testing.assert_array_equal(np.asarray(after),
                                      np.asarray(after_alone))

    # and a step of dead slots only is the identity on the pool
    _, same = call(x[1:], starts=starts[1:], cache=cache, seq_lens=lens[1:],
                   block_tables=tables[1:])
    for before, after in zip(cache, same):
        np.testing.assert_array_equal(np.asarray(after), np.asarray(before))


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_paged_pools_need_span_starts(family):
    """Paged pools are served by the ragged step only: ``block_tables``
    without ``span_starts`` is refused by name, not routed elsewhere."""
    rng = np.random.default_rng(3)
    layer, cfg, kv_heads, call = _layer(family)
    cache = _pools(cfg, kv_heads, False, rng)
    x = jnp.zeros((2, 1, cfg.hidden_size), jnp.float32)
    with pytest.raises(ValueError, match="span_starts"):
        call(x, cache=cache, seq_lens=jnp.asarray([3, 5], jnp.int32),
             block_tables=_tables(rng, 2))


def test_fused_ops_has_three_values():
    """``"mega"`` left with the decode megakernel: it raises what any
    unknown value raises, when the layer resolves its fused ops."""
    ids = jnp.zeros((1, 4), jnp.int32)
    for mode in ("mega", "yes"):
        model = L.llama("tiny", fused_ops=mode)
        with pytest.raises(ValueError, match=r"expected on\|off\|auto$"):
            model(ids)
    for mode in ("on", "off", "auto"):
        assert L.llama("tiny", fused_ops=mode)(ids).shape == (1, 4, 256)


def test_llama_config_has_no_fuse_qkv_mlp():
    assert "fuse_qkv_mlp" not in {
        f.name for f in dataclasses.fields(L.LlamaConfig)}
    with pytest.raises(TypeError):
        L.LlamaConfig(fuse_qkv_mlp=True)


def test_gpt_config_has_no_fused_ops():
    """GPT's blocks have no fused entry to choose: the field went with
    the GELU kernel the chip's compiler cannot lower."""
    assert "fused_ops" not in {
        f.name for f in dataclasses.fields(G.GPTConfig)}
    with pytest.raises(TypeError):
        G.GPTConfig(fused_ops="auto")
    with pytest.raises(TypeError):
        G.gpt("tiny", fused_ops="auto")
