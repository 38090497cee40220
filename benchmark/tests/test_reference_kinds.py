"""The references' layers by kind, and the attention core they share.

A family tells ``common`` the kind of layer ``i``; two kinds may hold the
same leaves and differ in their equations alone.  A toy family of that
sort (a windowed layer with RoPE, a full one without positions, on
identical leaves) is read by both comparisons; every family compiles one
reference layer per precision and kind, not per layer; the blocked,
windowed core equals a brute-force masked softmax and keeps no
``S x S`` array."""

import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config
from test_evabyte import tiny_config as evabyte_config
from test_qwen3_next import tiny_config as qwen3_next_config

from benchmark import weights
from benchmark.reference import common
from benchmark.reference.llama_ref import rms_norm, rope

F32 = common.Precision("f32")
HIGHEST = common.HIGHEST


# -- a toy family of two kinds on identical leaves ----------------------------

TOY = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 8, "vocab_size": 64, "window": 8, "block": 16,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
       "pattern": ["sliding", "sliding", "sliding", "full"]}
SWAPPED = dict(TOY, pattern=["full", "full", "full", "sliding"])
LAYERS = 4


class Toy:
    """``sliding``: RoPE and a window of ``cfg["window"]`` keys; ``full``:
    no position embedding, every earlier key.  Counts its traces by
    (precision, kind)."""

    def __init__(self):
        self.traces = collections.Counter()

    @staticmethod
    def layer_kind(cfg, i):
        return cfg["pattern"][i]

    @staticmethod
    def layer_prefix(i):
        return f"layers.{i}."

    @staticmethod
    def outer_names(cfg):
        return ["embed.weight", "norm.weight", "head.weight"]

    @staticmethod
    def layer_shapes(cfg, i):
        h, d = cfg["hidden_size"], cfg["head_dim"]
        nq, nk = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        return {"attn_norm.weight": (h,), "q.weight": (h, nq),
                "k.weight": (h, nk), "v.weight": (h, nk), "o.weight": (nq, h),
                "mlp_norm.weight": (h,), "up.weight": (h, 2 * h),
                "down.weight": (2 * h, h)}

    @classmethod
    def param_shapes(cls, cfg, layers):
        h, v = cfg["hidden_size"], cfg["vocab_size"]
        out = {"embed.weight": (v, h), "norm.weight": (h,),
               "head.weight": (h, v)}
        for i in range(layers):
            out.update({cls.layer_prefix(i) + k: s
                        for k, s in cls.layer_shapes(cfg, i).items()})
        return out

    @staticmethod
    def embed(params, ids, cfg):
        return params["embed.weight"][ids]

    def layer(self, x, lp, cfg, prec, kind, attend=common.causal_attention):
        self.traces[prec.mode, kind] += 1
        s, eps = x.shape[0], cfg["rms_norm_eps"]
        d, nh, nkv = (cfg["head_dim"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"])
        a = rms_norm(x, lp["attn_norm.weight"], eps)
        q = prec.mm(a, lp["q.weight"]).reshape(s, nh, d)
        k = prec.mm(a, lp["k.weight"]).reshape(s, nkv, d)
        v = prec.mm(a, lp["v.weight"]).reshape(s, nkv, d)
        window = None
        if kind == "sliding":
            pos = jnp.arange(s)
            q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos,
                                                          cfg["rope_theta"])
            window = cfg["window"]
        x = x + prec.mm(attend(q, k, v, window=window, block=cfg["block"]),
                        lp["o.weight"])
        b = rms_norm(x, lp["mlp_norm.weight"], eps)
        return x + prec.mm(jax.nn.gelu(prec.mm(b, lp["up.weight"])),
                           lp["down.weight"])

    @staticmethod
    def head(x, params, cfg, prec):
        return prec.mm(rms_norm(x, params["norm.weight"], cfg["rms_norm_eps"]),
                       params["head.weight"])


def toy_params(seed: int = 3) -> dict:
    """Weights large enough that attention is far from uniform."""
    key = jax.random.key(seed)
    out = {}
    for i, (name, shape) in enumerate(sorted(
            Toy.param_shapes(TOY, LAYERS).items())):
        if len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jax.random.normal(jax.random.fold_in(key, i), shape) \
                * (1.5 / np.sqrt(shape[0]) if name != "embed.weight" else 1.0)
    return out


def brute_attention(q, k, v, window=None, block=None):
    """Every score of every head at once, masked by ``0 <= t - s < window``."""
    s, h, d = q.shape
    g = h // k.shape[1]
    kh, vh = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, kh, precision=HIGHEST) / d ** 0.5
    dist = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (dist >= 0) & (dist < (s if window is None else window))
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, vh,
                      precision=HIGHEST).reshape(s, h * d)


def hand_hidden(toy, params, ids, cfg):
    """Layer by layer, the kinds read off the pattern, attention brute."""
    x = params["embed.weight"][ids]
    for i, kind in enumerate(cfg["pattern"]):
        lp = {k: params[f"layers.{i}.{k}"] for k in toy.layer_shapes(cfg, i)}
        x = toy.layer(x, lp, cfg, F32, kind, attend=brute_attention)
    return x


def greedy(toy, params, prompt, n):
    """``n`` greedy tokens of the toy family, each from a whole forward."""
    row = list(prompt)
    for _ in range(n):
        x = hand_hidden(toy, params, jnp.asarray(row), TOY)
        row.append(int(jnp.argmax(toy.head(x[-1:], params, TOY, F32)[0])))
    return row[len(prompt):]


def test_toy_kinds_read_right_through_served_token_gaps():
    """The family's own greedy tokens sit at the reference's best (a gap
    of float32 rounding); read as if the kinds were swapped, the same
    tokens lie below it; each (precision, kind) compiles once."""
    toy, params = Toy(), toy_params()
    rng = np.random.default_rng(0)
    seqs = [(p, greedy(toy, params, p, 6)) for p in
            (rng.integers(0, 64, n).tolist() for n in (30, 19, 41))]
    weights_of = lambda names: {n: params[n] for n in names}  # noqa: E731
    toy.traces.clear()
    got = common.served_token_gaps(toy, TOY, LAYERS, weights_of, seqs,
                                   pad_to=64, max_out=8, control="bf16")
    assert got["widest"] < 1e-5, got
    assert dict(toy.traces) == {(m, k): 1 for m in ("f32", "bf16")
                                for k in ("sliding", "full")}
    swapped = common.served_token_gaps(toy, SWAPPED, LAYERS, weights_of,
                                       seqs, pad_to=64, max_out=8)
    assert swapped["widest"] > 0.1, swapped


def test_toy_kinds_read_right_through_sequence_hidden_and_batch_loss():
    toy, params = Toy(), toy_params()
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, 37)))
    for row in ids:
        np.testing.assert_allclose(
            np.asarray(common.sequence_hidden(toy, params, row, TOY, F32,
                                              LAYERS)),
            np.asarray(hand_hidden(toy, params, row, TOY)),
            rtol=2e-5, atol=2e-5)
    labels = jnp.roll(ids, -1, axis=1)

    def hand_loss(p):
        total = 0.0
        for r in range(ids.shape[0]):
            logits = toy.head(hand_hidden(toy, p, ids[r], TOY), p, TOY, F32)
            total = total + common.cross_entropy_sum(logits, labels[r])
        return total / ids.size

    want_loss, want = jax.value_and_grad(hand_loss)(params)
    loss, grads = jax.value_and_grad(
        lambda p: common.batch_loss(toy, p, ids, labels, TOY, F32,
                                    LAYERS))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    for k in want:
        np.testing.assert_allclose(np.asarray(grads[k]), np.asarray(want[k]),
                                   rtol=1e-4, atol=1e-5 * float(
                                       jnp.max(jnp.abs(want[k]))), err_msg=k)
    # read with the kinds swapped, the loss is another
    swapped = common.batch_loss(toy, params, ids, labels, SWAPPED, F32,
                                LAYERS)
    assert abs(float(swapped) - float(loss)) > 1e-3


# -- every family: one compiled layer per precision and kind -----------------

class Counting:
    """A family whose ``layer`` counts its traces by (precision, kind)."""

    def __init__(self, family):
        self.family, self.traces = family, collections.Counter()

    def __getattr__(self, name):
        return getattr(self.family, name)

    def layer(self, x, lp, cfg, prec, kind):
        self.traces[prec.mode, kind] += 1
        return self.family.layer(x, lp, cfg, prec, kind)


def family_config(name: str) -> dict:
    if name == "evabyte":
        return evabyte_config()
    if name == "qwen3-next-80b-a3b":
        return qwen3_next_config()
    return tiny_config(name)


@pytest.mark.parametrize("name, kinds", [
    ("mistral-7b", {"full"}), ("gpt3-6.7b", {"full"}),
    ("evabyte", {"eva"}), ("qwen3-next-80b-a3b", {"linear", "full"})])
def test_one_compiled_layer_per_precision_and_kind(name, kinds):
    config = family_config(name)
    family = Counting(importlib.import_module("benchmark.reference."
                                              + config["reference"]))
    assert {family.layer_kind(config, i) for i in range(LAYERS)} == kinds
    shapes = family.param_shapes(config, LAYERS)
    rng = np.random.default_rng(2)
    seqs = [(rng.integers(0, 256, n).tolist(), rng.integers(0, 256, 5)
             .tolist()) for n in (40, 21)]
    got = common.served_token_gaps(
        family, config, LAYERS,
        lambda names: weights.make_weights(shapes, 2**31 + 5, names=names),
        seqs, pad_to=64, max_out=8, control="fp8")
    assert got["widest"] >= 0 and got["control_widest"] >= 0
    assert dict(family.traces) == {(m, k): 1 for m in ("f32", "fp8")
                                   for k in kinds}


# -- the attention core ------------------------------------------------------

def qkv(s, h=4, kv=2, d=8, seed=0):
    r = np.random.default_rng(seed)
    return tuple(jnp.asarray(r.normal(size=(s, n, d)), jnp.float32)
                 for n in (h, kv, kv))


@pytest.mark.parametrize("window", [None, 1, 7, 37])
def test_blocked_core_is_the_brute_force_softmax(window):
    """37 positions in blocks of 8 (the last one ragged); windows of 1 and
    7 slice the keys a block can see, 37 and None do not."""
    q, k, v = qkv(37)
    got = common.causal_attention(q, k, v, window=window, block=8)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(brute_attention(q, k, v, window)),
                               rtol=1e-5, atol=1e-6)
    if window == 1:     # each query sees itself alone
        np.testing.assert_allclose(
            np.asarray(got).reshape(37, 4, 8),
            np.asarray(jnp.repeat(v, 2, axis=1)), rtol=1e-6)


@pytest.mark.parametrize("window", [37, 38, 100])
def test_a_window_past_the_sequence_is_no_window(window):
    q, k, v = qkv(37, seed=1)
    for block in (8, 512):
        np.testing.assert_array_equal(
            np.asarray(common.causal_attention(q, k, v, window=window,
                                               block=block)),
            np.asarray(common.causal_attention(q, k, v, block=block)))


@pytest.mark.parametrize("window", [None, 5])
def test_blocked_core_gradients_are_the_brute_force_ones(window):
    q, k, v = qkv(29, seed=2)
    ct = jnp.asarray(np.random.default_rng(3).normal(size=(29, 32)),
                     jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v, window=window,
                                              block=8) * ct)

    got = jax.grad(loss(common.causal_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(brute_attention), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=name)


@pytest.mark.parametrize("grad", [False, True])
def test_compiled_core_holds_no_whole_score_matrix(grad):
    """At 16,384 positions one ``S x S`` float32 array is 1 GiB: the
    compiled core, forward or with its backward, holds less than that in
    temporaries (compiled for the CPU; nothing runs)."""
    s = 16384
    x = jax.ShapeDtypeStruct((s, 2, 128), jnp.float32)
    fn = common.causal_attention
    if grad:
        fn = jax.grad(
            lambda q, k, v: jnp.sum(common.causal_attention(q, k, v)),
            argnums=(0, 1, 2))
    temp = jax.jit(fn).lower(x, x, x).compile().memory_analysis() \
        .temp_size_in_bytes
    assert 0 < temp < s * s * 4, temp
