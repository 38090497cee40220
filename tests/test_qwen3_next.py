"""Qwen3-Next (``models/qwen3_next.py``), the gated delta rule
(``incubate.nn.functional.gated_delta_rule``) and the dropless expert
layer (``distributed.moe.DroplessMoE``) on the CPU at tiny widths that
keep every ratio of the published model: 2 key heads / 4 value heads,
8 query heads on 1 kv head, a quarter of the head rotated, 16 experts
top-4 with a shared expert, 8 layers so that two periods run.  The
yardstick is the benchmark's plain reference
(``benchmark/reference/qwen3_next_ref.py``), which imports nothing of the
program and runs the delta rule as the recurrence, position by position,
and every held expert densely.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import amp, nn, observability as obs, optimizer, serving
from paddle_tpu.distributed import moe
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import qwen3_next as Q
from paddle_tpu.nn.layer import functional_call, raw_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from benchmark.reference import common, qwen3_next_ref as ref  # noqa: E402

F32 = common.Precision("f32")
LAYERS = 8


def ref_config(cfg, held=None):
    first, count = held or cfg.experts_held or (0, cfg.num_experts)
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "full_attention_interval": cfg.full_attention_interval,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "rope_theta": cfg.rope_theta,
            "linear_num_key_heads": cfg.linear_num_key_heads,
            "linear_num_value_heads": cfg.linear_num_value_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
            "router_width": cfg.num_experts, "first_expert": first,
            "num_experts": count,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "shared_expert_intermediate_size":
                cfg.shared_expert_intermediate_size,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.rms_norm_eps}


def seeded_leaves(shapes, seed):
    """Matrices at 0.5 / sqrt(fan-in) x 4 (logits of order 10, a router
    far from uniform), one-dimensional leaves at 0.3 (norm offsets, A_log
    and dt_bias all away from their defaults)."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray((0.3 if len(s) == 1 else 0.5)
                           * rng.normal(size=s), jnp.float32)
            for k, s in sorted(shapes.items())}


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return Q.qwen3_next("tiny", num_hidden_layers=LAYERS)


@pytest.fixture(scope="module")
def leaves(model):
    shapes = {k: tuple(v.shape) for k, v in raw_params(model).items()}
    assert shapes == ref.param_shapes(ref_config(model.cfg), LAYERS)
    return seeded_leaves(shapes, 7)


@pytest.fixture(scope="module")
def ids():
    # 75 positions: one whole chunk of 64 and a ragged second one
    return jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 75)),
                       jnp.int32)


def test_layer_kinds_follow_the_interval(model):
    kinds = [layer.full_attention for layer in model.model.layers]
    assert kinds == [False, False, False, True] * 2
    names = set(raw_params(model))
    assert "model.layers.3.self_attn.q_norm.weight" in names
    assert "model.layers.4.linear_attn.A_log" in names
    assert "model.layers.3.linear_attn.A_log" not in names


def test_logits_match_reference(model, leaves, ids):
    """float32 against float32 through eight layers of both kinds: the
    program's chunked rule, grouped product and fused projections against
    the recurrence and the dense experts.  Logits are of order 20 here and
    agree to 2.5e-4 (sums in another order); 2e-3 is eight times that and
    a hundredth of what bfloat16 operands (2**-9 of 20 a product, over
    eight layers) or a dropped term (a gate, a decay, the shared expert:
    each moves logits by more than 0.1) would give."""
    got = functional_call(model, leaves, ids)
    rc = ref_config(model.cfg)
    want = jnp.stack([common.sequence_logits(ref, leaves, row, rc, F32,
                                             LAYERS) for row in ids])
    assert float(jnp.max(jnp.abs(want))) > 5.0
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=0)


def test_loss_and_every_gradient_match_reference(model, leaves, ids):
    """The loss to 1e-5 (a mean of 150 float32 cross entropies of order 6)
    and every leaf's gradient to a thousandth of its norm, the median
    leaf's where a leaf's own is smaller: float32 sums in another order
    give 1e-5 .. 1e-4 through the scan's and the loop's backward passes; a
    routing weight whose gradient did not reach the router, or a dropped
    row, moves ``mlp.gate.weight`` or an expert's leaf by its whole
    norm."""
    labels = jnp.roll(ids, -1, axis=1)
    batch = {"input_ids": ids, "labels": labels}
    rc = ref_config(model.cfg)

    def program_loss(p):
        return functional_call(model, p, ids, labels=labels)

    loss, grads = jax.value_and_grad(program_loss)(leaves)
    want_loss, want = jax.value_and_grad(
        lambda p: common.batch_loss(ref, p, batch["input_ids"],
                                    batch["labels"], rc, F32, LAYERS))(leaves)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    norms = {k: float(jnp.linalg.norm(v)) for k, v in want.items()}
    floor = float(np.median(list(norms.values())))
    assert all(n > 0 for n in norms.values())
    worst = max(
        (float(jnp.linalg.norm(grads[k] - want[k])) / max(norms[k], floor),
         k) for k in want)
    assert worst[0] < 1e-3, worst


@pytest.mark.parametrize("length", [64, 128, 37, 100])
@pytest.mark.parametrize("decay", ["strong", "near_zero", "mixed"])
def test_chunked_delta_rule_matches_the_recurrence(length, decay):
    """The recurrence is the definition.  Lengths that are and are not
    multiples of the chunk (16 here); ``g`` strongly negative (-20: the
    state is forgotten at once, ``exp`` underflows in the chunk's decay
    matrix), near zero (-1e-3: the state keeps everything and the
    triangular system is at its worst conditioned) and mixed.  Both sides
    float32: 2e-5 of outputs of order 1 is sums in another order; a wrong
    decay power or a missing term is of order 0.1."""
    rng = np.random.default_rng(length)
    b, h, dk, dv = 2, 3, 16, 8
    q, k = (rng.normal(size=(b, length, h, dk)) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, length, h, dv))
    beta = rng.uniform(0.05, 1.0, size=(b, length, h))
    g = {"strong": np.full((b, length, h), -20.0),
         "near_zero": np.full((b, length, h), -1e-3),
         "mixed": -rng.exponential(1.0, size=(b, length, h))
         * rng.choice([1e-3, 1.0, 30.0], size=(b, length, h))}[decay]
    q, k, v, g, beta = (jnp.asarray(x, jnp.float32)
                        for x in (q, k, v, g, beta))
    got = IF.gated_delta_rule(q, k, v, g, beta, chunk=16)
    want = jnp.stack([ref.delta_recurrence(q[i], k[i], v[i], g[i], beta[i])
                      for i in range(b)])
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_chunked_delta_rule_gradients_match_the_recurrence():
    """Autodiff through the scan over chunks against autodiff through the
    recurrence, in every input; a strongly negative ``g`` among them, where
    a mask on the decay matrix's value and not on its exponent would give
    NaN."""
    rng = np.random.default_rng(3)
    s, h, dk, dv = 40, 2, 8, 8
    args = [rng.normal(size=(1, s, h, dk)) * 0.4,
            rng.normal(size=(1, s, h, dk)) * 0.4,
            rng.normal(size=(1, s, h, dv)),
            -rng.exponential(1.0, size=(1, s, h))
            * rng.choice([1e-2, 1.0, 40.0], size=(1, s, h)),
            rng.uniform(0.05, 1.0, size=(1, s, h))]
    args = [jnp.asarray(a, jnp.float32) for a in args]
    w = jnp.asarray(rng.normal(size=(1, s, h, dv)), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(
        IF.gated_delta_rule(*a, chunk=16) * w), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(
        ref.delta_recurrence(*(x[0] for x in a)) * w[0]),
        argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=1e-4 * float(
            jnp.max(jnp.abs(b))), rtol=0)


def interpret_delta_kernel(monkeypatch):
    """The gated delta rule's TPU dispatch (its gate and both kernels)
    through the Pallas interpreter on the CPU; counts the calls the
    kernel served and the calls it declined."""
    from paddle_tpu.ops import dispatch
    from paddle_tpu.ops import pallas as P
    calls = {"served": 0, "declined": 0}

    def kernel(q, k, v, g, beta, chunk):
        out = P._gated_delta_rule_dispatch(q, k, v, g, beta, chunk,
                                           interpret=True)
        calls["declined" if out is None else "served"] += 1
        return out
    monkeypatch.setitem(dispatch._REGISTRY, "gated_delta_rule", kernel)
    monkeypatch.setitem(dispatch._PLATFORM, "gated_delta_rule", "cpu")
    return calls


@pytest.fixture
def interpreted_delta_kernel(monkeypatch):
    return interpret_delta_kernel(monkeypatch)


# name: (positions, key heads, value heads, v's dtype, decay).  Two value
# heads are one grid step's packed rows and read one key head through the
# index map (dq, dk summed inside the step); 200 positions are three
# whole chunks of 64 and a ragged fourth, so the state crosses chunks and
# dS crosses back; "two_keys" is two grid steps a chunk, a key head each,
# "repeat_4" two steps that share one (their dq, dk summed after the
# kernel); "forget" is exp underflowing inside the decay matrix and
# between chunks; "bf16" is the cell's operands (float32 q and k from the
# normalisation beside bfloat16 v: every product at full precision),
# "bf16_qk" q and k in bfloat16 too (the state products round their
# operands to bfloat16)
DELTA_KERNEL_CASES = {
    "ragged": (200, 1, 2, jnp.float32, "mixed"),
    "whole": (256, 1, 2, jnp.float32, "mixed"),
    "forget": (192, 1, 2, jnp.float32, "strong"),
    "keep": (130, 1, 2, jnp.float32, "near_zero"),
    "two_keys": (136, 2, 4, jnp.float32, "mixed"),
    "repeat_4": (136, 1, 4, jnp.float32, "mixed"),
    "bf16": (200, 1, 2, jnp.bfloat16, "mixed"),
    "bf16_qk": (200, 1, 2, jnp.bfloat16, "mixed"),
}


def delta_kernel_operands(case):
    s, hk, hv, dtype, decay = DELTA_KERNEL_CASES[case]
    rng = np.random.default_rng(s + hk + hv)
    d = 128
    q, k = (rng.normal(size=(1, s, hk, d)) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(1, s, hv, d))
    beta = rng.uniform(0.05, 1.0, size=(1, s, hv))
    g = {"strong": np.full((1, s, hv), -20.0),
         "near_zero": np.full((1, s, hv), -1e-3),
         "mixed": -rng.exponential(1.0, size=(1, s, hv))
         * rng.choice([1e-3, 1.0, 30.0], size=(1, s, hv))}[decay]
    # q and k float32 as the model's normalisation hands them over
    # ("bf16_qk": in v's dtype)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    qk = lambda x: jnp.asarray(x, dtype if case == "bf16_qk" else jnp.float32)
    return qk(q), qk(k), jnp.asarray(v, dtype), f32(g), f32(beta)


def delta_recurrence(q, k, v, g, beta):
    """The reference's position-by-position rule, float32, every value
    head with its key head's q and k."""
    rep = v.shape[2] // q.shape[2]
    return ref.delta_recurrence(
        *(jnp.repeat(x[0].astype(jnp.float32), rep, 1) for x in (q, k)),
        v[0].astype(jnp.float32), g[0], beta[0])[None]


def rel_gap(a, b, floor=0.0):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), floor))


def delta_kernel_tolerance(case, dtype):
    """Of the norm: float32 sums in another order; ``dv`` rounded to
    bfloat16 once; with bfloat16 q and k every state product's operands
    rounded."""
    return 2e-2 if case == "bf16_qk" else \
        2e-5 if dtype == jnp.float32 else 4e-3


@pytest.mark.parametrize("case", sorted(DELTA_KERNEL_CASES))
def test_delta_kernel_matches_the_recurrence_and_the_composition(
        case, interpreted_delta_kernel):
    """The Pallas kernel (interpreted) against the definition and against
    the XLA composition it replaces on the chip.  Float32: sums in
    another order, with bfloat16 ``v`` too (``o`` is float32 on both
    paths).  bfloat16 q and k: every operand of the state products is
    rounded to 8 bits of mantissa."""
    args = delta_kernel_operands(case)
    got = IF.gated_delta_rule(*args)
    assert interpreted_delta_kernel == {"served": 1, "declined": 0}
    assert got.dtype == jnp.float32 and got.shape == args[2].shape
    tol = delta_kernel_tolerance(case, got.dtype)
    for want in (delta_recurrence(*args), IF._gated_delta_rule_ref(*args)):
        assert float(jnp.max(jnp.abs(want))) > 0.02
        assert rel_gap(got, want) < tol


@pytest.mark.parametrize("case", sorted(DELTA_KERNEL_CASES))
def test_delta_kernel_gradients_match_the_recurrence_and_the_composition(
        case, interpreted_delta_kernel):
    """The backward kernel against autodiff through the recurrence and
    through the composition, in all five inputs; every gradient finite
    (a strongly negative ``g`` among the cases).  The floor under the
    norm is for ``dg`` where everything is forgotten: it is 1e-10 an
    element there, and the composition's own reads 1e-9 (the diagonal of
    ``q k^T * M`` reaches ``G_i`` with both signs and cancels in
    rounding only; the kernel leaves it out)."""
    args = delta_kernel_operands(case)
    w = jnp.asarray(np.random.default_rng(1).normal(size=args[2].shape),
                    jnp.float32)

    def grads(rule):
        return jax.grad(lambda *a: jnp.sum(
            rule(*a).astype(jnp.float32) * w), argnums=range(5))(*args)
    got = grads(IF.gated_delta_rule)
    assert interpreted_delta_kernel["served"] >= 1
    assert interpreted_delta_kernel["declined"] == 0
    for rule in (delta_recurrence, IF._gated_delta_rule_ref):
        for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got,
                              grads(rule)):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
            assert rel_gap(a, b, floor=1e-2) < delta_kernel_tolerance(
                case, a.dtype), (name, rule.__name__)


@pytest.mark.parametrize(
    "why", ["head_size", "odd_heads", "own_keys", "chunk", "mesh"])
def test_delta_kernel_declines_into_the_composition(
        why, interpreted_delta_kernel):
    """What the gate does not admit takes the XLA composition, with the
    composition's results: a head size that is no multiple of 128, an odd
    number of value heads to a key head (three, or one: a key head a
    value head), another chunk than the kernel's, an active mesh (Mosaic
    kernels cannot be partitioned by GSPMD)."""
    import contextlib
    rng = np.random.default_rng(5)
    hk, hv, d = {"odd_heads": (1, 3, 128), "own_keys": (2, 2, 128),
                 "head_size": (1, 2, 64)}.get(why, (1, 2, 128))
    q, k, v = (jnp.asarray(rng.normal(size=(1, 70, h, d)) * d ** -0.5,
                           jnp.float32) for h in (hk, hk, hv))
    g = jnp.asarray(-rng.exponential(1.0, size=(1, 70, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 1.0, size=(1, 70, hv)), jnp.float32)
    chunk = 32 if why == "chunk" else 64
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",)) \
        if why == "mesh" else contextlib.nullcontext()
    with mesh:
        got = IF.gated_delta_rule(q, k, v, g, beta, chunk=chunk)
    assert interpreted_delta_kernel == {"served": 0, "declined": 1}
    np.testing.assert_array_equal(
        got, IF._gated_delta_rule_ref(q, k, v, g, beta, chunk=chunk))


def test_gated_delta_mixer_is_the_same_through_the_kernel(monkeypatch):
    """One gated-delta mixer at the release's head size (128; one key
    head serving two value heads, unrepeated): the loss and every
    parameter's gradient through the kernel pair equal those through the
    recurrence (the composition's ``A_log`` and ``dt_bias`` gradients are
    1 % off both here: the rounding in its ``dg``, as above)."""
    pt.seed(0)
    cfg = Q.Qwen3NextConfig(
        hidden_size=64, linear_num_key_heads=1, linear_num_value_heads=2,
        linear_key_head_dim=128, linear_value_head_dim=128)
    mixer = Q.Qwen3NextGatedDeltaNet(cfg)
    params = raw_params(mixer)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 150, 64)),
                    jnp.float32)

    def loss(params, x):
        return jnp.sum(jnp.sin(functional_call(mixer, params, x)))
    with monkeypatch.context() as m:
        m.setattr(IF, "gated_delta_rule", delta_recurrence)
        want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    calls = interpret_delta_kernel(monkeypatch)
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert calls == {"served": 1, "declined": 0}
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, a), (_, b) in zip(flat(got), flat(want)):
        assert rel_gap(a, b) < 1e-4, jax.tree_util.keystr(path)


def test_conv_matches_the_explicit_sum():
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(size=(2, 11, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    want = np.zeros((2, 11, 6), np.float32)
    for t in range(11):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w)[:, j] * np.asarray(u)[:, t - 3 + j]
    np.testing.assert_allclose(Q.depthwise_causal_conv(u, w), want,
                               atol=1e-5)
    np.testing.assert_allclose(ref.causal_conv(u[0], w), want[0], atol=1e-5)


# -- the expert layer ---------------------------------------------------------

H, E, K, F_, FS = 32, 16, 4, 24, 24


def make_block(held=None, seed=0, bias_to=None):
    """A ``DroplessMoE`` and its leaves at unit-scale logits; with
    ``bias_to`` the router's column of that expert is made so large that
    every token puts it first."""
    pt.seed(seed)
    layer = moe.DroplessMoE(H, E, K, F_, held=held, shared_width=FS)
    rng = np.random.default_rng(seed)
    leaves = {k: jnp.asarray(rng.normal(size=v.shape) / np.sqrt(v.shape[-2]),
                             jnp.float32)
              for k, v in sorted(raw_params(layer).items())}
    return layer, leaves


def share_of(leaves, first, count):
    return {k: (v[first:first + count] if k.startswith("experts.") else v)
            for k, v in leaves.items()}


def block_ref_config(first, count):
    return {"router_width": E, "first_expert": first, "num_experts": count,
            "num_experts_per_tok": K, "norm_topk_prob": True}


def ref_leaves(leaves):
    return {"mlp." + k: v for k, v in leaves.items()}


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(5).normal(size=(3, 20, H)),
                       jnp.float32)


def test_the_shares_add_up_to_the_uncut_block(tokens):
    """The share test of the model-configs guide: the routed parts that
    the four ranks of a 4-way expert-parallel layer compute (``held =
    (4r, 4)``), added to the shared expert counted once, are the whole
    block, the program's (``held`` all) and the plain reference's."""
    whole, leaves = make_block()
    full = functional_call(whole, leaves, tokens)
    flat = tokens.reshape(-1, H)
    want = ref.sparse_block(flat, ref_leaves(leaves),
                            block_ref_config(0, E), F32).reshape(tokens.shape)
    np.testing.assert_allclose(full, want, atol=2e-5)

    # what every rank computes alike: the block with its routed part nought
    shared_only = ref.sparse_block(
        flat, ref_leaves(dict(leaves, **{"experts.down_proj": jnp.zeros_like(
            leaves["experts.down_proj"])})),
        block_ref_config(0, E), F32).reshape(tokens.shape)
    total = shared_only
    for r in range(4):
        part, _ = make_block(held=(4 * r, 4))
        out = functional_call(part, share_of(leaves, 4 * r, 4), tokens)
        # the rank's share against the reference given the same share
        np.testing.assert_allclose(out, ref.sparse_block(
            flat, ref_leaves(share_of(leaves, 4 * r, 4)),
            block_ref_config(4 * r, 4), F32).reshape(tokens.shape),
            atol=2e-5)
        total = total + (out - shared_only)
    np.testing.assert_allclose(total, full, atol=5e-5)


@pytest.mark.parametrize("held", [(0, 4), (8, 4), None])
def test_dropless_under_skew_forward_and_gradient(tokens, held):
    """A router biased so that one held expert takes every token: 60 rows
    on one expert where the mean is 60 x 4 / 16 = 15.  Nothing is dropped:
    the output and the gradient of every leaf (the router's and the
    starved experts' too) are those of the dense reference."""
    first, count = held or (0, E)
    layer, leaves = make_block(held=held)       # leaves of its own share
    leaves["gate.weight"] = leaves["gate.weight"].at[:, first + 1].set(0.0)
    # every token's logit at that expert is large whatever its sign
    x = tokens.at[..., 0].set(3.0)
    leaves["gate.weight"] = leaves["gate.weight"].at[0, first + 1].set(6.0)
    rc = block_ref_config(first, count)

    def program(p, x):
        return jnp.sum(jnp.square(functional_call(layer, p, x)))

    def reference(p, x):
        return jnp.sum(jnp.square(ref.sparse_block(
            x.reshape(-1, H), ref_leaves(p), rc, F32)))

    functional_call(layer, leaves, x)
    assert int(layer.load["expert_rows_max"]) == 60
    got, (gp, gx) = jax.value_and_grad(program, argnums=(0, 1))(leaves, x)
    want, (wp, wx) = jax.value_and_grad(reference, argnums=(0, 1))(leaves, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(gx, wx, atol=1e-4 * float(jnp.max(jnp.abs(wx))))
    for k in wp:
        np.testing.assert_allclose(
            gp[k], wp[k], atol=1e-4 * float(jnp.max(jnp.abs(wp[k]))),
            err_msg=k)


def test_the_work_follows_the_live_rows_block_by_block(tokens):
    """``held_experts`` walks ``ceil(live / block)`` blocks: with blocks of
    8 rows the 60 tokens' assignments take many passes and give what one
    pass over the whole worst case gives, forward and backward."""
    layer, leaves = make_block(held=(0, 8))
    flat = tokens.reshape(-1, H)
    n = flat.shape[0]
    assert moe.row_block(n * K, n * K * 8 / E) == 184   # 1.5 x 120, on 8s

    def run(block, x, ws):
        with nn.layer._swapped_params(layer, leaves):
            top, idx = layer.route(x)
        key = jnp.where(idx.reshape(-1) < 8, idx.reshape(-1), 8)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=9)[:8].astype(jnp.int32)
        order = jnp.pad(order, (0, -order.shape[0] % block))
        out = moe.held_experts(block, x, (order // K).astype(jnp.int32),
                               top.reshape(-1)[order], sizes, *ws)
        return jnp.sum(jnp.square(out)), sizes

    ws = tuple(leaves["experts." + k]
               for k in ("gate_proj", "up_proj", "down_proj"))
    (one, sizes), g_one = jax.value_and_grad(
        lambda x, ws: run(n * K, x, ws), argnums=(0, 1), has_aux=True)(
        flat, ws)
    assert 8 < int(jnp.sum(sizes)) < n * K
    (many, _), g_many = jax.value_and_grad(
        lambda x, ws: run(8, x, ws), argnums=(0, 1), has_aux=True)(flat, ws)
    np.testing.assert_allclose(many, one, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_many), jax.tree.leaves(g_one)):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(
            jnp.max(jnp.abs(b))))


def test_rows_past_the_groups_reach_no_product(tokens, monkeypatch):
    """XLA's grouped kernel on the chip leaves the rows past
    ``sum(group_sizes)`` as the buffer held them, forward and in the
    gradient with respect to its rows (seen on the chip in PR 34: NaN in
    every gradient on a build that masked only the block's two ends).  A
    stand-in that writes NaN there, and that turns NaN wherever such a row
    of an OPERAND is not finite (a kernel that masks by multiplying would),
    must change nothing: every operand of every product is clean."""
    real = jax.lax.ragged_dot

    def dirty(a, live):
        """0 where the rows past the groups are finite, else NaN."""
        return 0.0 * jnp.sum(jnp.where(live, 0.0, a.astype(jnp.float32)))

    def poisoned(x, w, sizes):
        live = (jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None]

        @jax.custom_vjp
        def f(x, w):
            return fwd(x, w)[0]

        def fwd(x, w):
            y = real(x, w, sizes) + dirty(x, live).astype(x.dtype)
            return jnp.where(live, y, jnp.nan), (x, w)

        def bwd(res, ct):
            x, w = res
            dx, dw = jax.vjp(lambda x, w: real(x, w, sizes), x, w)[1](
                jnp.where(live, ct, 0))
            bad = dirty(ct, live) + dirty(x, live)
            return (jnp.where(live, dx + bad.astype(dx.dtype), jnp.nan),
                    dw + bad.astype(dw.dtype))

        f.defvjp(fwd, bwd)
        return f(x, w)

    layer, leaves = make_block(held=(4, 8))

    def loss(p, x):
        return jnp.sum(jnp.square(functional_call(layer, p, x)))

    want, want_g = jax.value_and_grad(loss, argnums=(0, 1))(leaves, tokens)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got, got_g = jax.value_and_grad(loss, argnums=(0, 1))(leaves, tokens)
    assert 0 < int(layer.load["rows_held"]) < 60 * K    # there ARE such rows
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, atol=1e-5 * float(
            jnp.max(jnp.abs(b))))


def test_held_must_lie_in_the_router():
    with pytest.raises(ValueError, match="held"):
        moe.DroplessMoE(H, E, K, F_, held=(12, 8))


# -- the normal path ----------------------------------------------------------

def test_trains_through_amp_adamw_trainstep():
    """``amp.decorate(O2, bfloat16)`` + ``AdamW`` with global-norm clipping
    + ``jit.TrainStep``, the benchmark's runner's own build: stacked
    three-dimensional expert leaves, ``A_log`` / ``dt_bias`` (1-D leaves
    that are neither norm nor bias) and the conv kernel all get a float32
    master and move; one memorised batch's loss falls."""
    pt.seed(0)
    model = Q.qwen3_next("tiny", experts_held=(4, 8))
    opt = optimizer.AdamW(learning_rate=3e-3, weight_decay=0.1,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0),
                          parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    step = TrainStep(model, Q.causal_lm_loss, opt,
                     extra_metrics=Q.moe_load_metrics(model))
    state = step.init_state(seed=0)
    before = {k: np.asarray(v, np.float32)
              for k, v in state["opt"]["master"].items()}
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 48)),
                      jnp.int32)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    losses = []
    for _ in range(6):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses
    for name in ("model.layers.0.mlp.experts.down_proj",
                 "model.layers.1.linear_attn.A_log",
                 "model.layers.1.linear_attn.dt_bias",
                 "model.layers.2.linear_attn.conv1d.weight",
                 "model.layers.3.self_attn.k_norm.weight",
                 "model.layers.3.mlp.shared_expert_gate.weight"):
        master = state["opt"]["master"][name]
        assert master.dtype == jnp.float32, name
        assert state["params"][name].dtype == jnp.bfloat16, name
        assert np.abs(np.asarray(master) - before[name]).max() > 0, name
    # the routing counts a caller asked for: 96 tokens x 4 choices x 4
    # layers, of which the 8 held of 16 experts take about half
    rows = int(metrics["moe.rows_held"])
    assert 0 < rows < 96 * 4 * 4
    assert float(metrics["moe.expert_rows_max"]) \
        >= float(metrics["moe.expert_rows_mean"])
    obs.enable()
    try:
        Q.record_moe_load(metrics)
        snap = obs.get_registry().snapshot()
    finally:
        obs.disable()
    assert snap["moe.rows_held"] == rows
    assert snap["moe.expert_rows_max"]["count"] == 1


def test_regions_and_scopes_reach_the_compiled_step():
    """Each of the eight region names in the compiled TrainStep, and the
    model's three scopes under the regions they belong to, forward and
    backward (``tests/test_trace_spans.py`` holds the other families to
    the same vocabulary; this one has no serving step to hold)."""
    import re

    from paddle_tpu.observability.regions import REGIONS

    name = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        pt.seed(0)
        model = Q.qwen3_next("tiny")
        opt = optimizer.AdamW(learning_rate=1e-3, weight_decay=0.1,
                              grad_clip=nn.ClipGradByGlobalNorm(1.0),
                              parameters=model.parameters())
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
        step = TrainStep(model, Q.causal_lm_loss, opt)
        ids = jnp.zeros((1, 16), jnp.int32)
        text = step.lower(step.init_state(seed=0), {
            "input_ids": ids, "labels": ids}).compile().as_text()
    finally:
        jax.config.update(name, was)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    parts = {re.sub(r"^(?:(?:jvp|transpose|vmap)\()+|\)+$", "", part)
             for path in paths for part in path.split("/")}
    assert set(REGIONS) <= parts
    for scope, region in (("gated_delta_rule", "attn_core"),
                          ("moe_router", "mlp"), ("moe_experts", "mlp")):
        mine = [p for p in paths if f"/{scope}/" in p + "/"]
        assert mine, scope
        assert all(f"/{region}/" in p.split(scope)[0] for p in mine), scope
        assert any(p.startswith("jit(_step)/transpose(") for p in mine), scope


def test_fused_adamw_takes_the_stacked_expert_leaves():
    """``(32, 2048, 512)`` and ``(32, 512, 2048)`` with a bfloat16 gradient
    and copy: the leading axis folds into the rows (a bitcast: 2048 and 512
    are whole 16-row tiles), blocks of (256, 512) / (128, 1024)."""
    from paddle_tpu.ops.pallas import fused_adamw as fa

    for shape, view, block in (((32, 2048, 512), (65536, 512), (256, 512)),
                               ((32, 512, 2048), (16384, 2048), (128, 1024))):
        p = jax.ShapeDtypeStruct(shape, jnp.float32)
        g = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        assert fa.eligible(p, g, jnp.bfloat16)
        assert fa._view(shape, 16) == view
        assert fa._block(*view, 16) == block
    # what stays with XLA's composition: the conv kernel, the ba
    # projection, the shared expert's gate, the 1-D leaves
    for shape in ((8192, 4), (2048, 64), (2048, 1), (32,)):
        assert not fa.eligible(jax.ShapeDtypeStruct(shape, jnp.float32),
                               jax.ShapeDtypeStruct(shape, jnp.bfloat16),
                               jnp.bfloat16)


def test_engine_refuses_the_model_by_name(model):
    with pytest.raises(NotImplementedError) as e:
        serving.Engine(model, max_batch=2, max_seq_len=64, num_blocks=16)
    text = str(e.value)
    assert "Qwen3NextForCausalLM" in text
    assert "state per slot" in text and "supports_paged" in text
    assert "DroplessMoE" in text


def test_generate_recomputes_the_prefix(model, leaves):
    """No cache of any kind yet: ``generate()`` takes the full-recompute
    path and its greedy tokens are the arg-max of the uncached forward."""
    with nn.layer._swapped_params(model, leaves):
        prompt = jnp.asarray([[5, 9, 200, 17]], jnp.int32)
        out = model.generate(prompt, max_new_tokens=3, temperature=0.0)
        seq = np.asarray(out)[0]
        for t in range(4, 7):
            logits = model(jnp.asarray(seq[None, :t]))
            assert int(jnp.argmax(logits[0, -1])) == int(seq[t])
