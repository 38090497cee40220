"""Plain reference of Qwen3-Next-80B-A3B's decoder as its published
``config.json`` and the Hugging Face implementation's equations give it:
zero-centred RMSNorm, three gated-delta (Gated DeltaNet) layers to every
gated softmax-attention layer, and in every layer a sparse block of
routed experts beside a gated shared expert.  float32, no kernels, no
cache, no batching; leaves carry the Hugging Face names, the routed
experts of a layer stacked on a leading axis.  Imports nothing of the
program.

Departures from the published model, all three shared with the program:

- no multi-token-prediction module (the checkpoint has one; the catalog's
  ``config`` has no key for it);
- no auxiliary load-balancing loss (``config`` carries no coefficient);
- **the share**: ``cfg["num_experts"]`` routed experts are held here, the
  experts ``first_expert .. first_expert + num_experts - 1`` of the
  ``router_width`` that the router scores.  The router, its top-k and the
  renormalisation run over all ``router_width``; what the experts held
  elsewhere would have added is left out, and that partial result goes on
  to the next layer.  The vocabulary is the slice ``vocab_size``.  With
  ``num_experts == router_width`` the block is the published one.

The delta rule is the recurrence, position by position; the conv is the
explicit sum; every held expert runs on every token and the routing
weights (zero where an expert was not chosen) mask the sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common

HIGHEST = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 128        # positions of the recurrence under one checkpoint


def layer_prefix(i: int) -> str:
    return f"model.layers.{i}."


def is_full_attention(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def outer_names(cfg: dict) -> list:
    return ["model.embed_tokens.weight", "model.norm.weight",
            "lm_head.weight"]


def sparse_shapes(cfg: dict) -> dict:
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fs, n = cfg["shared_expert_intermediate_size"], cfg["num_experts"]
    return {"mlp.gate.weight": (h, cfg["router_width"]),
            "mlp.experts.gate_proj": (n, h, f),
            "mlp.experts.up_proj": (n, h, f),
            "mlp.experts.down_proj": (n, f, h),
            "mlp.shared_expert.gate_proj.weight": (h, fs),
            "mlp.shared_expert.up_proj.weight": (h, fs),
            "mlp.shared_expert.down_proj.weight": (fs, h),
            "mlp.shared_expert_gate.weight": (h, 1)}


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i``: its kind follows from its index."""
    h = cfg["hidden_size"]
    out = {"input_layernorm.weight": (h,),
           "post_attention_layernorm.weight": (h,)}
    if is_full_attention(cfg, i):
        d = cfg["head_dim"]
        nq, nk = cfg["num_attention_heads"] * d, \
            cfg["num_key_value_heads"] * d
        out.update({"self_attn.q_proj.weight": (h, 2 * nq),
                    "self_attn.k_proj.weight": (h, nk),
                    "self_attn.v_proj.weight": (h, nk),
                    "self_attn.o_proj.weight": (nq, h),
                    "self_attn.q_norm.weight": (d,),
                    "self_attn.k_norm.weight": (d,)})
    else:
        hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        out.update({
            "linear_attn.in_proj_qkvz.weight": (h, 2 * hk * dk + 2 * hv * dv),
            "linear_attn.in_proj_ba.weight": (h, 2 * hv),
            "linear_attn.conv1d.weight": (2 * hk * dk + hv * dv,
                                          cfg["linear_conv_kernel_dim"]),
            "linear_attn.A_log": (hv,),
            "linear_attn.dt_bias": (hv,),
            "linear_attn.norm.weight": (dv,),
            "linear_attn.out_proj.weight": (hv * dv, h)})
    out.update(sparse_shapes(cfg))
    return out


def param_shapes(cfg: dict, layers: int) -> dict:
    """Every leaf's shape (matrices stored ``(in, out)``)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,),
           "lm_head.weight": (h, v)}
    for i in range(layers):
        for k, s in layer_shapes(cfg, i).items():
            out[layer_prefix(i) + k] = s
    return out


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def norm(x, w, eps):
    """Zero-centred: the stored weight is the offset from 1."""
    return rms(x, eps) * (1.0 + w)


def rope_partial(x, positions, theta, rot: int):
    """x ``(S, H, D)``: rotary positions on the first ``rot`` dimensions
    (half-split pairs ``i``, ``i + rot / 2``), the rest unchanged."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    f = positions.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([f, f], axis=-1)[:, None, :]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return jnp.concatenate(
        [xr * jnp.cos(emb) + turned * jnp.sin(emb), rest], axis=-1)


def full_attention(a, lp, cfg, prec):
    s = a.shape[0]
    d, nh, nkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    eps = cfg["rms_norm_eps"]
    qg = prec.mm(a, lp["self_attn.q_proj.weight"]).reshape(s, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = prec.mm(a, lp["self_attn.k_proj.weight"]).reshape(s, nkv, d)
    v = prec.mm(a, lp["self_attn.v_proj.weight"]).reshape(s, nkv, d)
    q = norm(q, lp["self_attn.q_norm.weight"], eps)
    k = norm(k, lp["self_attn.k_norm.weight"], eps)
    rot = int(d * cfg["partial_rotary_factor"])
    pos = jnp.arange(s)
    q = rope_partial(q, pos, cfg["rope_theta"], rot)
    k = rope_partial(k, pos, cfg["rope_theta"], rot)
    att = common.causal_attention(q, k, v)                 # (S, H * D)
    return prec.mm(att * jax.nn.sigmoid(gate.reshape(s, nh * d)),
                   lp["self_attn.o_proj.weight"])


def causal_conv(u, w):
    """Depthwise causal conv over positions with zero history: ``u``
    ``(S, C)``, ``w`` ``(C, K)``; ``c_t = sum_j w[:, j] * u_{t-K+1+j}``."""
    kk = w.shape[1]
    up = jnp.pad(u, ((kk - 1, 0), (0, 0)))
    s = u.shape[0]
    return sum(up[j:j + s] * w[:, j][None, :] for j in range(kk))


def delta_recurrence(q, k, v, g, beta):
    """``q``, ``k`` ``(S, H, dk)``, ``v`` ``(S, H, dv)``, ``g``, ``beta``
    ``(S, H)``: the gated delta rule position by position, the state a
    ``(dk, dv)`` matrix a head from zero.  Nested in blocks of
    ``SCAN_BLOCK`` positions under ``jax.checkpoint``, so that the
    backward pass keeps one state a block and not one a position."""
    s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % SCAN_BLOCK

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, SCAN_BLOCK) + x.shape[1:])

    def position(state, x):
        q1, k1, v1, g1, b1 = x
        state = jnp.exp(g1)[:, None, None] * state
        m = jnp.einsum("hkv,hk->hv", state, k1, precision=HIGHEST)
        d = b1[:, None] * (v1 - m)
        state = state + k1[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q1, precision=HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(position, state, xs)

    # padded positions (k = beta = g = 0) leave the state as it is
    _, o = jax.lax.scan(block, jnp.zeros((h, dk, dv), jnp.float32),
                        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return o.reshape(-1, h, dv)[:s]


def gated_delta(a, lp, cfg, prec):
    s = a.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = hv // hk
    # per key head: [q dk | k dk | v r x dv | z r x dv], and [b r | a r]
    qkvz = prec.mm(a, lp["linear_attn.in_proj_qkvz.weight"]).reshape(
        s, hk, 2 * dk + 2 * r * dv)
    ba = prec.mm(a, lp["linear_attn.in_proj_ba.weight"]).reshape(s, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(s, hv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(s, hv, dv)
    b, al = ba[..., :r].reshape(s, hv), ba[..., r:].reshape(s, hv)
    u = jnp.concatenate([q.reshape(s, hk * dk), k.reshape(s, hk * dk),
                         v.reshape(s, hv * dv)], axis=-1)
    u = jax.nn.silu(causal_conv(u, lp["linear_attn.conv1d.weight"]))
    q = u[:, :hk * dk].reshape(s, hk, dk)
    k = u[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = u[:, 2 * hk * dk:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(lp["linear_attn.A_log"]) \
        * jax.nn.softplus(al + lp["linear_attn.dt_bias"])
    # key head j serves value heads r j .. r j + r - 1
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    o = delta_recurrence(q, k, v, g, beta)                 # (S, Hv, dv)
    # this one norm is not zero-centred
    o = rms(o, cfg["rms_norm_eps"]) * lp["linear_attn.norm.weight"] \
        * jax.nn.silu(z)
    return prec.mm(o.reshape(s, hv * dv), lp["linear_attn.out_proj.weight"])


def routing_weights(a, lp, cfg, prec):
    """``(S, router_width)``: each token's renormalised top-k
    probabilities at the experts it chose, zero elsewhere."""
    p = jax.nn.softmax(prec.mm(a, lp["mlp.gate.weight"]), axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(a.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, idx].set(top)


def sparse_block(a, lp, cfg, prec):
    def expert(x, wg, wu, wd):
        return prec.mm(jax.nn.silu(prec.mm(x, wg)) * prec.mm(x, wu), wd)

    first, n = cfg.get("first_expert", 0), cfg["num_experts"]
    w = routing_weights(a, lp, cfg, prec)[:, first:first + n]

    def add(acc, xs):
        wg, wu, wd, col = xs
        # the sum is no input of the checkpointed part: nothing of its
        # size is kept an expert
        return acc + jax.checkpoint(expert)(a, wg, wu, wd) * col[:, None], \
            None

    routed, _ = jax.lax.scan(
        add, jnp.zeros_like(a),
        (lp["mlp.experts.gate_proj"], lp["mlp.experts.up_proj"],
         lp["mlp.experts.down_proj"], w.T))
    shared = expert(a, lp["mlp.shared_expert.gate_proj.weight"],
                    lp["mlp.shared_expert.up_proj.weight"],
                    lp["mlp.shared_expert.down_proj.weight"])
    return routed + jax.nn.sigmoid(
        prec.mm(a, lp["mlp.shared_expert_gate.weight"])) * shared


def embed(params, ids, cfg):
    return params["model.embed_tokens.weight"].astype(jnp.float32)[ids]


def layer(x, lp, cfg, prec):
    """One layer over one sequence ``(S, h)``; its kind is told from the
    leaves it is given."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    eps = cfg["rms_norm_eps"]
    a = norm(x, lp["input_layernorm.weight"], eps)
    mixer = gated_delta if "linear_attn.A_log" in lp else full_attention
    x = x + mixer(a, lp, cfg, prec)
    return x + sparse_block(
        norm(x, lp["post_attention_layernorm.weight"], eps), lp, cfg, prec)


def head(x, params, cfg, prec):
    x = norm(x, params["model.norm.weight"].astype(jnp.float32),
             cfg["rms_norm_eps"])
    return prec.mm(x, params["lm_head.weight"].astype(jnp.float32))
