"""Plain reference of the Llama family's decoder as Mistral-7B-v0.1
publishes it (arXiv:2310.06825; the Hugging Face implementation's
equations): RMSNorm, rotary positions on half-split pairs, grouped-query
causal attention, SwiGLU, untied output head.  float32, no kernels, no
cache, no batching; leaves carry the Hugging Face names.  The sliding
window is not modelled: no cell passes 4,096 positions, where it equals
full causal attention.  Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common


def layer_prefix(i: int) -> str:
    return f"model.layers.{i}."


def outer_names(cfg: dict) -> list:
    names = ["model.embed_tokens.weight", "model.norm.weight"]
    if not cfg["tie_word_embeddings"]:
        names.append("lm_head.weight")
    return names


def layer_shapes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg["head_dim"]
    nq, nk = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return {"input_layernorm.weight": (h,),
            "self_attn.q_proj.weight": (h, nq),
            "self_attn.k_proj.weight": (h, nk),
            "self_attn.v_proj.weight": (h, nk),
            "self_attn.o_proj.weight": (nq, h),
            "post_attention_layernorm.weight": (h,),
            "mlp.gate_proj.weight": (h, i),
            "mlp.up_proj.weight": (h, i),
            "mlp.down_proj.weight": (i, h)}


def param_shapes(cfg: dict, layers: int) -> dict:
    """Every leaf's shape (weights stored ``(in, out)``)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head.weight"] = (h, v)
    for i in range(layers):
        for k, s in layer_shapes(cfg).items():
            out[layer_prefix(i) + k] = s
    return out


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x ``(S, H, D)``; pairs dimension i with i + D/2."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = positions.astype(jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([f, f], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def embed(params, ids, cfg):
    return params["model.embed_tokens.weight"].astype(jnp.float32)[ids]


def layer(x, lp, cfg, prec):
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    s = x.shape[0]
    d, nh, nkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    pos = jnp.arange(s)
    a = rms_norm(x, lp["input_layernorm.weight"], cfg["rms_norm_eps"])
    q = prec.mm(a, lp["self_attn.q_proj.weight"]).reshape(s, nh, d)
    k = prec.mm(a, lp["self_attn.k_proj.weight"]).reshape(s, nkv, d)
    v = prec.mm(a, lp["self_attn.v_proj.weight"]).reshape(s, nkv, d)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    att = common.causal_attention(q, k, v)
    x = x + prec.mm(att, lp["self_attn.o_proj.weight"])

    def mlp(xb):
        a = rms_norm(xb, lp["post_attention_layernorm.weight"],
                     cfg["rms_norm_eps"])
        gate = prec.mm(a, lp["mlp.gate_proj.weight"])
        up = prec.mm(a, lp["mlp.up_proj.weight"])
        return xb + prec.mm(jax.nn.silu(gate) * up,
                            lp["mlp.down_proj.weight"])

    return mlp(x)


def head(x, params, cfg, prec):
    x = rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    if cfg["tie_word_embeddings"]:
        w = params["model.embed_tokens.weight"].astype(jnp.float32).T
    else:
        w = params["lm_head.weight"].astype(jnp.float32)
    return prec.mm(x, w)


def matmul_params(cfg: dict, layers: int) -> int:
    """Parameters that a token multiplies: the layers' matrices and the
    output head; the input embedding is a lookup."""
    per = sum(a * b for (a, b) in
              (s for s in layer_shapes(cfg).values() if len(s) == 2))
    return layers * per + cfg["hidden_size"] * cfg["vocab_size"]
