"""Plain reference of the GPT-3 decoder (Brown et al. 2020, section 2.1:
the GPT-2 architecture with pre-normalisation): learned absolute
positions, LayerNorm with biases, full multi-head causal attention from
one packed qkv projection, a 4h GELU (erf) feed-forward, output head tied
to the token embedding.  Every layer attends densely (the paper
alternates dense and locally banded layers; PaddleNLP's GPT-3 is dense).
float32, no kernels; leaves carry the program's names, weights stored
``(in, out)``.  Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common


def layer_prefix(i: int) -> str:
    return f"model.h.{i}."


def outer_names(cfg: dict) -> list:
    names = ["model.embed_tokens.weight", "model.embed_positions.weight",
             "model.ln_f.weight", "model.ln_f.bias"]
    if not cfg["tie_word_embeddings"]:
        names.append("lm_head.weight")
    return names


def layer_kind(cfg: dict, i: int) -> str:
    return "full"


def layer_shapes(cfg: dict, i: int) -> dict:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"ln_1.weight": (h,), "ln_1.bias": (h,),
            "attn.qkv_proj.weight": (h, 3 * h), "attn.qkv_proj.bias": (3 * h,),
            "attn.out_proj.weight": (h, h), "attn.out_proj.bias": (h,),
            "ln_2.weight": (h,), "ln_2.bias": (h,),
            "mlp.fc_in.weight": (h, f), "mlp.fc_in.bias": (f,),
            "mlp.fc_out.weight": (f, h), "mlp.fc_out.bias": (h,)}


def param_shapes(cfg: dict, layers: int) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h),
           "model.embed_positions.weight": (cfg["max_position_embeddings"], h),
           "model.ln_f.weight": (h,), "model.ln_f.bias": (h,)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head.weight"] = (h, v)
    for i in range(layers):
        for k, s in layer_shapes(cfg, i).items():
            out[layer_prefix(i) + k] = s
    return out


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def embed(params, ids, cfg):
    s = ids.shape[0]
    wte = params["model.embed_tokens.weight"].astype(jnp.float32)
    wpe = params["model.embed_positions.weight"].astype(jnp.float32)
    return wte[ids] + wpe[:s]


def layer(x, lp, cfg, prec, kind):
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    s = x.shape[0]
    nh, d, eps = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["layer_norm_eps"]
    a = layer_norm(x, lp["ln_1.weight"], lp["ln_1.bias"], eps)
    qkv = prec.mm(a, lp["attn.qkv_proj.weight"]) + lp["attn.qkv_proj.bias"]
    qkv = qkv.reshape(s, 3, nh, d)
    att = common.causal_attention(qkv[:, 0], qkv[:, 1], qkv[:, 2])
    x = x + prec.mm(att, lp["attn.out_proj.weight"]) + lp["attn.out_proj.bias"]

    def mlp(xb):
        a = layer_norm(xb, lp["ln_2.weight"], lp["ln_2.bias"], eps)
        f = prec.mm(a, lp["mlp.fc_in.weight"]) + lp["mlp.fc_in.bias"]
        f = jax.nn.gelu(f, approximate=False)
        return xb + prec.mm(f, lp["mlp.fc_out.weight"]) \
            + lp["mlp.fc_out.bias"]

    return mlp(x)


def head(x, params, cfg, prec):
    x = layer_norm(x, params["model.ln_f.weight"].astype(jnp.float32),
                   params["model.ln_f.bias"].astype(jnp.float32),
                   cfg["layer_norm_eps"])
    if cfg["tie_word_embeddings"]:
        w = params["model.embed_tokens.weight"].astype(jnp.float32).T
    else:
        w = params["lm_head.weight"].astype(jnp.float32)
    return prec.mm(x, w)


def matmul_params(cfg: dict, layers: int) -> int:
    return sum(a * b for i in range(layers)
               for (a, b) in (s for s in layer_shapes(cfg, i).values()
                              if len(s) == 2)) \
        + cfg["hidden_size"] * cfg["vocab_size"]
