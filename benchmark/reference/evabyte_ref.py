"""Plain reference of EvaByte's decoder (EvaByte 6.5B, released 2025-01;
https://huggingface.co/EvaByte/EvaByte config.json): a byte-level
language model whose attention is EVA, chunked linearized attention
(Zheng, Yuan, Wang and Kong, "Efficient Attention via Control Variates",
ICLR 2023, arXiv:2302.04542), in its causal form with the local set = the
query's window.  float32, no kernels, no cache, no batching; leaves carry
the released names.  Imports nothing of the program.

Per layer, heads h = 1..H of size d, s = d^-1/2, window W, chunk c,
positions t = 0, 1, ...:

1. ``a = x * rsqrt(mean(x^2) + eps) * (1 + g_in)``; q, k, v = a W_q, a W_k,
   a W_v (no bias), split into heads; RoPE (half-split pairs, as
   ``llama_ref.rope``) at position t on q and k.
2. Chunk j holds positions cj .. cj+c-1.  Per head, with learned phi_h and
   mu_h: ``w_jm = softmax over the c positions m of chunk j of
   (s * phi_h . k_m)``; ``ktilde_j = sum_m w_jm k_m + mu_h``;
   ``vtilde_j = sum_m w_jm v_m``.
3. Query t lies in window w = t // W.  It sees the keys of its own window
   up to itself and one summary for every chunk of every earlier window,
   ``j < (W / c) w``, under ONE softmax; summaries of the query's own
   window are not seen.
4. ``x <- x + concat_h(o_t) W_o``; ``b = RMSNorm(x; 1 + g_post)``;
   ``x <- x + (silu(b W_gate) * (b W_up)) W_down`` (the adds in float32,
   as everything here is).
5. After the last layer ``RMSNorm(x; 1 + g)``, then ``lm_head``
   (hidden -> num_pred_heads x vocab); columns ``vocab i .. vocab i +
   vocab - 1`` are prediction head i, which predicts byte t + 1 + i.

What the published ``config.json`` does not say, and is assumed here and
in the program alike (the configuration file lists the same items under
``assumed``; the builder knows of no departure of the released code from
any of them and made none from the issue that asked for this file):

(a) the summary weights are ``softmax(s * phi . k)`` within a chunk,
    ``ktilde = weighted keys + mu``, ``vtilde = weighted values``;
(b) RoPE at token positions on q and k, before summarising;
(c) summaries are visible only from later windows; windows are aligned at
    multiples of ``window_size``;
(d) prediction head i = columns ``vocab i ..`` of ``lm_head``;
(e) ``mixedp_attn`` read as "bfloat16 operands, float32 scores and
    softmax" (here: float32 throughout);
(f) ``initializer_range`` 0.02 for the seed's weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common
from .llama_ref import rope

HIGHEST = common.HIGHEST
MLP_ROWS = 1024       # rows of the MLP held at once (memory, not numerics)


def layer_prefix(i: int) -> str:
    return f"model.layers.{i}."


def outer_names(cfg: dict) -> list:
    return ["model.embed_tokens.weight", "model.norm.weight",
            "lm_head.weight"]


def layer_shapes(cfg: dict) -> dict:
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    return {"input_layernorm.weight": (h,),
            "self_attn.q_proj.weight": (h, nh * d),
            "self_attn.k_proj.weight": (h, nh * d),
            "self_attn.v_proj.weight": (h, nh * d),
            "self_attn.o_proj.weight": (nh * d, h),
            "self_attn.adaptive_phi": (nh, d),
            "self_attn.adaptive_mu_k": (nh, d),
            "post_attention_layernorm.weight": (h,),
            "mlp.gate_proj.weight": (h, i),
            "mlp.up_proj.weight": (h, i),
            "mlp.down_proj.weight": (i, h)}


def param_shapes(cfg: dict, layers: int) -> dict:
    """Every leaf's shape (weights stored ``(in, out)``)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,),
           "lm_head.weight": (h, cfg["num_pred_heads"] * v)}
    for i in range(layers):
        for k, s in layer_shapes(cfg).items():
            out[layer_prefix(i) + k] = s
    return out


def rms_norm(x, g, eps):
    """``norm_add_unit_offset``: the stored leaf is ``g``, the scale 1 + g."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def chunk_summaries(k, v, phi, mu, chunk: int):
    """k, v ``(S, H, D)`` with S a multiple of ``chunk``; phi, mu
    ``(H, D)``.  Step 2: ``(ktilde, vtilde)``, each ``(S / chunk, H, D)``."""
    s, h, d = k.shape
    kc = k.reshape(s // chunk, chunk, h, d)
    vc = v.reshape(s // chunk, chunk, h, d)
    sc = jnp.einsum("hd,jmhd->jmh", phi, kc, precision=HIGHEST) * d ** -0.5
    w = jax.nn.softmax(sc, axis=1)
    kt = jnp.einsum("jmh,jmhd->jhd", w, kc, precision=HIGHEST) + mu[None]
    vt = jnp.einsum("jmh,jmhd->jhd", w, vc, precision=HIGHEST)
    return kt, vt


def eva_attention(q, k, v, phi, mu, window: int, chunk: int):
    """Steps 2 and 3 for one sequence: q, k, v ``(S, H, D)`` after RoPE.
    One head and one window at a time, recomputed in a backward pass, so
    that only one ``W x (W + S / c)`` block of scores lives at once.
    Returns ``(S, H * D)``."""
    s, h, d = q.shape
    nw = -(-s // window)
    pad = nw * window - s
    if pad:
        # the padding lies in the last window: its keys are behind every
        # real query's causal mask and its chunks are summarised for
        # windows that do not exist
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    kt, vt = chunk_summaries(k, v, phi, mu, chunk)
    per_window = window // chunk
    nc = kt.shape[0]
    scale = d ** -0.5
    causal = jnp.tril(jnp.ones((window, window), bool))

    @jax.checkpoint
    def one_head(args):
        q1, k1, v1, kt1, vt1 = args       # (nw*W, D) x3, (nc, D) x2

        def one_window(w):
            qs = jax.lax.dynamic_slice_in_dim(q1, w * window, window)
            ks = jax.lax.dynamic_slice_in_dim(k1, w * window, window)
            vs = jax.lax.dynamic_slice_in_dim(v1, w * window, window)
            sc_w = jnp.einsum("qd,kd->qk", qs, ks, precision=HIGHEST) * scale
            sc_w = jnp.where(causal, sc_w, -jnp.inf)
            sc_s = jnp.einsum("qd,jd->qj", qs, kt1, precision=HIGHEST) * scale
            seen = jnp.arange(nc)[None, :] < per_window * w
            sc_s = jnp.where(seen, sc_s, -jnp.inf)
            p = jax.nn.softmax(jnp.concatenate([sc_w, sc_s], axis=1), axis=1)
            return jnp.einsum("qk,kd->qd", p[:, :window], vs,
                              precision=HIGHEST) \
                + jnp.einsum("qj,jd->qd", p[:, window:], vt1,
                             precision=HIGHEST)

        out = jax.lax.map(jax.checkpoint(one_window), jnp.arange(nw))
        return out.reshape(nw * window, d)

    heads_first = lambda a: a.transpose(1, 0, 2)
    out = jax.lax.map(one_head, tuple(heads_first(a)
                                      for a in (q, k, v, kt, vt)))
    return out.transpose(1, 0, 2)[:s].reshape(s, h * d)


def embed(params, ids, cfg):
    return params["model.embed_tokens.weight"].astype(jnp.float32)[ids]


def layer(x, lp, cfg, prec):
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    s = x.shape[0]
    d, nh = cfg["head_dim"], cfg["num_attention_heads"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    a = rms_norm(x, lp["input_layernorm.weight"], eps)
    q = prec.mm(a, lp["self_attn.q_proj.weight"]).reshape(s, nh, d)
    k = prec.mm(a, lp["self_attn.k_proj.weight"]).reshape(s, nh, d)
    v = prec.mm(a, lp["self_attn.v_proj.weight"]).reshape(s, nh, d)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    att = eva_attention(q, k, v, lp["self_attn.adaptive_phi"],
                        lp["self_attn.adaptive_mu_k"], cfg["window_size"],
                        cfg["chunk_size"])
    x = x + prec.mm(att, lp["self_attn.o_proj.weight"])

    def mlp(xb):
        b = rms_norm(xb, lp["post_attention_layernorm.weight"], eps)
        gate = prec.mm(b, lp["mlp.gate_proj.weight"])
        up = prec.mm(b, lp["mlp.up_proj.weight"])
        return xb + prec.mm(jax.nn.silu(gate) * up,
                            lp["mlp.down_proj.weight"])

    if s > MLP_ROWS and s % MLP_ROWS == 0 and prec.mode == "f32":
        # rows are independent: some at a time, so that the (S, ffn)
        # intermediates of a 17,408-position sequence need not live whole
        return jax.lax.map(mlp, x.reshape(-1, MLP_ROWS, x.shape[1])
                           ).reshape(x.shape)
    return mlp(x)


def all_heads(x, params, cfg, prec):
    """Step 5: ``(S, num_pred_heads, vocab)`` logits."""
    x = rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                 cfg["rms_norm_eps"])
    lg = prec.mm(x, params["lm_head.weight"].astype(jnp.float32))
    return lg.reshape(x.shape[0], cfg["num_pred_heads"], cfg["vocab_size"])


def head(x, params, cfg, prec):
    """Head 0's logits ``(S, vocab)``: what plain decoding samples from."""
    return all_heads(x, params, cfg, prec)[:, 0]
