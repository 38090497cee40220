"""Mixture-of-Experts with expert parallelism (``ep`` mesh axis).

Reference surface (SURVEY.md §2.5): MoELayer + gates
(python/paddle/incubate/distributed/models/moe/moe_layer.py,
gate/gshard_gate.py, gate/switch_gate.py, gate/naive_gate.py), capacity +
token dropping via the fused CUDA helper ops (number_count,
limit_by_capacity, prune_gate_by_capacity, random_routing), grouped NCCL
all-to-all dispatch/combine, and the expert-aware grad clip
(moe/grad_clip.py).

TPU redesign: the reference routes tokens with scatter/gather CUDA kernels
and explicit alltoall calls.  Here routing is the GShard einsum
formulation — dense one-hot dispatch/combine tensors contracted on the MXU
— and expert placement is a sharding annotation: expert parameters are
stacked on a leading expert axis sharded over ``ep``, the dispatched
activations [E, C, H] carry the same constraint, and XLA emits the
all-to-all exchange.  The helper ops become one-liners on cumsums
(number_count/limit_by_capacity below) instead of kernels.

Capacity semantics match the reference: each expert processes at most
``capacity_factor * tokens / num_experts`` tokens; overflow tokens are
dropped (their combine weight is zero, so they pass through the residual
path of the surrounding block).

Grad-clip note: expert params are global sharded arrays under GSPMD, so
``ClipGradByGlobalNorm`` already reduces their squared norms globally —
the reference's special expert-aware clip exists only because its expert
params are process-local.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import random as prandom
from ..nn.layer import Layer, ParamMeta
from .mp_layers import constrain as _constrain

_SEP = "__"


# ---------------------------------------------------------------------------
# helper "ops" (reference: fused CUDA kernels, here cumsum one-liners)
# ---------------------------------------------------------------------------

def number_count(gate_idx, upper_range):
    """Tokens routed to each expert (reference: number_count op)."""
    return jnp.sum(jax.nn.one_hot(gate_idx, upper_range, dtype=jnp.int32),
                   axis=0)


def limit_by_capacity(expert_mask, capacity):
    """Zero mask entries beyond each expert's capacity, preserving token
    order (reference: limit_by_capacity + prune_gate_by_capacity ops).
    ``expert_mask``: [N, E] one-hot; returns (kept_mask, position_in_expert).
    """
    pos = jnp.cumsum(expert_mask, axis=0) * expert_mask - expert_mask
    kept = expert_mask * (pos < capacity)
    return kept, pos


# ---------------------------------------------------------------------------
# gates
# ---------------------------------------------------------------------------

class NaiveGate(Layer):
    """Linear router returning (combine_weights, dispatch_mask, aux_loss).

    Subclasses implement ``route(probs, capacity)``.
    """

    top_k = 2

    def __init__(self, d_model: int, num_experts: int,
                 capacity_factor: float = 1.25,
                 eval_capacity_factor: Optional[float] = None):
        # eval_capacity_factor None (default) → dropless eval routing; set
        # it explicitly to cap eval capacity like training
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor  # None = dropless
        self.weight = self.create_parameter(
            (d_model, num_experts),
            default_initializer=lambda k, s, d: jax.random.uniform(
                k, s, d, -1 / math.sqrt(d_model), 1 / math.sqrt(d_model)))

    def capacity(self, num_tokens: int) -> int:
        if not self.training and self.eval_capacity_factor is None:
            # eval default: DROPLESS routing. Inference must not drop
            # tokens, and — critically for KV-cache serving — capacity from
            # the per-call token count would make a one-token decode step
            # route differently from the full-prefix recompute it must
            # reproduce (the generate() greedy-identity contract).
            return num_tokens
        f = self.capacity_factor if self.training else self.eval_capacity_factor
        return max(int(f * num_tokens * self.top_k / self.num_experts), 4)

    def forward(self, x):
        """x: [N, H] tokens → (combine [N,E,C], dispatch [N,E,C] bool, aux)."""
        logits = (x.astype(jnp.float32) @
                  self.weight.astype(jnp.float32))        # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        return self.route(probs, self.capacity(x.shape[0]))

    def route(self, probs, capacity):
        raise NotImplementedError


class SwitchGate(NaiveGate):
    """Top-1 routing (Switch Transformer; reference: switch_gate.py)."""

    top_k = 1

    def route(self, probs, capacity):
        E = self.num_experts
        idx1 = jnp.argmax(probs, axis=-1)                 # [N]
        mask1 = jax.nn.one_hot(idx1, E, dtype=probs.dtype)
        # load-balancing aux loss (mean prob × mean assignment, scaled by E)
        aux = E * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(mask1, axis=0))
        kept1, pos1 = limit_by_capacity(mask1, capacity)
        gate1 = jnp.sum(probs * kept1, axis=-1)           # [N]
        loc1 = jax.nn.one_hot(jnp.sum(pos1 * mask1, axis=-1).astype(jnp.int32),
                              capacity, dtype=probs.dtype)  # [N, C]
        combine = gate1[:, None, None] * kept1[:, :, None] * loc1[:, None, :]
        return combine, combine > 0, aux


class GShardGate(NaiveGate):
    """Top-2 routing with random second-expert admission (gshard_gate.py)."""

    top_k = 2

    def __init__(self, *args, random_routing: bool = True, **kwargs):
        super().__init__(*args, **kwargs)
        self.random_routing = random_routing

    def route(self, probs, capacity):
        E = self.num_experts
        idx1 = jnp.argmax(probs, axis=-1)
        mask1 = jax.nn.one_hot(idx1, E, dtype=probs.dtype)
        probs_wo1 = probs * (1 - mask1)
        idx2 = jnp.argmax(probs_wo1, axis=-1)
        mask2 = jax.nn.one_hot(idx2, E, dtype=probs.dtype)

        aux = E * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(mask1, axis=0))

        gate1 = jnp.sum(probs * mask1, axis=-1)
        gate2 = jnp.sum(probs * mask2, axis=-1)
        if self.random_routing and self.training:
            # admit the 2nd expert with prob 2*gate2 (GShard §3.2): biases
            # traffic toward confident second choices
            u = jax.random.uniform(prandom.next_key("moe_gate"),
                                   gate2.shape, gate2.dtype)
            mask2 = mask2 * (u < 2.0 * gate2).astype(mask2.dtype)[:, None]

        kept1, pos1 = limit_by_capacity(mask1, capacity)
        # 2nd-choice tokens queue behind ALL 1st-choice tokens per expert
        pos2_base = jnp.sum(mask1, axis=0, keepdims=True)
        pos2 = (jnp.cumsum(mask2, axis=0) - mask2) * mask2 + pos2_base * mask2
        kept2 = mask2 * (pos2 < capacity)

        gate1 = jnp.sum(probs * kept1, axis=-1)
        gate2 = jnp.sum(probs * kept2, axis=-1)
        denom = jnp.maximum(gate1 + gate2, 1e-9)
        gate1, gate2 = gate1 / denom, gate2 / denom

        def _combine(gate, kept, pos, mask):
            loc = jax.nn.one_hot(
                jnp.sum(pos * mask, axis=-1).astype(jnp.int32), capacity,
                dtype=probs.dtype)
            return gate[:, None, None] * kept[:, :, None] * loc[:, None, :]

        combine = (_combine(gate1, kept1, pos1, mask1) +
                   _combine(gate2, kept2, pos2, mask2))
        return combine, combine > 0, aux


GATES = {"naive": SwitchGate, "switch": SwitchGate, "gshard": GShardGate}


# ---------------------------------------------------------------------------
# MoE layer
# ---------------------------------------------------------------------------

class MoELayer(Layer):
    """Expert-parallel MoE (reference: moe_layer.py MoELayer).

    ``experts`` is a factory building one expert Layer (any [..., H] →
    [..., H] module); ``num_experts`` instances are built with independent
    init and their parameters stacked on a leading expert axis sharded over
    ``ep``.

    Aux-loss contract (jax-native — NO global side channel, it would leak
    tracers across checkpoint/scan/vmap boundaries): after ``forward``
    returns, ``self.aux_loss`` holds the load-balancing loss of THAT call.
    It is valid only at the same trace level, i.e. read it immediately
    after calling the layer (as MixtralDecoderLayer does) and thread it
    outward through your function's outputs.  ``moe_group`` and
    ``recompute_interval`` are accepted for reference-signature parity; the
    expert group is the mesh's ``ep`` axis and recompute is the enclosing
    block's concern.
    """

    def __init__(self, d_model: int, expert: Callable[[], Layer],
                 num_experts: int, gate="gshard", top_k: Optional[int] = None,
                 capacity_factor: float = 1.25, moe_group=None,
                 recompute_interval: int = 0):
        super().__init__()
        self.num_experts = num_experts
        if isinstance(gate, str):
            self.gate = GATES[gate](d_model, num_experts,
                                    capacity_factor=capacity_factor)
        else:
            self.gate = gate
        if top_k is not None and top_k != self.gate.top_k:
            raise ValueError(
                f"top_k={top_k} conflicts with gate {type(self.gate).__name__}"
                f" (top_k={self.gate.top_k}); pick gate='switch' for top-1 "
                "or gate='gshard' for top-2")
        instances = [expert() for _ in range(num_experts)]
        object.__setattr__(self, "template", instances[0])
        per_exp = [dict(inst.named_parameters()) for inst in instances]
        metas = instances[0].param_meta()
        self._param_names = list(per_exp[0].keys())
        for name in self._param_names:
            first = per_exp[0][name]
            if isinstance(first, jax.ShapeDtypeStruct):
                # nn.meta_init() construction (deviceless memory proofs):
                # stack abstractly — jnp.stack rejects ShapeDtypeStructs
                stacked = jax.ShapeDtypeStruct(
                    (num_experts,) + tuple(first.shape), first.dtype)
            else:
                stacked = jnp.stack([pe[name] for pe in per_exp], axis=0)
            meta = metas.get(name, ParamMeta())
            base = list(meta.partition) if meta.partition is not None else []
            base += [None] * (stacked.ndim - 1 - len(base))
            self._register_parameter(
                name.replace(".", _SEP), stacked,
                ParamMeta(trainable=meta.trainable,
                          partition=P("ep", *base), is_bias=meta.is_bias))
        self.aux_loss = 0.0

    def _extra_mode_layers(self):
        # train()/eval() must reach the expert template even though it is
        # outside the sublayer registry (its params are superseded by the
        # stacked arrays)
        return (self.template,)

    def stacked_params(self):
        return {n: getattr(self, n.replace(".", _SEP))
                for n in self._param_names}

    def forward(self, x):
        """x: [..., H] → [..., H]; routing over the flattened token dim."""
        from ..nn.layer import _swapped_params
        shape = x.shape
        H = shape[-1]
        tokens = x.reshape(-1, H)                       # [N, H]
        combine, dispatch, aux = self.gate(tokens)      # [N,E,C] ×2, scalar
        self.aux_loss = aux  # same-trace readback only (see class docstring)

        # dispatch: [E, C, H] — expert-sharded; XLA emits the all-to-all
        expert_in = jnp.einsum("nec,nh->ech",
                               dispatch.astype(x.dtype), tokens)
        expert_in = _constrain(expert_in, "ep")

        params = self.stacked_params()

        def one_expert(p, h):
            with _swapped_params(self.template, p):
                return self.template(h)

        expert_out = jax.vmap(one_expert)(params, expert_in)   # [E, C, H]
        expert_out = _constrain(expert_out, "ep")

        out = jnp.einsum("ech,nec->nh", expert_out,
                         combine.astype(x.dtype))
        return out.reshape(shape)


# ---------------------------------------------------------------------------
# routing as data: the experts held here, no capacity, no dropped token
# ---------------------------------------------------------------------------

def row_block(worst: int, expected: float) -> int:
    """Rows of one pass of :func:`held_experts`: one and a half times the
    expected number of live rows on whole sublane tiles, at most the
    worst case."""
    return min(worst, max(8, -(-int(1.5 * expected) // 8) * 8))


def _experts_block(rows, wts, sizes, w_gate, w_up, w_down):
    """One block of sorted assignments through the held experts: ``rows``
    ``(block, h)`` the tokens' rows, ``sizes`` the rows of each held
    expert that lie in this block.  Rows past their sum belong to no
    expert: ``grouped_matmul`` returns zeros there, and the gradient that
    comes back for them (which the grouped kernel leaves undefined) is
    cut by the select on ``rows``."""
    from ..incubate.nn.functional import grouped_matmul, swiglu
    live = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
    rows = jnp.where(live, rows, jnp.zeros((), rows.dtype))
    mid = swiglu(grouped_matmul(rows, w_gate, sizes),
                 grouped_matmul(rows, w_up, sizes))
    return grouped_matmul(mid, w_down, sizes).astype(jnp.float32) \
        * wts[:, None].astype(jnp.float32)


def _blocks_of(tok, sizes, block):
    """(number of blocks that hold a live row, ``i -> (first row, the
    block's tokens, each held expert's rows inside the block)``)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    def at(i):
        r0 = i * block
        return r0, jax.lax.dynamic_slice_in_dim(tok, r0, block), (
            jnp.clip(ends, r0, r0 + block)
            - jnp.clip(starts, r0, r0 + block)).astype(jnp.int32)

    return (ends[-1] + block - 1) // block, at


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def held_experts(block, x, tok, wts, sizes, w_gate, w_up, w_down):
    """``sum over a token's held experts of w_e E_e(x)``, the work
    following the live rows.

    ``x`` ``(N, h)``; ``tok`` ``(M,)`` the token of each assignment and
    ``wts`` ``(M,)`` its weight, the assignments sorted by held expert
    with the ones whose expert lies elsewhere last; ``sizes`` ``(count,)``
    the rows of each held expert; the stacked leaves ``(count, h, f)``,
    ``(count, h, f)``, ``(count, f, h)``.  ``M`` is the worst case (every
    token choosing ``min(top_k, count)`` held experts, a multiple of
    ``block``), so nothing is ever dropped, and only index arrays are
    that long: the rows are gathered, multiplied and added back
    ``block`` assignments at a time in a loop whose trip count,
    ``ceil(live rows / block)``, is data.  The backward pass walks the
    same blocks and recomputes each, so no buffer outlives its block."""
    return _held_experts_fwd(block, x, tok, wts, sizes, w_gate, w_up,
                             w_down)[0]


def _held_experts_fwd(block, x, tok, wts, sizes, w_gate, w_up, w_down):
    n_blocks, at = _blocks_of(tok, sizes, block)

    def body(i, acc):
        r0, tok_b, sizes_b = at(i)
        y = _experts_block(x[tok_b],
                           jax.lax.dynamic_slice_in_dim(wts, r0, block),
                           sizes_b, w_gate, w_up, w_down)
        # ten rows a token at the most add up in float32
        return acc.at[tok_b].add(y)

    out = jax.lax.fori_loop(0, n_blocks, body,
                            jnp.zeros(x.shape, jnp.float32))
    return out.astype(x.dtype), (x, tok, wts, sizes, w_gate, w_up, w_down)


def _held_experts_bwd(block, res, ct):
    x, tok, wts, sizes, w_gate, w_up, w_down = res
    n_blocks, at = _blocks_of(tok, sizes, block)
    ct = ct.astype(jnp.float32)

    def body(i, carry):
        dx, dwts, dws = carry
        r0, tok_b, sizes_b = at(i)
        _, vjp = jax.vjp(
            lambda rows, wts_b, *ws: _experts_block(rows, wts_b, sizes_b,
                                                    *ws),
            x[tok_b], jax.lax.dynamic_slice_in_dim(wts, r0, block),
            w_gate, w_up, w_down)
        d_rows, d_wts, *d_ws = vjp(ct[tok_b])
        return (dx.at[tok_b].add(d_rows.astype(jnp.float32)),
                jax.lax.dynamic_update_slice_in_dim(dwts, d_wts, r0, 0),
                tuple(a + d.astype(jnp.float32) for a, d in zip(dws, d_ws)))

    dx, dwts, dws = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(wts),
         tuple(jnp.zeros(w.shape, jnp.float32)
               for w in (w_gate, w_up, w_down))))
    return (dx.astype(x.dtype), None, dwts, None) + tuple(
        d.astype(w.dtype) for d, w in zip(dws, (w_gate, w_up, w_down)))


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


class _ExpertStack(Layer):
    """The held experts' leaves, stacked on a leading axis."""

    def __init__(self, count, d_model, width, weight_attr):
        super().__init__()
        for name, shape in (("gate_proj", (count, d_model, width)),
                            ("up_proj", (count, d_model, width)),
                            ("down_proj", (count, width, d_model))):
            setattr(self, name, self.create_parameter(shape, attr=weight_attr))


class _GatedMLP(Layer):
    def __init__(self, d_model, width, weight_attr):
        super().__init__()
        from ..nn.layers_common import Linear
        self.gate_proj = Linear(d_model, width, weight_attr, bias_attr=False)
        self.up_proj = Linear(d_model, width, weight_attr, bias_attr=False)
        self.down_proj = Linear(width, d_model, weight_attr, bias_attr=False)

    def forward(self, x):
        from ..incubate.nn.functional import swiglu
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class DroplessMoE(Layer):
    """A sparse expert block whose routing is data: softmax over all
    ``num_experts``, the ``top_k`` largest (renormalised to sum 1 under
    ``norm_topk_prob``), every chosen expert computed for every token
    that chose it, whatever the imbalance: no capacity, no dropped token.

    ``held = (first, count)`` says which of the ``num_experts`` experts
    this rank holds (expert parallelism: rank ``r`` of ``R`` holds
    ``(r * num_experts // R, num_experts // R)``); the default holds them
    all.  The router keeps its full width and its ``top_k``; the
    assignments whose expert is held are sorted by expert and go through
    one grouped product over the stacked leaves ``experts.gate_proj`` /
    ``up_proj`` ``(count, d_model, expert_width)`` and
    ``experts.down_proj`` ``(count, expert_width, d_model)``
    (:func:`held_experts`); the result is this rank's part of the routed
    sum.  What the experts held elsewhere would add is **left out**: on
    one chip the layer runs without its exchange, and nothing stands in
    for the other ranks.  The parts of all ranks add up to the whole
    block's routed sum (``tests/test_qwen3_next.py``).

    ``shared_width`` adds an expert that every token takes, behind a
    sigmoid gate of one column: ``sigmoid(shared_expert_gate(x)) *
    shared_expert(x)``; every rank computes it alike.

    After ``forward`` returns, ``self.load`` holds that call's routing
    counts (``rows_held``, ``expert_rows_max``, ``expert_rows_mean``),
    valid at the same trace level only, as ``MoELayer.aux_loss``.
    """

    def __init__(self, d_model: int, num_experts: int, top_k: int,
                 expert_width: int, held=None, shared_width: int = 0,
                 norm_topk_prob: bool = True, weight_attr=None):
        super().__init__()
        from ..nn.layers_common import Linear
        first, count = held if held is not None else (0, num_experts)
        if not (0 <= first and 0 < count and first + count <= num_experts):
            raise ValueError(f"held={held} does not lie in the router's "
                             f"{num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.held = (first, count)
        self.norm_topk_prob = norm_topk_prob
        self.gate = Linear(d_model, num_experts, weight_attr, bias_attr=False)
        self.experts = _ExpertStack(count, d_model, expert_width, weight_attr)
        if shared_width:
            self.shared_expert = _GatedMLP(d_model, shared_width, weight_attr)
            self.shared_expert_gate = Linear(d_model, 1, weight_attr,
                                             bias_attr=False)
        else:
            self.shared_expert = None
        self.load = {}

    def route(self, tokens):
        """``(weights (N, top_k) float32, experts (N, top_k) int32)``."""
        p = jax.nn.softmax(self.gate(tokens).astype(jnp.float32), axis=-1)
        top, idx = jax.lax.top_k(p, self.top_k)
        if self.norm_topk_prob:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return top, idx

    def forward(self, x):
        """x: [..., H] -> [..., H]; routing over the flattened tokens."""
        from ..observability.regions import region
        shape = x.shape
        tokens = x.reshape(-1, shape[-1])
        n = tokens.shape[0]
        first, count = self.held
        with region("mlp"):
            with jax.named_scope("moe_router"):
                top, idx = self.route(tokens)
                local = idx.reshape(-1) - first
                here = (local >= 0) & (local < count)
                key = jnp.where(here, local, count)     # elsewhere: last
                order = jnp.argsort(key, stable=True)
                sizes = jnp.bincount(key, length=count + 1)[:count]
                # an assignment's token is its place over top_k.  The
                # worst case holds min(top_k, count) rows a token; the
                # index arrays are padded to whole blocks
                worst = n * min(self.top_k, count)
                block = row_block(
                    worst, n * self.top_k * count / self.num_experts)
                order = jnp.pad(order[:worst], (0, -worst % block))
                tok = (order // self.top_k).astype(jnp.int32)
                wts = top.reshape(-1)[order]
                self.load = {
                    "rows_held": jnp.sum(sizes),
                    "expert_rows_max": jnp.max(sizes),
                    "expert_rows_mean": jnp.sum(sizes) / count}
            with jax.named_scope("moe_experts"):
                out = held_experts(
                    block, tokens, tok, wts, sizes.astype(jnp.int32),
                    self.experts.gate_proj, self.experts.up_proj,
                    self.experts.down_proj)
            if self.shared_expert is not None:
                out = out + (jax.nn.sigmoid(self.shared_expert_gate(tokens))
                             * self.shared_expert(tokens)).astype(out.dtype)
        return out.reshape(shape)


def moe_dispatch(x, combine_weights, dispatch_mask):
    """Functional dispatch (incubate.nn.functional.moe_dispatch parity)."""
    return jnp.einsum("nec,nh->ech", dispatch_mask.astype(x.dtype), x)


def moe_combine(expert_out, combine_weights):
    """Functional combine (incubate.nn.functional.moe_combine parity)."""
    return jnp.einsum("ech,nec->nh", expert_out,
                      combine_weights.astype(expert_out.dtype))
