"""The chunked gated delta rule (Gated DeltaNet, arXiv:2412.06464) for TPU
in Pallas: a forward and a backward kernel under one ``jax.custom_vjp``.

Per head, with a state ``S`` ``(dk, dv)`` from zero, position ``t`` does
``S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T;  o_t = S^T
q_t``.  The XLA composition (``incubate.nn.functional
._gated_delta_rule_ref``) computes everything that does not read ``S``
for all chunks at once and carries float32 arrays of 67-268 MB a layer
through HBM between that part and a ``lax.scan`` (PERF.md section 5: a
third of Qwen3-Next's training step).  Here a chunk's ``(C, C)`` system,
its solution and ``S`` never leave VMEM.

**One grid step** is one chunk of ``C`` = 64 positions of ``P`` = 128 / C
= 2 value heads, whose rows are *packed* into ``R`` = 128 rows (head
``p`` in rows ``p C .. (p + 1) C``): every ``(C, C)`` matrix of the rule
becomes one block-diagonal ``(R, R)`` matrix, a whole MXU tile, at the
passes one head would take.  Grid ``(B, Hv / P, S / C)``, the chunk axis
``"arbitrary"`` with ``S`` ``(P, dk, dv)`` float32 in a VMEM scratch that
lives across it.  q, k and v are read straight from the projections'
layout (``(B, S, H d)``, a ``(1, C, d)`` block at the head's column
block); the ``P`` value heads of a step share one key head, ``h P //
repeat`` through the ``index_map``, so the repeat is never materialised.
With ``G`` the running sum of ``g`` inside the chunk (the wrapper's
``cumsum`` over a ``(B, S, Hv)`` array: one megabyte), ``M = exp(where(i
>= j, G_i - G_j, -inf))``, ``e = exp(G)``, ``f = exp(G_C - G)``:

    A = strict_lower((beta k) k^T * M)        T = (I + A)^-1
    u = T (beta * (v - (e k) S))              (= the composition's v_new)
    o = (e q) S + lower(q k^T * M) u          S <- exp(G_C) S + (f k)^T u

``T (beta v) - T (beta e k) S`` is the composition's ``V' - K' S`` with
the product against ``S`` taken first.  Mosaic has no triangular solve:
``A`` is strictly lower inside each ``C``-block, so ``A^C = 0`` and
``(I + A)^-1 = (I - A)(I + A^2)(I + A^4) ... (I + A^(C/2))``: ``2
(log2 C - 1)`` products.  ``k k^T``, the inverse and ``T r`` are float32
at ``Precision.HIGHEST``; the products that read or write ``S`` and
``q k^T`` run as the composition runs them, by ``q``'s dtype: float32
q and k (what the model's normalisation hands over) at ``HIGHEST``
whatever ``v``'s dtype, bfloat16 q and k with every operand rounded to
bfloat16 and one MXU pass; float32 accumulation either way, and ``o``
leaves in float32 as the composition's does.  Every exponent is masked
before ``exp`` and is <= 0.

**The backward kernel** walks the chunks in reverse with ``dS`` in
scratch.  The forward keeps, a chunk, the state it started from and
``T`` (float32: ``Hv dk dv + Hv C R`` numbers); the backward reloads
them, redoes ``u`` (two products) and applies the chunk's vjp by hand.
With ``dr = T^T du`` the system's cotangent is ``dA = -dr u^T``: no
product is spent on differentiating the inverse.  It returns ``dq``,
``dk`` (summed over a step's value heads, which share a key head),
``dv``, ``dG`` and ``dbeta``; the wrapper sums the rest of the repeat and
turns ``dG`` into ``dg`` (a reversed ``cumsum``).

``supported()`` admits ``dk``, ``dv`` multiples of 128, ``C`` = 64 and
``P`` value heads or a multiple of that to a key head (Qwen3-Next: 2);
the caller (``ops/pallas/__init__.py``) declines under a mesh.  A sequence
that is no multiple of ``C`` is padded with ``k = beta = g = 0``
positions, which write nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp
from ._common import VMEM_BUDGET, VMEM_LIMIT
from ._common import mxu_precision as _precision

CHUNK = 64
ROWS = 128                     # packed rows a grid step: one MXU tile
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, prec, dims=None):
    if dims is None:
        return jax.lax.dot(a, b, precision=prec, preferred_element_type=F32)
    return jax.lax.dot_general(a, b, dims, precision=prec,
                               preferred_element_type=F32)


def _vmem_estimate(dk: int, dv: int, heads: int, q_bytes: int,
                   v_bytes: int) -> int:
    """Bytes of the backward step's blocks, twice (the pipeline's two
    buffers), its scratch and some forty ``(R, R)`` float32 temporaries."""
    qk = 4 * CHUNK * dk * q_bytes                    # q, k in; dq, dk out
    vs = 2 * CHUNK * heads * dv * (v_bytes + 4)      # v, do in; dv out
    state = 2 * heads * dk * dv * 4                  # S in; dS scratch
    return 2 * (qk + vs + state // 2 + ROWS * ROWS * 4) + state // 2 \
        + 40 * ROWS * ROWS * 4 + 12 * ROWS * max(dk, dv) * 4


def supported(q, k, v, g, beta, chunk: int = CHUNK) -> bool:
    if q.ndim != 4 or v.ndim != 4 or q.shape != k.shape:
        return False
    hk, dk = q.shape[2:]
    hv, dv = v.shape[2:]
    heads = ROWS // CHUNK
    # a step's packed value heads read one key head between them
    if chunk != CHUNK or dk % 128 or dv % 128 or hv % (hk * heads):
        return False
    if g.shape != v.shape[:3] or beta.shape != v.shape[:3] \
            or g.dtype != jnp.float32 or beta.dtype != jnp.float32:
        return False
    if q.dtype not in (jnp.float32, jnp.bfloat16) \
            or v.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return _vmem_estimate(dk, dv, heads, q.dtype.itemsize,
                          v.dtype.itemsize) <= VMEM_BUDGET


# -- the chunk's mathematics, shared by both kernels ------------------------

def _unit_lower_inverse(a, eye, chunk: int):
    """``(I + a)^-1`` for ``a`` strictly lower inside blocks of
    ``chunk``: ``a`` is nilpotent there, so the Neumann series ends and
    factors into ``log2(chunk)`` terms."""
    t = eye - a
    p = a
    for _ in range(chunk.bit_length() - 2):
        p = _dot(p, p, HI)
        t = t + _dot(t, p, HI)
    return t


def _pack_rows(blocks):
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)


def _chunk_common(q, k, v2, g_row, b_row, states, *, chunk, mm, inverse):
    """What both kernels compute of a chunk before the state moves: ``q``,
    ``k`` the key head's ``(C, dk)`` block, ``v2`` ``(R, dv)`` packed,
    ``g_row``, ``b_row`` ``(1, R)``, ``states`` the heads' entering
    ``(dk, dv)``; all float32.  ``mm``: the dtype the state products'
    operands are rounded to.  ``inverse``: a function of ``(A, I)`` that
    returns ``T``."""
    heads = len(states)
    rows = heads * chunk
    prec = _precision(mm)
    q2, k2 = _pack_rows([q] * heads), _pack_rows([k] * heads)   # (R, dk)

    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    # the block (head) a packed row belongs to
    shift = chunk.bit_length() - 1
    blk_r = jax.lax.shift_right_logical(ri, shift)
    blk_c = jax.lax.shift_right_logical(ci, shift)
    same = blk_r == blk_c
    eye = ri == ci
    lower = same & (ri >= ci)
    strict = same & (ri > ci)
    last = ci == blk_r * chunk + (chunk - 1)

    def col(row, mask=eye):
        """(1, R) -> (R, 1): ``out[i] = row[j]`` where ``mask[i, j]``."""
        return jnp.sum(jnp.where(mask, row, 0.0), axis=1, keepdims=True)

    g_col, b_col = col(g_row), col(b_row)
    g_last = col(g_row, last)                  # G_C of the row's own block
    # the mask goes on the exponent: exp of a masked-out positive
    # difference would overflow, and its zero cotangent would be NaN
    m = jnp.exp(jnp.where(lower, g_col - g_row, -jnp.inf))
    e = jnp.exp(g_col)
    f = jnp.exp(g_last - g_col)
    bkk = b_col * _dot(k2, k2, HI, _NT)
    t = inverse(jnp.where(strict, bkk * m, 0.0),
                jnp.where(eye, 1.0, 0.0).astype(F32))
    qk = _dot(q2.astype(mm), k2.astype(mm), prec, _NT)
    kg, qg, kd = e * k2, e * q2, f * k2
    s_mm = [x.astype(mm) for x in states]
    head = lambda x, p: x[p * chunk:(p + 1) * chunk]
    w = _pack_rows([_dot(head(kg, p).astype(mm), s_mm[p], prec)
                    for p in range(heads)])
    vmw = v2 - w
    u = _dot(t, b_col * vmw, HI)                                # (R, dv)
    gamma = [jnp.exp(g_last[p * chunk:p * chunk + 1])           # (1, 1)
             for p in range(heads)]
    return dict(q2=q2, k2=k2, m=m, e=e, f=f, gamma=gamma, b_col=b_col,
                bkk=bkk, t=t, qk=qk, kg=kg, qg=qg, kd=kd, s_mm=s_mm, vmw=vmw,
                u=u, eye=eye, strict=strict, ci=ci, head=head, prec=prec,
                heads=heads)


def _chunk_forward(c, states, mm):
    """``o`` a head ``(C, dv)`` and the heads' leaving states."""
    head, prec, heads = c["head"], c["prec"], c["heads"]
    um = c["u"].astype(mm)
    # m is zero above the diagonal and outside the row's own block
    pu = _dot((c["qk"] * c["m"]).astype(mm), um, prec)
    outs = [_dot(head(c["qg"], p).astype(mm), c["s_mm"][p], prec)
            + head(pu, p) for p in range(heads)]
    new = [c["gamma"][p] * states[p]
           + _dot(head(c["kd"], p).astype(mm), head(um, p), prec, _TN)
           for p in range(heads)]
    return outs, new


def _chunk_backward(c, states, do, ds, mm):
    """The chunk's vjp by hand.  ``do`` the heads' ``(C, dv)`` cotangents
    (in ``mm``), ``ds`` those of the leaving states (float32).  Returns
    ``dq2``, ``dk2`` ``(R, dk)``, ``dv2`` ``(R, dv)``, ``dG``, ``dbeta``
    ``(1, R)`` and the entering states' cotangents."""
    head, prec, heads = c["head"], c["prec"], c["heads"]
    q2, k2, m, e, f = c["q2"], c["k2"], c["m"], c["e"], c["f"]
    b_col, t, u, s_mm, gamma = c["b_col"], c["t"], c["u"], c["s_mm"], \
        c["gamma"]
    chunk = q2.shape[0] // heads
    do2 = _pack_rows(do)
    um = u.astype(mm)
    ds_mm = [x.astype(mm) for x in ds]

    # o = (e q) S + (qk * m) u;  S' = gamma S + (f k)^T u
    pm = (c["qk"] * m).astype(mm)
    du = _dot(pm, do2, prec, _TN) + _pack_rows(
        [_dot(head(c["kd"], p).astype(mm), ds_mm[p], prec)
         for p in range(heads)])
    dpm = _dot(do2, um, prec, _NT) * m                # cotangent of q k^T
    dqg = _pack_rows([_dot(do[p], s_mm[p], prec, _NT) for p in range(heads)])
    dkd = _pack_rows([_dot(head(um, p), ds_mm[p], prec, _NT)
                      for p in range(heads)])
    # u = T r, r = beta (v - w), w = (e k) S;  T = (I + A)^-1
    dr = _dot(t, du, HI, _TN)
    dam = jnp.where(c["strict"], -_dot(dr, u, HI, _NT) * m, 0.0)
    dv2 = b_col * dr
    dkg = -_pack_rows([_dot(head(dv2, p).astype(mm), s_mm[p], prec, _NT)
                       for p in range(heads)])
    ds_in = [gamma[p] * ds[p]
             + _dot(head(c["qg"], p).astype(mm), do[p], prec, _TN)
             - _dot(head(c["kg"], p).astype(mm), head(dv2, p).astype(mm),
                    prec, _TN) for p in range(heads)]
    # A = strict((beta k) k^T * m)
    dkb = _dot(dam, k2, HI)
    dk2 = _dot(dam, b_col * k2, HI, _TN) + b_col * dkb + e * dkg + f * dkd \
        + _dot(dpm.astype(mm), q2.astype(mm), prec, _TN)
    dq2 = _dot(dpm.astype(mm), k2.astype(mm), prec) + e * dqg
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    db_col = rowsum(dr * c["vmw"]) + rowsum(dkb * k2)
    # G reaches the chunk through m, e, f and gamma
    # (the diagonal of q k^T * m reaches G_i with both signs: left out)
    n = dam * c["bkk"] + jnp.where(c["strict"], dpm * c["qk"], 0.0)
    dff = rowsum(dkd * k2) * f
    dg_col = (rowsum(dkg * k2) + rowsum(dqg * q2)) * e - dff + rowsum(n)
    row = lambda x: jnp.sum(jnp.where(c["eye"], x, 0.0), axis=0,
                            keepdims=True)
    dg_row = row(dg_col) - jnp.sum(n, axis=0, keepdims=True)
    lane = c["ci"][:1]
    for p in range(heads):
        at_last = jnp.sum(head(dff, p), axis=0, keepdims=True) + gamma[p] \
            * jnp.sum(rowsum(ds[p] * states[p]), axis=0, keepdims=True)
        dg_row = dg_row + jnp.where(lane == p * chunk + chunk - 1, at_last,
                                    0.0)
    return dq2, dk2, dv2, dg_row, row(db_col), ds_in


# -- the kernels -------------------------------------------------------------

def _operands(q_ref, k_ref, v_ref, g_ref, b_ref, heads):
    """The step's q, k (its key head's block), packed v, G and beta,
    float32."""
    dv = v_ref.shape[-1] // heads
    v2 = _pack_rows([v_ref[0][:, p * dv:(p + 1) * dv].astype(F32)
                     for p in range(heads)])
    return (q_ref[0].astype(F32), k_ref[0].astype(F32), v2, g_ref[0, 0, 0],
            b_ref[0, 0, 0])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, heads,
                chunk, mm, save):
    s_scr = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    states = [s_scr[p] for p in range(heads)]
    c = _chunk_common(
        *_operands(q_ref, k_ref, v_ref, g_ref, b_ref, heads), states,
        chunk=chunk, mm=mm,
        inverse=lambda a, eye: _unit_lower_inverse(a, eye, chunk))
    if save:
        rest[0][0, :, 0] = s_scr[...]
        rest[1][0, 0, 0] = c["t"]
    outs, new = _chunk_forward(c, states, mm)
    for p in range(heads):
        s_scr[p] = new[p]
    o_ref[0] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, heads,
                chunk, mm):

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    states = [s_ref[0, p, 0] for p in range(heads)]
    c = _chunk_common(
        *_operands(q_ref, k_ref, v_ref, g_ref, b_ref, heads), states,
        chunk=chunk, mm=mm, inverse=lambda a, eye: t_ref[0, 0, 0])
    dv = do_ref.shape[-1] // heads
    do = [do_ref[0][:, p * dv:(p + 1) * dv].astype(mm) for p in range(heads)]
    dq2, dk2, dv2, dg_row, db_row, ds_in = _chunk_backward(
        c, states, do, [ds_scr[p] for p in range(heads)], mm)
    for p in range(heads):
        ds_scr[p] = ds_in[p]
    dg_ref[0, 0, 0] = dg_row
    db_ref[0, 0, 0] = db_row
    head = c["head"]
    # the heads share a key head: its block is their sum
    shared = lambda x: sum(head(x, p) for p in range(heads))
    dq_ref[0] = shared(dq2).astype(dq_ref.dtype)
    dk_ref[0] = shared(dk2).astype(dk_ref.dtype)
    dv_ref[0] = jnp.concatenate([head(dv2, p) for p in range(heads)],
                                axis=1).astype(dv_ref.dtype)


# -- the calls ---------------------------------------------------------------

def _geometry(q, v):
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    heads = ROWS // CHUNK
    return b, s, hk, dk, hv, dv, heads, hv // hk


def _pack(x, heads):
    """(B, Sp, Hv) -> (B, Hv / heads, n, 1, heads * C): a step's packed
    row of per-position scalars."""
    b, sp, hv = x.shape
    x = x.reshape(b, sp // CHUNK, CHUNK, hv // heads, heads)
    return x.transpose(0, 3, 1, 4, 2).reshape(b, hv // heads, sp // CHUNK, 1,
                                              heads * CHUNK)


def _unpack(x, heads):
    b, hp, n = x.shape[:3]
    x = x.reshape(b, hp, n, heads, CHUNK).transpose(0, 2, 4, 1, 3)
    return x.reshape(b, n * CHUNK, hp * heads)


def _qk_spec(chunk_of, heads, rep, dk):
    """Block spec of q (or k) over its ``(B, Sp, Hk dk)`` view: the key
    head of the step's value heads, through the index map."""
    return pl.BlockSpec((1, CHUNK, dk),
                        lambda b, h, c: (b, chunk_of(c), h * heads // rep))


def _step_specs(chunk_of, heads, dk, dv):
    """Block specs of a step's chunk ``chunk_of(c)``: a packed ``(1, R)``
    row (G, beta), the heads' columns of a ``(B, Sp, Hv dv)`` view (v, o
    and their cotangents), the heads' states and the step's inverse."""
    at = lambda b, h, c: (b, h, chunk_of(c), 0, 0)
    return (pl.BlockSpec((1, 1, 1, 1, ROWS), at),
            pl.BlockSpec((1, CHUNK, heads * dv),
                         lambda b, h, c: (b, chunk_of(c), h)),
            pl.BlockSpec((1, heads, 1, dk, dv), at),
            pl.BlockSpec((1, 1, 1, ROWS, ROWS), at))


_PARAMS = _pcp()(dimension_semantics=("parallel", "parallel", "arbitrary"),
                 vmem_limit_bytes=VMEM_LIMIT)


def _forward(q, k, v, big, beta, save, interpret):
    """Operands padded to whole chunks; ``big`` and ``beta`` packed."""
    b, sp, hk, dk, hv, dv, heads, rep = _geometry(q, v)
    n, hp = sp // CHUNK, hv // heads
    packed, wide, state, inverse = _step_specs(lambda c: c, heads, dk, dv)
    qk = _qk_spec(lambda c: c, heads, rep, dk)
    out_shape = [jax.ShapeDtypeStruct((b, sp, hv * dv), F32)]
    out_specs = [wide]
    if save:
        out_shape += [jax.ShapeDtypeStruct((b, hv, n, dk, dv), F32),
                      jax.ShapeDtypeStruct((b, hp, n, ROWS, ROWS), F32)]
        out_specs += [state, inverse]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, chunk=CHUNK, mm=q.dtype,
                          save=save),
        grid=(b, hp, n),
        in_specs=[qk, qk, wide, packed, packed],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)],
        compiler_params=_PARAMS,
        interpret=interpret, name="gated_delta_rule_fwd",
    )(q.reshape(b, sp, hk * dk), k.reshape(b, sp, hk * dk),
      v.reshape(b, sp, hv * dv), big, beta)
    o = out[0].reshape(b, sp, hv, dv)
    return (o, out[1], out[2]) if save else o


def _backward(q, k, v, big, beta, states, inv, do, interpret):
    b, sp, hk, dk, hv, dv, heads, rep = _geometry(q, v)
    n, hp = sp // CHUNK, hv // heads
    back = lambda c: n - 1 - c
    packed, wide, state, inverse = _step_specs(back, heads, dk, dv)
    qk = _qk_spec(back, heads, rep, dk)
    # dq, dk: a step's block is the sum over its heads, for their key head
    dqk = pl.BlockSpec((1, CHUNK, dk), lambda b, h, c: (b, back(c), h))
    dq, dk_, dv_, dbig, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, chunk=CHUNK, mm=q.dtype),
        grid=(b, hp, n),
        in_specs=[qk, qk, wide, packed, packed, state, inverse, wide],
        out_specs=[dqk, dqk, wide, packed, packed],
        out_shape=[jax.ShapeDtypeStruct((b, sp, hp * dk), q.dtype),
                   jax.ShapeDtypeStruct((b, sp, hp * dk), k.dtype),
                   jax.ShapeDtypeStruct((b, sp, hv * dv), v.dtype),
                   jax.ShapeDtypeStruct(big.shape, F32),
                   jax.ShapeDtypeStruct(beta.shape, F32)],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)],
        compiler_params=_PARAMS,
        interpret=interpret, name="gated_delta_rule_bwd",
    )(q.reshape(b, sp, hk * dk), k.reshape(b, sp, hk * dk),
      v.reshape(b, sp, hv * dv), big, beta, states, inv,
      do.reshape(b, sp, hv * dv))
    # the steps of a key head (repeat / P of them)
    per_key = lambda x: x.reshape(b, sp, hk, -1, dk).sum(3)
    return (per_key(dq), per_key(dk_), dv_.reshape(b, sp, hv, dv), dbig,
            dbeta)


def _running_sum(g):
    """``G``: the sum of ``g`` from its chunk's first position on."""
    b, sp, hv = g.shape
    return jnp.cumsum(g.reshape(b, sp // CHUNK, CHUNK, hv),
                      axis=2).reshape(b, sp, hv)


def _padded(q, k, v, g, beta):
    pad = -q.shape[1] % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    heads = ROWS // CHUNK
    return q, k, v, _pack(_running_sum(g), heads), _pack(beta, heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gated_delta_rule(q, k, v, g, beta, interpret=False):
    """``q``, ``k`` ``(B, S, Hk, dk)``, ``v`` ``(B, S, Hv, dv)`` with
    ``Hv`` a multiple of ``Hk`` (key head ``h // (Hv / Hk)`` serves value
    head ``h``), ``g``, ``beta`` ``(B, S, Hv)`` float32; returns ``o`` ``(B, S,
    Hv, dv)`` float32, as the composition does.  Shapes that ``supported()``
    admits."""
    with jax.named_scope("gated_delta_rule"):
        o = _forward(*_padded(q, k, v, g, beta), False, interpret)
        return o[:, :q.shape[1]]


def _vjp_fwd(q, k, v, g, beta, interpret):
    with jax.named_scope("gated_delta_rule"):
        qp, kp, vp, big, bp = _padded(q, k, v, g, beta)
        o, states, inv = _forward(qp, kp, vp, big, bp, True, interpret)
        return o[:, :q.shape[1]], (qp, kp, vp, big, bp, states, inv)


def _vjp_bwd(interpret, res, do):
    qp, kp, vp, big, bp, states, inv = res
    s, sp = do.shape[1], qp.shape[1]
    heads = ROWS // CHUNK
    with jax.named_scope("gated_delta_rule"):
        if sp != s:
            do = jnp.pad(do, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
        dq, dk, dv, dbig, dbeta = _backward(qp, kp, vp, big, bp, states, inv,
                                            do, interpret)
        b, hv = vp.shape[0], vp.shape[2]
        # G is a running sum inside the chunk: g_j reaches every G_i, i >= j
        dbig = _unpack(dbig, heads).reshape(b, sp // CHUNK, CHUNK, hv)
        dg = jnp.flip(jnp.cumsum(jnp.flip(dbig, 2), axis=2), 2)
        dg = dg.reshape(b, sp, hv)[:, :s]
        dbeta = _unpack(dbeta, heads)[:, :s]
        return dq[:, :s], dk[:, :s], dv[:, :s], dg, dbeta


gated_delta_rule.defvjp(_vjp_fwd, _vjp_bwd)
