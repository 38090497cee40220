"""Fused AdamW update for TPU in Pallas — moments + parameter in one
elementwise kernel over aliased (donated) buffers.

Why a kernel: the XLA optimizer update is ~10 elementwise HLOs per
parameter (two moment EMAs, two bias corrections, rsqrt, decay, axpy).
XLA fuses them, but the fusion boundaries still read p/m/v from HBM and
write p'/m'/v' back as separate buffers; with ``input_output_aliases``
this kernel pins the in-place contract — each of the three state arrays
is read once and overwritten in place, the theoretical traffic floor for
the update: master, gradient and two moments read, master, two moments
and the low-precision copy written (28 B a parameter under amp O2).

The operands are the leaves as the train step holds them: the grid runs
over ``(rows / br, cols / bc)`` blocks of the leaf's own trailing two
dimensions, so nothing parameter-sized is reshaped before or after the
call (on the chip ``(4096, 14336) -> (458752, 128)`` changes the tiling
and is a copy; seven of them a leaf cost more than the kernel).  Leading
dimensions are collapsed into the rows only where that is a bitcast.
The gradient is read in the dtype it arrives in (bfloat16 under amp O2)
and widened in VMEM; where the parameter is kept in a lower precision
than its float32 master, the kernel writes that copy as a fourth output.

The decoupled-weight-decay formula mirrors ``optimizer.Adam._adam_core``
exactly (same operation order, f32 throughout); betas/eps/wd are static
(folded into the trace), lr and the two bias corrections are traced
scalars in SMEM.  :func:`eligible` is the gate: float32 state, at least
two dimensions, the last a multiple of 128 lanes and the one before it
a multiple of the sublane tile (8, or 16 where a 16-bit operand shares
the block).  Everything else — norm weights, biases, conv kernels, odd
sizes — keeps the XLA composition, which fuses to one in-place pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp

LANES = 128
# One operand block: at most 1024 lanes wide (so a block of one 16-row
# tile always fits) and 512 KiB of float32: (128, 1024) where the leaf
# divides.  Eight operands double-buffered then
# take 7 MiB of the 16 MiB scoped VMEM; twice this compiles to 17.46 MiB
# and is refused.  On the chip blocks from (16, 7168) to (256, 512) run
# within 2 % of each other, (128, 1024) ahead on most leaves (PERF.md).
BLOCK_COLS = 1024
BLOCK_ELEMS = 128 * 1024


def _kernel(s_ref, p_ref, g_ref, m_ref, v_ref, p_out, m_out, v_out,
            *low_out, beta1, beta2, eps, wd):
    lr = s_ref[0, 0]
    c1 = s_ref[0, 1]        # 1 / (1 - beta1^t)
    c2 = s_ref[0, 2]        # 1 / (1 - beta2^t)
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...]
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * jnp.square(g)
    update = (m * c1) / (jnp.sqrt(v * c2) + eps)
    if wd:
        update = update + wd * p
    new_p = p - lr * update
    p_out[...] = new_p
    m_out[...] = m
    v_out[...] = v
    for ref in low_out:
        ref[...] = new_p.astype(ref.dtype)


def _sublanes(*dtypes) -> int:
    """Rows of the HBM tile that the narrowest operand is laid out in:
    8 for 32-bit, 16 for 16-bit.  A ``None`` is no operand."""
    return max(32 // jnp.dtype(d).itemsize for d in dtypes if d is not None)


def _view(shape, sub):
    """``(rows, cols)`` of the two-dimensional view the grid runs over, or
    None.  Leading dimensions fold into the rows only where the tiles of
    the last two stay whole, which makes the reshape a bitcast."""
    if len(shape) < 2 or shape[-1] % LANES or shape[-2] % sub \
            or 0 in shape:
        return None
    return math.prod(shape[:-1]), shape[-1]


def _block(rows, cols, sub):
    """``(br, bc)``: the most lanes up to ``BLOCK_COLS`` that divide
    ``cols``, then the most rows that divide ``rows`` inside
    ``BLOCK_ELEMS``, on whole ``(sub, 128)`` tiles."""
    bc = max(c for c in range(LANES, min(cols, BLOCK_COLS) + 1, LANES)
             if cols % c == 0)
    br = max(r for r in range(sub, rows + 1, sub)
             if rows % r == 0 and r * bc <= BLOCK_ELEMS)
    return br, bc


def eligible(p, g, low_dtype=None) -> bool:
    """Leaves this kernel serves natively: float32 parameter (or master),
    two or more dimensions, the last a multiple of 128 and the one before
    it a multiple of 8 — of 16 where the gradient ``g`` or the
    low-precision copy ``low_dtype`` is a 16-bit operand of the block."""
    others = [d for d in (g.dtype, low_dtype) if d is not None]
    return (p.dtype == jnp.float32 and g.shape == p.shape
            and all(jnp.issubdtype(d, jnp.floating)
                    and jnp.dtype(d).itemsize in (2, 4) for d in others)
            and _view(p.shape, _sublanes(*others)) is not None)


def fused_adamw_update(p, g, m, v, lr, c1, c2, *, beta1, beta2, eps,
                      wd=0.0, low_dtype=None, interpret: bool = False):
    """One fused AdamW step.  p/m/v: same-shape f32 arrays, g the gradient
    in the dtype it arrives in, together satisfying :func:`eligible`;
    lr/c1/c2: traced f32 scalars (c1/c2 the bias corrections
    ``1/(1-beta^t)``); beta1/beta2/eps/wd: static floats.  Returns
    ``(new_p, new_m, new_v)`` with p/m/v aliased in place, and with
    ``low_dtype`` a fourth array, ``new_p.astype(low_dtype)``."""
    shape = p.shape
    sub = _sublanes(g.dtype, low_dtype)
    rows, cols = _view(shape, sub)
    br, bc = _block(rows, cols, sub)
    scal = jnp.stack([lr.astype(jnp.float32),
                      c1.astype(jnp.float32),
                      c2.astype(jnp.float32)]).reshape(1, 3)
    if len(shape) > 2:      # a bitcast: the (sub, 128) tiles stay whole
        p, g, m, v = (a.reshape(rows, cols) for a in (p, g, m, v))

    block = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    state = jax.ShapeDtypeStruct((rows, cols), jnp.float32)
    out_shape = [state] * 3
    if low_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct((rows, cols), low_dtype))
    out = pl.pallas_call(
        functools.partial(_kernel, beta1=float(beta1), beta2=float(beta2),
                          eps=float(eps), wd=float(wd)),
        grid=(rows // br, cols // bc),
        in_specs=[pl.BlockSpec((1, 3), lambda i, j: (0, 0),
                               memory_space=pltpu.SMEM)] + [block] * 4,
        out_specs=[block] * len(out_shape),
        out_shape=out_shape,
        # in-place: p/m/v buffers are overwritten, never duplicated
        input_output_aliases={1: 0, 3: 1, 4: 2},
        compiler_params=_pcp()(
            dimension_semantics=("parallel", "parallel"),
            # what produced the gradient elementwise (the clip's scale,
            # a cast) is computed on the blocks as they are read
            allow_input_fusion=[False, False, True, False, False]),
        interpret=interpret,
        name="fused_adamw",
    )(scal, p, g, m, v)
    if len(shape) > 2:
        out = [a.reshape(shape) for a in out]
    return tuple(out)
