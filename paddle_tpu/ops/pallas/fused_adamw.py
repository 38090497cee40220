"""Fused AdamW update for TPU in Pallas — moments + parameter in one
elementwise kernel over aliased (donated) buffers.

Why a kernel: the XLA optimizer update is ~10 elementwise HLOs per
parameter (two moment EMAs, two bias corrections, rsqrt, decay, axpy).
XLA fuses them, but the fusion boundaries still read p/m/v from HBM and
write p'/m'/v' back as separate buffers; with ``input_output_aliases``
this kernel pins the in-place contract — each of the three state arrays
is read once and overwritten in place, the theoretical traffic floor for
the update (3 reads + 1 grad read + 3 writes of N elements).

The decoupled-weight-decay formula mirrors ``optimizer.Adam._adam_core``
exactly (same operation order, f32 throughout); betas/eps/wd are static
(folded into the trace), lr and the two bias corrections are traced
scalars in SMEM.  Eligible params are flattened to (rows, 128) lanes —
``optimizer.AdamW`` only dispatches here for f32 params whose size is a
multiple of 1024 (everything a transformer trains except odd scalars,
which keep the XLA path).

Block row-count comes from tools/tuned_configs.json (ops.tuning, trace
time); sweep with ``python tools/autotune.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.compat import pallas_compiler_params as _pcp
from .. import tuning

LANES = 128
DEFAULT_BLOCK_ROWS = 512    # (512, 128) f32 ≈ 256 KiB per operand block


def _kernel(s_ref, p_ref, g_ref, m_ref, v_ref,
            p_out, m_out, v_out, *, beta1, beta2, eps, wd):
    lr = s_ref[0, 0]
    c1 = s_ref[0, 1]        # 1 / (1 - beta1^t)
    c2 = s_ref[0, 2]        # 1 / (1 - beta2^t)
    g = g_ref[...]
    p = p_ref[...]
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * jnp.square(g)
    update = (m * c1) / (jnp.sqrt(v * c2) + eps)
    if wd:
        update = update + wd * p
    p_out[...] = p - lr * update
    m_out[...] = m
    v_out[...] = v


def eligible(p) -> bool:
    """Shapes this kernel serves: f32, size a multiple of 8·128 lanes
    (flattened without padding — padding would force copies and defeat
    the in-place aliasing)."""
    return (p.dtype == jnp.float32 and p.size >= 8 * LANES
            and p.size % (8 * LANES) == 0)


def fused_adamw_update(p, g, m, v, lr, c1, c2, *, beta1, beta2, eps,
                      wd=0.0, block_rows=None, interpret: bool = False):
    """One fused AdamW step.  p/g/m/v: same-shape f32 arrays satisfying
    :func:`eligible`; lr/c1/c2: traced f32 scalars (c1/c2 the bias
    corrections ``1/(1-beta^t)``); beta1/beta2/eps/wd: static floats.
    Returns ``(new_p, new_m, new_v)`` with p/m/v aliased in place."""
    shape = p.shape
    rows = p.size // LANES
    if block_rows is None:
        cfg = tuning.tuned_config("fused_adamw", "default")
        block_rows = cfg.get("block_rows", DEFAULT_BLOCK_ROWS)
    br = max(8, min(int(block_rows), rows) // 8 * 8)
    while rows % br:
        br //= 2
    br = max(br, 8)
    scal = jnp.stack([lr.astype(jnp.float32),
                      c1.astype(jnp.float32),
                      c2.astype(jnp.float32)]).reshape(1, 3)
    p2, g2, m2, v2 = (a.astype(jnp.float32).reshape(rows, LANES)
                      for a in (p, g, m, v))

    def rmap(i):
        return (i, 0)

    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_kernel, beta1=float(beta1), beta2=float(beta2),
                          eps=float(eps), wd=float(wd)),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((1, 3), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((br, LANES), rmap),
            pl.BlockSpec((br, LANES), rmap),
            pl.BlockSpec((br, LANES), rmap),
            pl.BlockSpec((br, LANES), rmap),
        ],
        out_specs=[
            pl.BlockSpec((br, LANES), rmap),
            pl.BlockSpec((br, LANES), rmap),
            pl.BlockSpec((br, LANES), rmap),
        ],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * 3,
        # in-place: p/m/v buffers are overwritten, never duplicated
        input_output_aliases={1: 0, 3: 1, 4: 2},
        compiler_params=_pcp()(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="fused_adamw",
    )(scal, p2, g2, m2, v2)
    return (new_p.reshape(shape), new_m.reshape(shape),
            new_v.reshape(shape))
