"""Shared helpers for the Pallas kernel pack — one definition of the
block-divisor picker and the MXU precision request (previously copied
per kernel module; a Mosaic alignment-rule change now lands in one
place)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Mosaic grants a kernel 16 MiB of scoped VMEM unless it asks for more;
# a v5e core has 128 MiB.  The weight-resident kernels (fused_mlp,
# fused_norm_qkv) ask for VMEM_LIMIT, and their
# ``supported()`` gates admit only block geometries whose estimated
# allocation fits VMEM_BUDGET — the gap is headroom for the internal
# scratch the estimates do not model (measured 1-5 MiB by deviceless
# v5e compiles, tests/test_multichip_pallas_compile.py).
VMEM_LIMIT = 48 * 2 ** 20
VMEM_BUDGET = 40 * 2 ** 20


def pick_block(n: int, preferred: int, quantum: int = 128) -> int:
    """Largest multiple of ``quantum`` that divides ``n`` and is
    <= ``preferred`` (Mosaic wants the last two block dims divisible by
    (8, 128) unless the block spans the full dim, which is the
    fallback)."""
    b = min(n, preferred) // quantum * quantum
    while b >= quantum:
        if n % b == 0:
            return b
        b -= quantum
    return n


def mxu_precision(dtype):
    """Precision request for kernel dots: f32 operands must NOT be
    truncated to bf16 by the TPU MXU default (the int4_matmul note);
    bf16 operands take the fast default.  Kernels only execute on TPU
    or in the interpreter, so no CPU-codegen caveat applies here (the
    XLA compositions use incubate's backend-aware ``_prec`` instead)."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None)
