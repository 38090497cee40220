#!/usr/bin/env python
"""On-chip A/B of the ring-attention PER-CHUNK compute (VERDICT r3 weak
#6 / directive #10): Pallas `flash_attention_with_lse` vs the einsum
online-softmax chunk step (`distributed.cp._ring_step`), single device,
at ring block shapes, both chunk kinds (full non-causal visit and the
causal diagonal).

Method: in-jit fori_loop slope (10-vs-60), output fed back into q so
iterations chain and nothing folds; forward pass only (the ring's scan
remats the step, so fwd cost is what the ring pays per visit).

Usage: python tools/ring_chunk_bench.py
Prints a markdown table for docs/BENCH.md §ring + one JSON line.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def slope(fn, carry0, n_lo=10, n_hi=60, reps=5):
    """Slope of min-over-reps timings: a shared host only ever ADDS time
    — so the per-point minimum is the clean estimate, and the slope of
    the minima is robust where a per-rep slope goes negative whenever a
    stall lands in the low point."""
    f = jax.jit(lambda n, c: jax.lax.fori_loop(0, n, lambda i, cc: fn(cc),
                                               c), static_argnums=0)
    jax.block_until_ready(f(n_lo, carry0))
    jax.block_until_ready(f(n_hi, carry0))
    t_lo = t_hi = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(n_lo, carry0))
        t_lo = min(t_lo, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(f(n_hi, carry0))
        t_hi = min(t_hi, time.perf_counter() - t0)
    return (t_hi - t_lo) / (n_hi - n_lo) * 1000.0


def main():
    from paddle_tpu.distributed import cp
    from paddle_tpu.ops.pallas import flash_attention as fa

    rows = []
    out_json = {}
    for chunk in (512, 1024, 2048):
        b, h, d = 2, 16, 64
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, chunk, h, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, chunk, h, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, chunk, h, d), jnp.bfloat16)

        for causal in (False, True):
            # flash chunk (what _ring_inner_flash runs per visit)
            def flash_step(qq, causal=causal):
                out, lse = fa.flash_attention_with_lse(qq, k, v,
                                                       causal=causal)
                return (qq + 1e-6 * out.astype(qq.dtype)).astype(qq.dtype)

            ms_flash = slope(flash_step, q)

            # einsum online-softmax chunk (what _ring_inner runs)
            qg = q.reshape(b, chunk, h, 1, d)
            q_pos = jnp.arange(chunk)
            step = functools.partial(cp._ring_step, causal=causal,
                                     scale=1.0 / (d ** 0.5), chunk=chunk)

            def einsum_step(qq):
                qg_i = qq.reshape(b, chunk, h, 1, d)
                m0 = jnp.full((b, h, 1, chunk), cp.NEG_INF, jnp.float32)
                l0 = jnp.zeros((b, h, 1, chunk), jnp.float32)
                a0 = jnp.zeros((b, chunk, h, 1, d), jnp.float32)
                m, l, acc = step((m0, l0, a0), k, v, qg_i, q_pos, 0)
                out = (acc / jnp.maximum(l, 1e-30)[..., None]
                       .transpose(0, 3, 1, 2, 4)).reshape(b, chunk, h, d)
                return (qq + 1e-6 * out.astype(qq.dtype)).astype(qq.dtype)

            ms_einsum = slope(einsum_step, q)
            kind = "diagonal (causal)" if causal else "full visit"
            rows.append((chunk, kind, ms_flash, ms_einsum,
                         ms_einsum / ms_flash))
            out_json[f"c{chunk}_{'causal' if causal else 'full'}"] = {
                "flash_ms": round(ms_flash, 3),
                "einsum_ms": round(ms_einsum, 3)}

    print("| chunk | visit kind | flash ms | einsum ms | einsum/flash |")
    print("|---|---|---|---|---|")
    for chunk, kind, msf, mse, ratio in rows:
        print(f"| {chunk} | {kind} | {msf:.3f} | {mse:.3f} | "
              f"{ratio:.2f}x |")
    print()
    print(json.dumps(out_json))


if __name__ == "__main__":
    main()
