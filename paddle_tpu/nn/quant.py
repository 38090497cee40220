"""``paddle.nn.quant`` parity: weight-only quantization for serving.

Reference: python/paddle/nn/quant/quantized_linear.py (weight_quantize /
weight_dequantize / weight_only_linear / llm_int8_linear over the Cutlass
fpA_intB GEMM — SURVEY §2.1 Cutlass row).  Decode is HBM-bandwidth-bound
(docs/BENCH.md "Decode throughput"): at batch 1 the parameter stream IS
the roofline, so storing weights as int8 (or packed int4) halves
(quarters) the bytes the MXU waits on.

TPU-first design: no custom GEMM — the weight is stored quantized in HBM
and dequantized *inside* the XLA matmul fusion (convert+scale fuse into
the dot's operand read; Mosaic emits the widening on the fly), which is
exactly what the reference's fpA_intB kernel hand-writes.  Scales are
per-out-channel (or per-(group, out-channel) for ``group_size``>0), so
for the ungrouped path the scale commutes out of the contraction and is
applied AFTER the int8 matmul — the hot loop reads only int8.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .layer import Layer

__all__ = ["weight_quantize", "weight_dequantize", "weight_only_linear",
           "llm_int8_linear", "QuantizedLinear", "quantize_linears"]

_QMAX = {"weight_only_int8": 127.0, "weight_only_int4": 7.0,
         "llm.int8": 127.0}


def _check_algo(algo: str) -> None:
    if algo not in _QMAX:
        raise ValueError(f"unsupported algo {algo!r}; one of {list(_QMAX)}")


def _use_int4_kernel() -> bool:
    """The fused int4 kernel is a TPU Mosaic kernel; CPU tests keep the
    XLA reference formulation (numerically identical — the kernel's own
    tests assert exactness in interpret mode)."""
    import os

    if os.environ.get("PDTPU_INT4_KERNEL", "1") == "0":
        return False
    return jax.default_backend() == "tpu"


def _use_int8_kernel() -> bool:
    """Same gate for the fused int8 kernel (ops/pallas/int8_matmul.py);
    PDTPU_INT8_KERNEL=0 pins the XLA formulation for A/B runs."""
    import os

    if os.environ.get("PDTPU_INT8_KERNEL", "1") == "0":
        return False
    return jax.default_backend() == "tpu"


def _active_mesh():
    """The physical mesh entered via ``with mesh:`` (None outside).
    Mosaic kernels cannot be auto-partitioned by GSPMD: under a mesh the
    kernel needs an explicit shard_map (column-parallel path below) or
    the XLA fallback.  One definition lives in ops/pallas."""
    from ..ops.pallas import _active_mesh as impl
    return impl()


def _kernel_eligible(weight_scale, n_tokens) -> bool:
    """One definition of when the fused int4 kernel serves: per-channel
    scales and decode/serving token counts (prefill's big-M matmuls
    amortise the weight stream in XLA and would blow the kernel's VMEM
    x-tiles)."""
    return (weight_scale.ndim == 1 and n_tokens <= 256
            and _use_int4_kernel())


def _int8_kernel_eligible(weight_scale, n_tokens) -> bool:
    """Same shape gate for the fused int8 kernel: decode-sized token
    counts where the weight stream is the roofline."""
    return (weight_scale.ndim == 1 and n_tokens <= 256
            and _use_int8_kernel())


def _int8_matmul_fn():
    from ..ops.pallas.int8_matmul import int8_matmul
    return int8_matmul


def _n_tokens(x) -> int:
    n = 1
    for d in x.shape[:-1]:
        n *= d
    return n


def _kernel_column_sharded(matmul_fn, x2d, weight, scale, mesh):
    """shard_map'd quantized matmul kernel for the COLUMN-parallel
    layout: weight (K|K2, N) split over mp on N, per-channel scales
    split with it — each shard runs the kernel on its own columns and no
    cross-device reduction is needed (that is what makes column the safe
    case; row-parallel contracts over a sharded K and keeps the XLA
    path, whose psum GSPMD inserts).  The token dim rides the data axes
    when it divides them, so a dp-sharded serving batch is not gathered.
    Shared by the int4 and int8 kernels."""
    from ..core.compat import shard_map
    from jax.sharding import PartitionSpec as P

    data_axes = tuple(a for a in ("dp", "sharding")
                      if a in mesh.axis_names and mesh.shape[a] > 1)
    dsize = 1
    for a in data_axes:
        dsize *= mesh.shape[a]
    bt = data_axes if (data_axes and x2d.shape[0] % dsize == 0) else None

    f = shard_map(
        lambda a, w, s: matmul_fn(a, w, s),
        mesh=mesh,
        in_specs=(P(bt, None), P(None, "mp"), P("mp")),
        out_specs=P(bt, "mp"),
        check_vma=False)
    return f(x2d, weight, scale)


def _int4_matmul_fn():
    from ..ops.pallas.int4_matmul import int4_matmul
    return int4_matmul


def _pack_int4(q):
    """(in, out) int4-valued int8 -> (in//2, out) int8, two nibbles per
    byte: row 2i in the low nibble, row 2i+1 in the high nibble.  Packing
    along the CONTRACTION axis keeps out-channel scales per-column."""
    if q.shape[0] % 2:
        raise ValueError("int4 packing needs an even in_features "
                         f"(got {q.shape[0]})")
    lo = q[0::2] & 0x0F
    hi = jnp.left_shift(q[1::2], 4)
    return (lo | hi).astype(jnp.int8)


def _unpack_int4(packed):
    """Inverse of :func:`_pack_int4` — arithmetic shifts restore the sign
    of each nibble."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    n2, out = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * n2, out)


def weight_quantize(x, algo: str = "weight_only_int8", group_size: int = -1):
    """Quantize a (in_features, out_features) weight for weight-only
    serving.  Returns ``(quantized weight, scale)``:

    - int8: weight (in, out) int8, scale (out,) f32
    - int4: weight (in//2, out) int8 (packed nibbles), scale (out,) f32
    - group_size > 0: scale (in//group_size, out) f32 (per-group absmax,
      the reference's groupwise int4 mode)
    """
    _check_algo(algo)
    xf = jnp.asarray(x).astype(jnp.float32)
    if xf.ndim != 2:
        raise ValueError(f"weight must be 2-D (in, out); got {xf.shape}")
    qmax = _QMAX[algo]
    if group_size and group_size > 0:
        n_in, n_out = xf.shape
        if n_in % group_size:
            raise ValueError(f"in_features {n_in} not divisible by "
                             f"group_size {group_size}")
        g = xf.reshape(n_in // group_size, group_size, n_out)
        scale = jnp.max(jnp.abs(g), axis=1) / qmax + 1e-12
        q = jnp.round(g / scale[:, None, :]).reshape(n_in, n_out)
    else:
        scale = jnp.max(jnp.abs(xf), axis=0) / qmax + 1e-12
        q = jnp.round(xf / scale)
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    if algo == "weight_only_int4":
        q = _pack_int4(q)
    return q, scale


def weight_dequantize(x, scale, algo: str = "weight_only_int8",
                      group_size: int = -1, out_dtype=jnp.float32):
    """Reconstruct the float weight (the reference's weight_dequantize)."""
    _check_algo(algo)
    q = _unpack_int4(x) if algo == "weight_only_int4" else jnp.asarray(x)
    qf = q.astype(out_dtype)
    if scale.ndim == 2:  # groupwise
        n_in, n_out = qf.shape
        gs = group_size if group_size and group_size > 0 \
            else n_in // scale.shape[0]
        return (qf.reshape(-1, gs, n_out)
                * scale[:, None, :].astype(out_dtype)).reshape(n_in, n_out)
    return qf * scale.astype(out_dtype)


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype: str = "int8", group_size: int = -1):
    """y = x @ dequant(weight) + bias, with the weight stored int8/int4.

    Reference: paddle.nn.quant.weight_only_linear (fpA_intB Cutlass GEMM).
    Per-out-channel scales commute out of the contraction: the matmul
    reads raw int8 (widened in-register by Mosaic) and the scale is one
    fused multiply on the (tiny) output tile.  Groupwise scales can't
    commute, so that path dequantizes into the matmul fusion instead."""
    algo = ("weight_only_int4" if weight_dtype in ("int4", "weight_only_int4")
            else "weight_only_int8")
    x = jnp.asarray(x)
    if weight_scale is None:
        raise ValueError("weight_scale is required (from weight_quantize)")
    if (algo == "weight_only_int4" and _kernel_eligible(weight_scale, _n_tokens(x))
            and _active_mesh() is None):
        # Under an ACTIVE MESH this generic entry falls back to XLA (GSPMD
        # cannot auto-partition Mosaic kernels, and this entry cannot know
        # the caller's weight sharding); the column-parallel layer routes
        # through the explicit shard_map instead.
        # fused dequant-in-matmul Pallas kernel: nibbles unpacked in VMEM,
        # HBM streams the PACKED bytes.  The XLA formulation below
        # materialises the unpacked weight to HBM every call — measured
        # ~8x slower at 7B-shaped GEMVs (docs/BENCH.md round 5)
        lead = x.shape[:-1]
        y = _int4_matmul_fn()(x.reshape(-1, x.shape[-1]),
                              jnp.asarray(weight), weight_scale)
        y = y.reshape(*lead, y.shape[-1])
        return y if bias is None else y + bias
    if (algo == "weight_only_int8"
            and _int8_kernel_eligible(weight_scale, _n_tokens(x))
            and _active_mesh() is None):
        # fused int8 dequant-in-matmul (ops/pallas/int8_matmul.py): HBM
        # streams the raw int8 bytes, the widening + per-channel scale
        # run in VMEM — serving's decode GEMVs stop dequantizing in fp.
        # Same mesh caveat as int4: the column-parallel layer routes
        # multi-chip through the explicit shard_map instead.
        lead = x.shape[:-1]
        y = _int8_matmul_fn()(x.reshape(-1, x.shape[-1]),
                              jnp.asarray(weight), weight_scale)
        y = y.reshape(*lead, y.shape[-1])
        return y if bias is None else y + bias
    if weight_scale.ndim == 2:  # groupwise: dequant fuses into the dot
        w = weight_dequantize(weight, weight_scale, algo=algo,
                              group_size=group_size, out_dtype=x.dtype)
        y = x @ w
    else:
        q = _unpack_int4(weight) if algo == "weight_only_int4" \
            else jnp.asarray(weight)
        acc = jax.lax.dot_general(
            x, q.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y = (acc * weight_scale).astype(x.dtype)
    if bias is not None:
        y = y + bias
    return y


def llm_int8_linear(x, weight, weight_scale=None, threshold: float = 6.0):
    """LLM.int8() decomposition (reference:
    paddle.nn.quant.llm_int8_linear): activation features whose absmax
    exceeds ``threshold`` go through a float matmul against the
    dequantized weight rows; the rest go int8 x int8 into the MXU's
    int32 accumulator with dynamic per-token activation scales."""
    if weight_scale is None:
        raise ValueError("weight_scale is required (from weight_quantize)")
    x = jnp.asarray(x)
    q = jnp.asarray(weight)
    feat_max = jnp.max(jnp.abs(x.astype(jnp.float32)),
                       axis=tuple(range(x.ndim - 1)))
    outlier = feat_max > threshold                       # (in,)
    # int8 branch: zero outlier features out of the quantized path
    x_in = jnp.where(outlier, 0.0, x.astype(jnp.float32))
    x_scale = jnp.max(jnp.abs(x_in), axis=-1, keepdims=True) / 127.0 + 1e-12
    x_q = jnp.round(x_in / x_scale).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x_q, q, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y_int8 = acc.astype(jnp.float32) * x_scale * weight_scale
    # outlier branch: float matmul on the few loud features
    w_out = q.astype(jnp.float32) * weight_scale
    x_out = jnp.where(outlier, x.astype(jnp.float32), 0.0)
    y = (y_int8 + x_out @ w_out).astype(x.dtype)
    return y


class QuantizedLinear(Layer):
    """Weight-only replacement for ``nn.Layer`` Linears at serving time —
    created by :func:`quantize_linears`.  A real ``nn.Layer`` (so
    ``.eval()``/``state_dict()``/sublayer walks keep working) whose
    weight lives in int8/packed-int4 BUFFERS, not trainable parameters —
    weight-only quantization is a serving transform, not QAT."""

    def __init__(self, linear, algo: str = "weight_only_int8",
                 group_size: int = -1):
        super().__init__()
        self.in_features = linear.in_features
        self.out_features = linear.out_features
        self.algo = algo
        self.group_size = group_size
        qw, scale = weight_quantize(jnp.asarray(linear.weight), algo=algo,
                                    group_size=group_size)
        self.register_buffer("weight", qw)
        self.register_buffer("weight_scale", scale)
        self.register_buffer(
            "bias", None if linear.bias is None else jnp.asarray(linear.bias))
        self._wdtype = "int4" if algo == "weight_only_int4" else "int8"

    def forward(self, x):
        return weight_only_linear(x, self.weight, bias=self.bias,
                                  weight_scale=self.weight_scale,
                                  weight_dtype=self._wdtype,
                                  group_size=self.group_size)

    def extra_repr(self):
        return (f"in_features={self.in_features}, "
                f"out_features={self.out_features}, algo={self.algo}")


class QuantizedColumnParallelLinear(Layer):
    """Weight-only variant of distributed.ColumnParallelLinear — same
    activation sharding constraints, int8/int4 weight stream."""

    def __init__(self, host, algo="weight_only_int8", group_size=-1):
        super().__init__()
        self.gather_output = host.gather_output
        self.sequence_parallel = host.sequence_parallel
        self.out_features = host.out_features
        self.algo, self.group_size = algo, group_size
        qw, s = weight_quantize(jnp.asarray(host.weight), algo=algo,
                                group_size=group_size)
        self.register_buffer("weight", qw)
        self.register_buffer("weight_scale", s)
        self.register_buffer(
            "bias", None if host.bias is None else jnp.asarray(host.bias))
        self._wdtype = "int4" if algo == "weight_only_int4" else "int8"

    def forward(self, x):
        from ..distributed.mp_layers import act_constrain
        if self.sequence_parallel:
            x = act_constrain(x, "mp", None)
        mesh = _active_mesh()
        sharded_fn = None
        if mesh is not None and "mp" in mesh.axis_names:
            if self._wdtype == "int4" and _kernel_eligible(
                    self.weight_scale, _n_tokens(x)):
                sharded_fn = _int4_matmul_fn()
            elif self._wdtype == "int8" and _int8_kernel_eligible(
                    self.weight_scale, _n_tokens(x)):
                sharded_fn = _int8_matmul_fn()
        if sharded_fn is not None:
            # multi-chip serving: explicit shard_map over mp (column split
            # needs no reduction) — GSPMD cannot partition the kernel
            y = _kernel_column_sharded(
                sharded_fn, x.reshape(-1, x.shape[-1]), self.weight,
                self.weight_scale, mesh)
            y = y.reshape(*x.shape[:-1], y.shape[-1])
            if self.bias is not None:
                y = y + self.bias
        else:
            y = weight_only_linear(x, self.weight, bias=self.bias,
                                   weight_scale=self.weight_scale,
                                   weight_dtype=self._wdtype,
                                   group_size=self.group_size)
        return act_constrain(y, None,
                             None if self.gather_output else "mp")


class QuantizedRowParallelLinear(Layer):
    """Weight-only variant of distributed.RowParallelLinear."""

    def __init__(self, host, algo="weight_only_int8", group_size=-1):
        super().__init__()
        self.input_is_parallel = host.input_is_parallel
        self.sequence_parallel = host.sequence_parallel
        self.algo, self.group_size = algo, group_size
        qw, s = weight_quantize(jnp.asarray(host.weight), algo=algo,
                                group_size=group_size)
        self.register_buffer("weight", qw)
        self.register_buffer("weight_scale", s)
        self.register_buffer(
            "bias", None if host.bias is None else jnp.asarray(host.bias))
        self._wdtype = "int4" if algo == "weight_only_int4" else "int8"

    def forward(self, x):
        from ..distributed.mp_layers import act_constrain
        if self.input_is_parallel:
            x = act_constrain(x, None, "mp")
        y = weight_only_linear(x, self.weight, bias=None,
                               weight_scale=self.weight_scale,
                               weight_dtype=self._wdtype,
                               group_size=self.group_size)
        if self.sequence_parallel:
            y = act_constrain(y, "mp", None)
        else:
            y = act_constrain(y, None, None)
        if self.bias is not None:
            y = y + self.bias
        return y


def quantize_linears(model, algo: str = "weight_only_int8",
                     group_size: int = -1,
                     predicate: Optional[callable] = None) -> int:
    """Swap every Linear-like layer under ``model`` — ``nn.Linear``,
    ``distributed.ColumnParallelLinear``, ``distributed.RowParallelLinear``
    — for its weight-only quantized variant (in place), returning the
    swap count.  This is the serving-side entry point: run it on a model
    before ``generate()``/Predictor decode and every projection streams
    int8 — stacked with the int8 KV cache it attacks both halves of
    decode's HBM bytes.  ``predicate(name, layer) -> bool`` filters
    (e.g. skip ``lm_head`` for quality)."""
    from ..distributed.mp_layers import (ColumnParallelLinear,
                                         RowParallelLinear)
    from .layers_common import Linear

    swaps = {Linear: QuantizedLinear,
             ColumnParallelLinear: QuantizedColumnParallelLinear,
             RowParallelLinear: QuantizedRowParallelLinear}
    count = 0
    seen = set()
    stack = [model]
    while stack:
        layer = stack.pop()
        if id(layer) in seen:
            continue
        seen.add(id(layer))
        subs = getattr(layer, "_sub_layers", None)
        if not subs:
            continue
        for name, sub in list(subs.items()):
            cls = swaps.get(type(sub))
            if cls is not None and (predicate is None
                                    or predicate(name, sub)):
                # setattr, not subs[name]=: Layer.__setattr__ mirrors
                # sublayers into __dict__, and attribute access reads
                # __dict__ first — a dict-only swap leaves the float
                # layer live at every self.proj(x) call site
                setattr(layer, name, cls(sub, algo=algo,
                                         group_size=group_size))
                count += 1
            else:
                stack.append(sub)
    return count
