"""paddle Tensor METHOD surface on jax arrays.

Reference: python/paddle/tensor/tensor.prototype.pyi + the monkey-patch
in python/paddle/tensor/__init__.py — the reference installs every
tensor op as a Tensor method; ported code writes ``x.abs()``,
``x.unsqueeze(0)``, ``x.add_(y)`` at least as often as ``paddle.abs(x)``.

TPU-native mechanics: ``jax.Array``'s concrete type and the ``Tracer``
base class both accept attribute injection, so every op whose leading
argument is a tensor is installed as a bound method on BOTH — methods
work eagerly and inside ``jit`` traces identically.  jax-native
attributes are never overridden (jax semantics win on name collisions
like ``reshape``/``sum``, which already match the reference).

In-place ``_`` methods are value-returning, the package-wide deviation
documented at ops/tail3.py.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# ops/F names that the reference exposes as Tensor methods and whose
# first parameter is the tensor itself.  (Creation ops and multi-tensor
# utilities like meshgrid/concat are deliberately absent.)
_OPS_METHODS = """
abs acos acosh add addmm all allclose amax amin angle any argmax argmin
argsort as_complex as_real asin asinh atan atan2 atanh baddbmm bincount
bitwise_and bitwise_not bitwise_or bitwise_xor bmm broadcast_to bucketize
cast cdist ceil cholesky chunk clip clone concat conj copysign corrcoef
cos cosh count_nonzero cov cross cummax cummin cumprod cumsum deg2rad
diag diag_embed diagflat diagonal diff digamma dist divide dot
equal equal_all erf erfinv exp expand expand_as expm1 flatten flip
fliplr flipud floor floor_divide floor_mod fmax fmin frac frexp gather
gather_nd gcd greater_equal greater_than heaviside histogram hypot i0
i0e i1 i1e imag increment index_add index_fill index_put index_sample
index_select inner inverse is_complex is_empty is_floating_point
is_integer isclose isfinite isin isinf isnan kron kthvalue lcm ldexp
lerp less_equal less_than lgamma log log10 log1p log2 logcumsumexp
logical_and logical_not logical_or logical_xor logit logsumexp
masked_fill masked_scatter masked_select matmul maximum median
minimum mm mod mode moveaxis multigammaln multiplex multiply mv
nan_to_num nanmean nanmedian nanquantile nansum neg nextafter nonzero
norm not_equal numel outer polygamma pow prod put_along_axis quantile
rad2deg real reciprocal remainder renorm repeat_interleave roll rot90
round rsqrt scale scatter scatter_nd_add searchsorted sgn sign signbit
sin sinc sinh slice sort split sqrt square squeeze stanh std
strided_slice subtract t take take_along_axis tan tanh tensor_split
tile tolist topk trace tril triu trunc unbind unflatten unfold unique
unique_consecutive unsqueeze unstack vdot where
kthvalue lu qr svd eig eigvals pinv matrix_power slogdet
exp_ sqrt_ rsqrt_ reciprocal_ floor_ ceil_ round_ abs_ scale_ clip_
tanh_ add_ subtract_ multiply_ divide_ floor_divide_ remainder_ pow_
lerp_ erfinv_ trunc_ frac_ digamma_ lgamma_ neg_ zero_ fill_
fill_diagonal_ uniform_ normal_ bernoulli_ cauchy_ geometric_
exponential_ acos_ acosh_ asin_ asinh_ atan_ atan2_ atanh_ copysign_
cos_ cosh_ cumprod_ cumsum_ erf_ expm1_ flatten_ gammainc_ gammaincc_
gammaln_ hypot_ i0_ index_add_ lcm_ gcd_ ldexp_ log_ log10_ log1p_
log2_ logical_and_ logical_not_ logical_or_ logical_xor_ logit_
masked_fill_ masked_scatter_ multigammaln_ nan_to_num_ nextafter_
renorm_ reshape_ scatter_ sigmoid_ sin_ sinh_ square_ squeeze_ stanh_
t_ tan_ tril_ triu_ unsqueeze_ where_ polygamma_
""".split()

_F_METHODS = ["sigmoid", "softmax", "relu", "gelu", "tanh", "silu"]


def _bind(fn, name):
    def method(self, *args, **kwargs):
        return fn(self, *args, **kwargs)
    method.__name__ = name
    method.__qualname__ = f"Tensor.{name}"
    method.__doc__ = f"Tensor method form of paddle_tpu.{name} (reference: " \
                     f"paddle.Tensor.{name})."
    method.__module__ = __name__
    return method


# -- hand-written specials --------------------------------------------------

def _numpy(self):
    """Reference: Tensor.numpy() — host round-trip."""
    return np.asarray(self)


def _detach(self):
    """Reference: Tensor.detach() — value without gradient flow."""
    return jax.lax.stop_gradient(self)


def _clone(self):
    return jnp.copy(self)


def _dim(self):
    return self.ndim


def _rank_m(self):
    return self.ndim


def _element_size(self):
    return self.dtype.itemsize


def _cpu(self):
    return jax.device_put(self, jax.devices("cpu")[0])


def _cuda(self, device_id=0, blocking=True):
    accel = [d for d in jax.devices() if d.platform != "cpu"]
    return jax.device_put(self, accel[device_id] if accel else
                          jax.devices()[0])


def _pin_memory(self):
    return _cpu(self)


def _backward(self, grad_tensor=None, retain_graph=False):
    raise RuntimeError(
        "Tensor.backward(): paddle_tpu has no eager tape — use "
        "paddle_tpu.autograd.value_and_grad or the compiled TrainStep "
        "(docs/MIGRATION.md §autograd)")


def _set_value(self, value):
    raise RuntimeError(
        "Tensor.set_value(): jax arrays are immutable — rebind the name, "
        "or for Layer parameters use layer.set_state_dict")


_SPECIALS = {
    "numpy": _numpy, "detach": _detach, "clone": _clone, "dim": _dim,
    "ndimension": _dim, "rank": _rank_m, "element_size": _element_size,
    "cpu": _cpu, "cuda": _cuda, "pin_memory": _pin_memory,
    "backward": _backward, "set_value": _set_value,
}


def _place(self):
    """Reference: Tensor.place — the resident device as a Place object.

    Sharded arrays: ``.device`` is a Sharding (not a Device), so resolve
    through ``.devices()`` — the platform of the first device in the
    sharding (all devices of one array share a platform)."""
    from ..device import CPUPlace, TPUPlace
    if isinstance(self, jax.core.Tracer):
        return TPUPlace(0) if jax.default_backend() != "cpu" else CPUPlace()
    dev = None
    devs = getattr(self, "devices", None)
    if callable(devs):
        try:
            dev = next(iter(devs()))
        except Exception:
            dev = None
    if dev is None:
        dev = getattr(self, "device", None)
    platform = getattr(dev, "platform", None)
    if platform is None:  # unknown handle: fall back to the backend
        return TPUPlace(0) if jax.default_backend() != "cpu" else CPUPlace()
    if platform == "cpu":
        return CPUPlace()
    return TPUPlace(getattr(dev, "id", 0))

_installed = []


def install():
    """Install the method surface on the concrete array type and the
    Tracer base (idempotent).

    PROCESS-GLOBAL SIDE EFFECT (ADVICE r4): this patches jax's own
    ArrayImpl/Tracer classes, so every jax consumer in-process gains
    methods like ``.cpu()``/``.numpy()``/``.dim()`` — third-party code
    that duck-types tensor kinds via ``hasattr(x, "numpy")`` will now
    classify jax arrays as tensor-like.  That is the point (ported
    reference scripts call ``x.numpy()`` on our arrays), but it is
    opt-outable: set ``PDTPU_NO_TENSOR_METHODS=1`` before importing
    paddle_tpu and the jax classes stay untouched (paddle_tpu itself
    only needs the methods for reference-script parity, not its own
    operation).  Existing attributes are never overwritten."""
    import os
    if os.environ.get("PDTPU_NO_TENSOR_METHODS") == "1":
        return 0
    if _installed:
        return len(_installed)
    from .. import ops
    from ..nn import functional as F

    # the concrete array class WITHOUT creating an array: jnp.zeros(())
    # would initialise the XLA backend at import time, which breaks
    # multi-process workers (jax.distributed.initialize must come first)
    from jax._src.array import ArrayImpl as _ArrayImpl
    targets = [_ArrayImpl, jax.core.Tracer]
    seen = set()

    def put(name, fn):
        if name in seen:
            return
        seen.add(name)
        for t in targets:
            if not hasattr(t, name):
                try:
                    setattr(t, name, fn)
                except (AttributeError, TypeError):  # pragma: no cover
                    return
        _installed.append(name)

    for name in _OPS_METHODS:
        fn = getattr(ops, name, None)
        if callable(fn):
            put(name, _bind(fn, name))
    for name in _F_METHODS:
        fn = getattr(F, name, None)
        if callable(fn):
            put(name, _bind(fn, name))
    for name, fn in _SPECIALS.items():
        put(name, fn)
    # properties (attribute access, not calls) — only recorded as
    # installed if the class actually accepted the attribute
    place_ok = False
    for t in targets:
        if not hasattr(t, "place"):
            try:
                setattr(t, "place", property(_place))
                place_ok = True
            except (AttributeError, TypeError):  # pragma: no cover
                pass
    if place_ok and "place" not in _installed:
        _installed.append("place")
    return len(_installed)


def installed_names():
    return sorted(_installed)
