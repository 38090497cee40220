"""Step compiler (``paddle.jit`` parity, TPU-first).

The reference's whole static-graph stack — ``@to_static`` SOT capture
(python/paddle/jit/sot), ProgramDesc/PIR, InterpreterCore scheduling, CINN
codegen (SURVEY.md §2.3) — collapses on TPU into ``jax.jit``: one trace, XLA
fusion/scheduling, compiled-once execution.  This module provides:

- ``to_static(fn)``: jax.jit with paddle-like surface (input_spec accepted
  and used for AOT lowering).
- ``TrainStep``: THE canonical training path.  Wraps (model, loss_fn,
  optimizer, scaler) into one donated, sharded, compiled step function:
  state -> state.  All parallelism (mesh axes, param partition specs, ZeRO
  sharding of optimizer state) is applied here.
- ``save``/``load``: AOT export of compiled functions via StableHLO
  (``paddle.jit.save``'s inference-graph role).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import random as prandom
from ..nn.layer import Layer, functional_call, raw_params, trainable_mask
from ..observability import _state as _obs_state
from ..observability.regions import region as _region
from ..observability.spans import TRACE_PREFIX as _TRACE_PREFIX
from ..observability.spans import span as _span
from ..resilience import _state as _rs_state
from . import control_flow
from .control_flow import (GraphBreakError, case, cond, switch_case,
                           while_loop)


class InputSpec:
    """``paddle.static.InputSpec`` parity.  Dynamic dims (None/-1) are not
    representable in XLA's static-shape model; AOT warm-up skips them."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def is_static(self) -> bool:
        return all(isinstance(d, int) and d >= 0 for d in self.shape)

    def to_shape_struct(self):
        from ..core import convert_dtype
        return jax.ShapeDtypeStruct(self.shape, convert_dtype(self.dtype))


def to_static(function=None, input_spec=None, full_graph=True, backend=None,
              donate_argnums=(), static_argnums=(),
              convert_control_flow=True):
    """``paddle.jit.to_static`` parity → jax.jit.

    With a fully-static ``input_spec`` the function is AOT-lowered and
    compiled immediately (the reference's program-capture step); dynamic
    dims fall back to lazy shape-specialised jit with a warning.

    ``convert_control_flow=True`` (default) applies the SOT-lite AST
    transform (reference: python/paddle/jit/sot): plain Python ``if`` /
    ``while`` on traced values are rewritten into ``lax.cond`` /
    ``lax.while_loop`` automatically; unconvertible patterns keep the
    graph-break diagnostic / eager-fallback behavior.
    """
    def deco(fn):
        if getattr(fn, "_pdtpu_not_to_static", False):
            return fn
        target = fn
        # SOT conversion is skipped for functions whose defining module
        # was registered via jit.ignore_module (the transform is local to
        # the decorated function, so the decoration site is the scope).
        # With enable_to_static(False) active at DECORATION time, the
        # transform and the eager AOT compile below are also skipped —
        # debugging mode must not mutate layer.forward or trigger XLA
        # (re-enabling later jits the unconverted function).
        skip_sot = (getattr(target, "__module__", None) in _IGNORED_MODULES
                    or not _TO_STATIC_ENABLED[0])
        if convert_control_flow and not skip_sot:
            from . import sot as _sot
            from ..nn.layer import Layer
            if isinstance(fn, Layer):
                converted, ok = _sot.convert_control_flow(fn.forward)
                if ok:
                    # instance attribute shadows the class method; hooks
                    # and __call__ plumbing stay intact
                    fn.forward = converted
            else:
                target, _ = _sot.convert_control_flow(fn)
        jitted = jax.jit(target, donate_argnums=donate_argnums,
                         static_argnums=static_argnums)
        if not isinstance(fn, type) and callable(fn) and hasattr(fn, "__name__"):
            functools.update_wrapper(jitted, fn, updated=[])
        if input_spec and _TO_STATIC_ENABLED[0]:
            specs = [s if isinstance(s, InputSpec) else InputSpec(*s)
                     for s in input_spec]
            if all(s.is_static() for s in specs):
                jitted.lower(*[s.to_shape_struct() for s in specs]).compile()
            else:
                import warnings
                warnings.warn(
                    "to_static input_spec has dynamic dims; XLA requires "
                    "static shapes — compiling lazily per concrete shape "
                    "instead", stacklevel=2)
        compiled = control_flow.intercept_graph_breaks(fn, jitted,
                                                       full_graph)

        # enable_to_static is a CALL-time switch (reference semantics:
        # flipping it off routes already-decorated functions to eager)
        site = f"to_static({getattr(fn, '__name__', type(fn).__name__)})"

        def dispatch(*args, **kwargs):
            if not _TO_STATIC_ENABLED[0]:
                return fn(*args, **kwargs)
            mon = _obs_state.MONITOR[0]
            if mon is not None:
                with mon.compile_site(site):
                    return compiled(*args, **kwargs)
            return compiled(*args, **kwargs)

        if callable(fn) and hasattr(fn, "__name__"):
            functools.update_wrapper(dispatch, fn, updated=[])
        dispatch._pdtpu_compiled = compiled
        return dispatch
    return deco(function) if function is not None else deco


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

def _spec_of(meta_partition, ndim) -> P:
    if meta_partition is None:
        return P()
    if isinstance(meta_partition, P):
        return meta_partition
    return P(*meta_partition)


ZERO_MIN_SIZE = 2048  # numel below which zero-sharding isn't worth the comm


def zero_shard_spec(spec: P, shape, axis_name: str, axis_size: int,
                    min_size: int = ZERO_MIN_SIZE) -> P:
    """ZeRO-style sharding: additionally shard over ``axis_name`` on the
    first dim that is divisible and not already sharded.

    This is how ZeRO-1/2/3 semantics (reference:
    dygraph_sharding_optimizer.py / group_sharded_stage3.py) map to GSPMD:
    the stage choreography (reduce-to-owner, broadcast, allgather/release)
    becomes a sharding annotation and XLA inserts the moving parts
    (SURVEY.md §7.2).  Small tensors stay replicated (the reference's
    segment_size bucketing serves the same purpose).
    """
    if axis_size <= 1:
        return spec
    n = 1
    for d in shape:
        n *= d
    if n < min_size:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    if any(axis_name in (e if isinstance(e, tuple) else (e,))
           for e in entries):
        # already ZeRO-sharded over this axis (e.g. the param spec passed
        # through stage-3 before the opt-state pass re-applies): sharding
        # twice is meaningless and an invalid NamedSharding.  Surfaced by
        # the MoE router gate (4096, 8) whose free dim-1 is divisible by
        # the axis size — llama params dodge it only because 'mp'
        # annotations occupy every dim.
        return spec
    for i, (dim, cur) in enumerate(zip(shape, entries)):
        if cur is None and dim % axis_size == 0:
            entries[i] = axis_name
            return P(*entries)
    return spec  # nothing divisible; leave replicated


def _named(mesh, spec, host=False):
    # without trailing Nones — the spelling jax gives a compiled step's
    # outputs.  P("mp", None) places an array exactly as P("mp") does but
    # is another jit-cache key, so a state spelled the long way going in
    # would compile the step a second time when it came back out.
    parts = tuple(spec)
    while parts and parts[-1] is None:
        parts = parts[:-1]
    spec = P(*parts)
    if host:
        return NamedSharding(mesh, spec, memory_kind="pinned_host")
    return NamedSharding(mesh, spec)


def _zero_over(spec, shape, axes, mesh):
    for ax in axes:
        spec = zero_shard_spec(spec, shape, ax, mesh.shape[ax])
    return spec


# ---------------------------------------------------------------------------
# TrainStep
# ---------------------------------------------------------------------------

class TrainStep:
    """Compiled, sharded training step.

    Usage::

        model = Llama(cfg)
        opt = optimizer.AdamW(learning_rate=sched, parameters=model.parameters())
        step = TrainStep(model, loss_fn, opt, mesh=topo.mesh)
        state = step.init_state(seed=0)
        state, metrics = step(state, batch)

    ``loss_fn(model, batch) -> scalar`` runs with parameters functionally
    swapped in, so inside it the model is called exactly like eager paddle
    code.  The whole step (fwd, bwd, clip, optimizer, scaler) is one XLA
    program with the state donated (in-place buffer reuse, reference:
    InterpreterCore inplace pass).
    """

    # calls so far, counted on the host: the step number of the profiler's
    # ``pdtpu.train`` step event (no device read; a class default, so an
    # instance built without __init__ counts too)
    _calls = 0

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 scaler=None, mesh: Optional[Mesh] = None,
                 batch_axes=("dp", "sharding"), batch_spec=None,
                 zero_stage: Optional[int] = None,
                 zero_axes=("dp", "sharding"),
                 extra_metrics: Optional[Callable] = None,
                 gradient_accumulation: Optional[bool] = None):
        from ..distributed.parallel import DataParallel
        from ..distributed.sharding import zero_offload_of, zero_stage_of
        self.model = model
        # DataParallel's no_sync() drives per-call accumulation; carrying
        # acc-grad buffers in the state costs memory, so they exist only
        # when the wrapper (or an explicit flag) asks for them
        self._accum = (isinstance(model, DataParallel)
                       if gradient_accumulation is None
                       else bool(gradient_accumulation))
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.scaler = scaler
        if mesh is None:
            # fleet.init() was called → pick up the global hybrid mesh
            # (paddle semantics: fleet state is process-global)
            from ..distributed import fleet as _fleet
            hcg = _fleet.get_hybrid_communicate_group()
            if hcg is not None:
                mesh = hcg.mesh
        self.mesh = mesh
        # group_sharded_parallel records the stage on the optimizer; an
        # explicit zero_stage argument (including 0 = force off) wins
        self.zero_stage = zero_stage_of(optimizer, zero_stage)
        self.zero_offload = zero_offload_of(optimizer)
        self.extra_metrics = extra_metrics
        if mesh is not None:
            present = [a for a in batch_axes if a in mesh.axis_names
                       and mesh.shape[a] > 1]
            self.batch_spec = batch_spec if batch_spec is not None else (
                P(tuple(present)) if present else P())
            self.zero_axes = [a for a in zero_axes if a in mesh.axis_names
                              and mesh.shape[a] > 1]
        else:
            self.batch_spec = P()
            self.zero_axes = []
        self._mask = trainable_mask(model)
        self._compiled = jax.jit(self._step, donate_argnums=(0,),
                                 static_argnums=(2,))
        self._site = f"TrainStep({type(model).__name__})"

    # -- sharding specs ----------------------------------------------------

    def param_specs(self) -> Dict[str, P]:
        meta = self.model.param_meta()
        params = raw_params(self.model)
        specs = {}
        for name, p in params.items():
            spec = _spec_of(meta[name].partition if name in meta else None, p.ndim)
            if self.zero_stage >= 3:
                spec = _zero_over(spec, p.shape, self.zero_axes, self.mesh)
            specs[name] = spec
        return specs

    def grad_specs(self, grads, param_specs) -> Dict[str, P]:
        """ZeRO-2+: gradients sharded like the optimizer states, so the
        grad all-reduce becomes a reduce-scatter (reference:
        GroupShardedOptimizerStage2 grad partitioning)."""
        if self.zero_stage < 2 or self.mesh is None:
            return {k: param_specs[k] for k in grads}
        return {k: _zero_over(param_specs[k], grads[k].shape,
                              self.zero_axes, self.mesh)
                for k in grads}

    def opt_state_specs(self, opt_state, param_specs) -> Any:
        """Optimizer slots/master weights: mirror param sharding; ZeRO>=1
        additionally shards them over the data axes."""
        def spec_for(path_name, leaf):
            base = param_specs.get(path_name, P())
            if self.zero_stage >= 1 and hasattr(leaf, "ndim") and leaf.ndim > 0:
                base = _zero_over(base, leaf.shape, self.zero_axes, self.mesh)
            return base

        out = {}
        for slot, val in opt_state.items():
            if isinstance(val, dict):
                out[slot] = {k: spec_for(k, v) if v is not None else None
                             for k, v in val.items()}
            else:
                out[slot] = P()
        return out

    # -- state -------------------------------------------------------------

    def init_state(self, seed: int = 0) -> Dict[str, Any]:
        params = raw_params(self.model)
        opt_state = self.optimizer.init(params)
        state = {"params": params, "opt": opt_state,
                 "step": jnp.zeros((), jnp.int32),
                 "rng": jax.random.key(seed)}
        if self._accum:
            state["acc_grads"] = {
                k: jnp.zeros_like(v) for k, v in params.items()
                if self._mask.get(k, True)}
        if self.scaler is not None and self.scaler.enable:
            state["scaler"] = self.scaler.init_state()
            if self._accum:
                state["scaler"]["acc_found_inf"] = jnp.asarray(False)
        return self.shard_state(state)

    def abstract_state(self) -> Dict[str, Any]:
        """Abstract (ShapeDtypeStruct) analogue of
        ``init_state()+shard_state()`` for AOT lowering: every leaf carries
        its shape, dtype, and target sharding, but nothing materialises.
        Works with ``nn.meta_init()``-constructed models, so a 70B step can
        be compiled and memory-analysed on a host that could never hold it
        (tools/memproof.py; SURVEY §6 HBM-highwater validation)."""
        if self.mesh is None:
            raise ValueError("abstract_state requires a mesh")
        pspecs = self.param_specs()
        params = raw_params(self.model)

        def struct(leaf, spec, host=False):
            return jax.ShapeDtypeStruct(
                tuple(leaf.shape), leaf.dtype,
                sharding=_named(self.mesh, spec, host=host))

        aparams = {k: struct(v, pspecs[k]) for k, v in params.items()}
        opt_abs = jax.eval_shape(self.optimizer.init, aparams)
        ospecs = self.opt_state_specs(opt_abs, pspecs)
        host = self.zero_offload
        opt = {}
        for slot, val in opt_abs.items():
            if isinstance(val, dict):
                opt[slot] = {k: (struct(v, ospecs[slot][k], host=host)
                                 if v is not None else None)
                             for k, v in val.items()}
            else:
                opt[slot] = struct(val, P())
        rng = jax.eval_shape(lambda: jax.random.key(0))
        state = {"params": aparams, "opt": opt,
                 "step": jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=_named(self.mesh, P())),
                 "rng": jax.ShapeDtypeStruct(rng.shape, rng.dtype,
                                             sharding=_named(self.mesh, P()))}
        if self._accum:
            gspecs = self.grad_specs(
                {k: v for k, v in aparams.items()
                 if self._mask.get(k, True)}, pspecs)
            state["acc_grads"] = {
                k: struct(aparams[k], gspecs[k]) for k in gspecs}
        if self.scaler is not None and self.scaler.enable:
            sc = jax.eval_shape(self.scaler.init_state)
            state["scaler"] = jax.tree.map(
                lambda v: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=_named(self.mesh, P())), sc)
            if self._accum:
                state["scaler"]["acc_found_inf"] = jax.ShapeDtypeStruct(
                    (), jnp.bool_, sharding=_named(self.mesh, P()))
        return state

    def shard_state(self, state):
        if self.mesh is None:
            return state
        pspecs = self.param_specs()
        ospecs = self.opt_state_specs(state["opt"], pspecs)
        with self.mesh:
            state["params"] = {
                k: jax.device_put(v, _named(self.mesh, pspecs[k]))
                for k, v in state["params"].items()}
            new_opt = {}
            # offload: optimizer states live in pinned host memory; XLA
            # inserts the transfers around the sharded update
            host = self.zero_offload
            for slot, val in state["opt"].items():
                if isinstance(val, dict):
                    new_opt[slot] = {
                        k: (jax.device_put(v, _named(self.mesh,
                                                     ospecs[slot][k],
                                                     host=host))
                            if v is not None else None)
                        for k, v in val.items()}
                else:
                    new_opt[slot] = jax.device_put(val, _named(self.mesh, P()))
            state["opt"] = new_opt
            if "acc_grads" in state:
                gspecs = self.grad_specs(state["acc_grads"], pspecs)
                state["acc_grads"] = {
                    k: jax.device_put(v, _named(self.mesh, gspecs[k]))
                    for k, v in state["acc_grads"].items()}
            state["step"] = jax.device_put(state["step"], _named(self.mesh, P()))
            # the rng key must be a mesh-replicated global array too —
            # otherwise a checkpoint-restored key stays committed to one
            # device and conflicts with the mesh-sharded state under jit.
            # device_put rejects typed key arrays on multi-process
            # shardings, so replicate the raw key_data and re-wrap.
            # (rng-less states — e.g. params/opt-only dicts fed through
            # Engine.load — pass through untouched)
            if "rng" in state:
                impl = str(jax.random.key_impl(state["rng"]))
                key_data = jax.device_put(jax.random.key_data(state["rng"]),
                                          _named(self.mesh, P()))
                state["rng"] = jax.random.wrap_key_data(key_data, impl=impl)
        return state

    # -- the step ----------------------------------------------------------

    def _loss(self, train_params, frozen, batch, key, scaler_state):
        from ..nn.layer import _swapped_params, _train_mode
        params = {**frozen, **train_params}
        # "forward" is the parent of the model's regions
        # (observability/regions.py); autodiff carries the names into the
        # backward pass
        with jax.named_scope("forward"), _swapped_params(self.model, params), \
                _train_mode(self.model, True), prandom.rng_scope(key):
            loss = self.loss_fn(self.model, batch)
        scaled = loss
        if self.scaler is not None and self.scaler.enable:
            scaled = self.scaler.scale_value(loss, scaler_state)
        return scaled, loss

    def _step(self, state, batch, accumulate=False):
        mesh = self.mesh
        if self.zero_offload and mesh is not None:
            # offloaded optimizer states live in pinned host memory between
            # steps; XLA compute requires device space, so the step opens
            # with an explicit host->HBM transfer (and closes with the
            # device_put back to host below)
            ospecs = self.opt_state_specs(state["opt"], self.param_specs())
            opt_dev = {}
            for slot, val in state["opt"].items():
                if isinstance(val, dict):
                    opt_dev[slot] = {
                        k: (jax.device_put(v, _named(mesh, ospecs[slot][k]))
                            if v is not None else None)
                        for k, v in val.items()}
                else:
                    opt_dev[slot] = val
            state = {**state, "opt": opt_dev}
        if mesh is not None:
            batch = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, _named(mesh, self.batch_spec)) if hasattr(x, "ndim") and x.ndim > 0 else x,
                batch)
        params = state["params"]
        train = {k: v for k, v in params.items() if self._mask.get(k, True)}
        frozen = {k: v for k, v in params.items() if not self._mask.get(k, True)}
        key = jax.random.fold_in(state["rng"], state["step"])
        scaler_state = state.get("scaler")
        grad_fn = jax.value_and_grad(self._loss, has_aux=True)
        (scaled, loss), grads = grad_fn(train, frozen, batch, key, scaler_state)
        if self.scaler is not None and self.scaler.enable:
            grads, scaler_state = self.scaler.unscale_and_update(grads, scaler_state)
        if accumulate:
            # no_sync microstep (reference: DataParallel.no_sync suppresses
            # the Reducer all-reduce): stage grads by SUM — callers scale
            # the loss by 1/accumulate_steps, exactly as with the
            # reference — and leave params/optimizer untouched
            new_state = {
                **state,
                "acc_grads": {k: state["acc_grads"][k] + g
                              for k, g in grads.items()},
                "step": state["step"] + 1}
            if scaler_state is not None:
                new_state["scaler"] = {
                    k: scaler_state[k]
                    for k in ("scale", "good_steps", "bad_steps")}
                # overflow on ANY microstep must skip the whole accumulated
                # update (reference scaler semantics) — sticky until the
                # update step consumes it
                new_state["scaler"]["acc_found_inf"] = (
                    state["scaler"].get("acc_found_inf", jnp.asarray(False))
                    | scaler_state.get("found_inf", jnp.asarray(False)))
            metrics = {"loss": loss,
                       "lr": _current_lr(self.optimizer,
                                         {"step": state["opt"]["step"]})}
            if self.extra_metrics is not None:
                metrics.update(self.extra_metrics(new_state, batch))
            return new_state, metrics
        if "acc_grads" in state:
            grads = {k: g + state["acc_grads"][k] for k, g in grads.items()}
            if scaler_state is not None and "found_inf" in scaler_state:
                scaler_state = {
                    **scaler_state,
                    "found_inf": scaler_state["found_inf"]
                    | state["scaler"].get("acc_found_inf",
                                          jnp.asarray(False))}
        if mesh is not None:
            pspecs = self.param_specs()
            gspecs = self.grad_specs(grads, pspecs)
            grads = {k: jax.lax.with_sharding_constraint(
                g, _named(mesh, gspecs[k])) for k, g in grads.items()}
        with _region("optimizer"):
            new_params, new_opt = self.optimizer.apply(grads, state["opt"], params)
        if scaler_state is not None and "found_inf" in scaler_state:
            # paddle GradScaler semantics: skip the whole optimizer step on
            # overflow (moments/step-count must not advance either)
            keep_old = scaler_state["found_inf"]
            sel = lambda old, new: jax.tree.map(
                lambda o, n: jnp.where(keep_old, o, n) if o is not None else None,
                old, new, is_leaf=lambda x: x is None)
            new_params = sel(params, new_params)
            new_opt = sel(state["opt"], new_opt)
        if mesh is not None:
            # the state leaves the step laid out as shard_state() laid it
            # out going in.  Left to itself XLA keeps e.g. ZeRO-1's
            # updated params in the optimizer's sharded layout: the next
            # call then misses the jit cache, compiles a second program
            # and trains on with a layout nobody asked for (seen on the
            # first four-chip run: 13 s second step, CHANGES.md PR 21).
            # Offloaded optimizer states go back to pinned host memory —
            # without that the donated step writes them to HBM and the
            # offload silently ends after one step.
            ospecs = self.opt_state_specs(new_opt, pspecs)
            new_params = {k: jax.lax.with_sharding_constraint(
                v, _named(mesh, pspecs[k])) for k, v in new_params.items()}
            place = jax.device_put if self.zero_offload \
                else jax.lax.with_sharding_constraint
            new_opt = {
                slot: ({k: (place(v, _named(mesh, ospecs[slot][k],
                                            host=self.zero_offload))
                            if v is not None else None)
                        for k, v in val.items()}
                       if isinstance(val, dict) else val)
                for slot, val in new_opt.items()}
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1, "rng": state["rng"]}
        if "acc_grads" in state:
            new_state["acc_grads"] = {
                k: jnp.zeros_like(v) for k, v in state["acc_grads"].items()}
        if scaler_state is not None:
            new_state["scaler"] = {k: scaler_state[k]
                                   for k in ("scale", "good_steps", "bad_steps")}
            if "acc_grads" in state:
                new_state["scaler"]["acc_found_inf"] = jnp.asarray(False)
        # lr from the OPTIMIZER's step counter (it does not advance on
        # overflow-skipped steps, unlike the outer step counter)
        metrics = {"loss": loss,
                   "lr": _current_lr(self.optimizer, {"step": state["opt"]["step"]})}
        if self.extra_metrics is not None:
            metrics.update(self.extra_metrics(new_state, batch))
        return new_state, metrics

    def __call__(self, state, batch, accumulate: Optional[bool] = None):
        if accumulate is None:
            # DataParallel.no_sync() context → accumulate this call
            accumulate = not getattr(self.model, "_grad_sync", True)
        if accumulate and not self._accum:
            raise RuntimeError(
                "gradient accumulation requested but this TrainStep was "
                "built without buffers: wrap the model in "
                "paddle_tpu.DataParallel or pass gradient_accumulation=True")
        # fault-injection site "step": same one-falsy-check discipline as
        # the telemetry hook below (enforced by the same CI gate); fires
        # BEFORE the compiled call so the incoming state is never donated
        # when the supervisor catches the injected failure
        fi = _rs_state.FAULTS[0]
        if fi is not None:
            fi("step")
        # telemetry: exactly ONE falsy check on the disabled path (the
        # distributed/debug.py zero-overhead contract, enforced by the
        # telemetry-overhead CI gate)
        mon = _obs_state.MONITOR[0]
        if mon is not None:
            return mon.timed_step(
                self._site, self.model, batch,
                lambda: self._run(state, batch, accumulate))
        return self._run(state, batch, accumulate)

    def _run(self, state, batch, accumulate):
        # one step event per call on the host timeline of whatever
        # profiler session is live (TraceMe's own check when none is), on
        # every path: the harness never enables telemetry
        n = self._calls
        self._calls = n + 1
        if not jax.profiler.TraceAnnotation.is_enabled():
            return self._dispatch(state, batch, accumulate)
        with jax.profiler.StepTraceAnnotation(_TRACE_PREFIX + "train",
                                              step_num=n):
            return self._dispatch(state, batch, accumulate)

    def _dispatch(self, state, batch, accumulate):
        if self.mesh is not None:
            with self.mesh:
                return self._compiled(state, batch, accumulate)
        return self._compiled(state, batch, accumulate)

    def lower(self, state, batch):
        # same mesh context as __call__: kernel dispatch (shard_map wrapping
        # of Pallas calls) keys off the active physical mesh during tracing
        if self.mesh is not None:
            with self.mesh:
                return self._compiled.lower(state, batch, False)
        return self._compiled.lower(state, batch, False)


def _current_lr(optimizer, state):
    from ..optimizer import LRScheduler
    lr = optimizer._learning_rate
    if isinstance(lr, LRScheduler):
        return lr.lr_at(state["step"])
    return jnp.asarray(lr, jnp.float32)


# ---------------------------------------------------------------------------
# AOT export (paddle.jit.save / load parity for inference graphs)
# ---------------------------------------------------------------------------

def save(fn, path: str, *example_args, input_spec=None):
    """Serialize a jitted function to StableHLO bytes + npz side-car.

    Reference: paddle.jit.save -> *.pdmodel/*.pdiparams, whose signature
    takes either example tensors or ``input_spec=[InputSpec(...)]``.
    Here the "model" is a serialized StableHLO program (jax.export) that
    can be reloaded and executed without the Python model definition.
    """
    from jax import export as jexport
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    if input_spec is not None and not example_args:
        specs = [s if isinstance(s, InputSpec) else InputSpec(*s)
                 for s in input_spec]
        example_args = tuple(s.to_shape_struct() for s in specs)
    # span: AOT export traces + lowers the whole program — a multi-second
    # cold op worth a first-class slot in the trace/JSONL vocabulary
    with _span("jit.save", path=path):
        exp = jexport.export(jitted)(*example_args)
        with open(path + ".stablehlo", "wb") as f:
            f.write(exp.serialize())
    return path + ".stablehlo"


def load(path: str):
    from jax import export as jexport
    with _span("jit.load", path=path):
        with open(path if path.endswith(".stablehlo") else path + ".stablehlo", "rb") as f:
            exp = jexport.deserialize(f.read())
    return TranslatedLayer(exp.call, path)


# ---------------------------------------------------------------------------
# conversion controls (reference: paddle.jit.{enable_to_static,
# not_to_static, ignore_module} — python/paddle/jit/api.py and
# sot/opcode_translator skip lists)
# ---------------------------------------------------------------------------

_TO_STATIC_ENABLED = [True]
_IGNORED_MODULES: set = set()


def enable_to_static(enable: bool = True):
    """Globally toggle to_static conversion: when off, decorated
    functions run eagerly (the reference's debugging switch)."""
    _TO_STATIC_ENABLED[0] = bool(enable)


def not_to_static(function=None):
    """Decorator: mark a function to stay eager inside to_static capture
    (its body executes at trace time as plain Python)."""
    def mark(fn):
        fn._pdtpu_not_to_static = True
        return fn
    return mark(function) if function is not None else mark


def ignore_module(modules):
    """Register modules whose functions the SOT transform must leave
    untouched (reference: sot skip-module list)."""
    for m in (modules if isinstance(modules, (list, tuple)) else [modules]):
        _IGNORED_MODULES.add(getattr(m, "__name__", str(m)))
    return _IGNORED_MODULES


class TranslatedLayer:
    """Reference: paddle.jit.TranslatedLayer — the callable a jit.load
    returns, Layer-shaped (``__call__``/``eval``/``train`` no-ops for
    inference artifacts).  Wraps the deserialized StableHLO callable."""

    def __init__(self, fn, path=None):
        self._fn = fn
        self._path = path
        self.training = False

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)

    forward = __call__

    def eval(self):
        self.training = False
        return self

    def train(self):
        raise RuntimeError(
            "TranslatedLayer is an inference artifact (AOT StableHLO); "
            "training needs the original Layer")


# public namespace hygiene: no foreign-module re-exports (tools/check_api_compat)
from paddle_tpu._export import public_all as _public_all
__all__ = _public_all(globals())
