"""What the plain references share: how a matrix product is rounded, the
attention core, the loss, AdamW with global-norm clipping, and the
numbers a training cell compares.  ``jax.numpy`` in float32 with
``precision=HIGHEST``; nothing here imports the program.

A *family* module (``llama_ref``, ``gpt_ref``, ``evabyte_ref``,
``qwen3_next_ref``) gives:

- ``layer_kind(cfg, i)``: a small hashable tag for the equations of
  layer ``i`` (a family whose layers are all alike returns one tag);
- ``layer_shapes(cfg, i)``: layer ``i``'s leaves, short name -> shape;
- ``layer(x, lp, cfg, prec, kind)``: one layer of that kind over one
  sequence ``(S, h)``, with ``lp`` the layer's own leaves by their short
  names;
- ``embed(params, ids, cfg)``, ``head(x, params, cfg, prec)`` -> logits,
  ``layer_prefix(i)`` for the names of layer ``i``'s leaves and
  ``outer_names(cfg)`` for the leaves outside the layers.

Layers of one kind may hold the same leaves and differ in their
equations alone (a window against full attention, RoPE against none):
the kind, not the leaves, says which.
"""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Precision:
    """How the reference multiplies matrices.

    ``f32``   the reference proper: float32 operands, six-pass products.
    ``bf16``  operands rounded to bfloat16 (what the configurations state).
    ``fp8``   the control: operands of every linear layer rounded to
              float8's e4m3 (4 exponent bits, 3 of mantissa) after a
              per-tensor scale to its range, the step below bfloat16 that
              would tempt a later PR.  The rounding is
              straight-through, so the backward pass sees the rounded
              forward values and an unrounded cotangent.
    """

    MODES = ("f32", "bf16", "fp8")

    def __init__(self, mode: str = "f32"):
        if mode not in self.MODES:
            raise ValueError(f"precision {mode!r} not in {self.MODES}")
        self.mode = mode

    def _round(self, a):
        # lax.reduce_precision is an operation of its own: a pair of
        # converts (f32 -> bf16 -> f32) is what XLA's TPU compiler removes
        # as excess precision, which left the rounding undone on the chip
        if self.mode == "f32":
            return a
        if self.mode == "bf16":
            q = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        else:
            amax = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
            s = 224.0 / amax        # e4m3's largest normal is 240
            q = jax.lax.reduce_precision(a * s, exponent_bits=4,
                                         mantissa_bits=3) / s
        return a + jax.lax.stop_gradient(q - a)

    def mm(self, x, w):
        """``x @ w`` for a linear layer."""
        return jnp.matmul(self._round(x), self._round(w), precision=HIGHEST)


def causal_attention(q, k, v, window: int = None, block: int = 512):
    """q ``(S, H, D)``, k and v ``(S, KV, D)`` with ``H = G * KV`` (query
    head ``i`` reads KV head ``i // G``): causal softmax attention in
    which query ``t`` sees key ``s`` when ``0 <= t - s < window`` (every
    ``s <= t`` when ``window`` is None).  Returns ``(S, H * D)``.

    One query head at a time and ``block`` query rows at a time, each
    recomputed in the backward pass, so that one ``block x S`` slab of
    scores lives at once (``block x (block + window - 1)`` where a window
    leaves the rest of the keys unseen), and never an ``S x S`` one."""
    s, h, d = q.shape
    g = h // k.shape[1]
    block = min(block, s)
    nb = -(-s // block)
    # keys a block of queries can see: all of them, or a slice that
    # covers the block's rows and the window behind its first
    span = s if window is None or block + window - 1 >= s \
        else block + window - 1
    qh = jnp.pad(q, ((0, nb * block - s), (0, 0), (0, 0))).transpose(1, 0, 2)
    kh = jnp.repeat(k.transpose(1, 0, 2), g, axis=0)       # (H, S, D)
    vh = jnp.repeat(v.transpose(1, 0, 2), g, axis=0)

    def one_head(args):
        q1, k1, v1 = args                                  # (nb*B, D), (S, D)

        @jax.checkpoint
        def one_block(b):
            qs = jax.lax.dynamic_slice_in_dim(q1, b * block, block)
            lo = jnp.clip(b * block - (span - block), 0, s - span)
            ks = jax.lax.dynamic_slice_in_dim(k1, lo, span)
            vs = jax.lax.dynamic_slice_in_dim(v1, lo, span)
            # the padded rows past S read as row S - 1, so that none of
            # them is left with no key (and a NaN in the backward pass)
            t = jnp.minimum(b * block + jnp.arange(block), s - 1)[:, None]
            dist = t - (lo + jnp.arange(span))[None, :]
            seen = dist >= 0
            if window is not None:
                seen &= dist < window
            sc = jnp.einsum("qd,kd->qk", qs, ks, precision=HIGHEST)
            sc = sc * (1.0 / d ** 0.5)
            sc = jnp.where(seen, sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("qk,kd->qd", p, vs, precision=HIGHEST)

        return jax.lax.map(one_block, jnp.arange(nb)).reshape(nb * block, d)

    out = jax.lax.map(jax.checkpoint(one_head), (qh, kh, vh))  # (H, nb*B, D)
    return out[:, :s].transpose(1, 0, 2).reshape(s, h * d)


def cross_entropy_sum(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - picked)


def layer_leaves(family, params: dict, cfg, i: int) -> dict:
    """Layer ``i``'s leaves by their short names."""
    prefix = family.layer_prefix(i)
    return {k: params[prefix + k] for k in family.layer_shapes(cfg, i)}


def sequence_hidden(family, params, ids, cfg, prec, layers: int):
    """The last layer's output ``(S, h)`` for one sequence."""
    x = family.embed(params, ids, cfg)
    for i in range(layers):
        kind = family.layer_kind(cfg, i)
        x = jax.checkpoint(
            lambda x, lp, kind=kind: family.layer(x, lp, cfg, prec, kind))(
                x, layer_leaves(family, params, cfg, i))
    return x


def sequence_logits(family, params, ids, cfg, prec, layers: int):
    """Logits ``(S, V)`` of one sequence through ``layers`` layers."""
    return family.head(sequence_hidden(family, params, ids, cfg, prec,
                                       layers), params, cfg, prec)


def batch_loss(family, params, ids, labels, cfg, prec, layers: int):
    """Mean next-token cross entropy over a batch ``(B, S)``, one row at
    a time (the rows' gradients add up in the loop's carry)."""

    @jax.checkpoint
    def row(args):
        ids_r, labels_r = args
        logits = sequence_logits(family, params, ids_r, cfg, prec, layers)
        return cross_entropy_sum(logits, labels_r)

    sums = jax.lax.map(row, (ids, labels))
    return jnp.sum(sums) / (ids.shape[0] * ids.shape[1])


# -- training ---------------------------------------------------------------

def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def small_leaves(tree: dict) -> dict:
    """The one-dimensional leaves whole (norm weights, biases: a few
    thousand numbers each), for the rule by element below."""
    return {k: v.astype(jnp.float32) for k, v in tree.items() if v.ndim == 1}


def change_readings(now: dict, start: dict):
    d = {k: now[k].astype(jnp.float32) - start[k].astype(jnp.float32)
         for k in now}
    return leaf_norms(d), small_leaves(d)


def make_step(family, cfg, layers: int, hp: dict, mode: str = "f32",
              fault: str = None):
    """The jitted reference step ``(p, m, v, t, ids, labels) ->
    (p, m, v, loss, (leaf norms, small leaves) of the clipped gradient)``."""
    prec = Precision(mode)
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
    lr, wd, clip = hp["learning_rate"], hp["weight_decay"], hp["clip_norm"]

    def loss_of(p, ids, labels):
        if fault == "half_batch" and ids.shape[0] > 1:
            half = ids.shape[0] // 2
            ids, labels = ids[:half], labels[:half]
        elif fault == "half_batch":       # one row: half of its positions
            half = ids.shape[1] // 2
            ids, labels = ids[:, :half], labels[:, :half]
        return batch_loss(family, p, ids, labels, cfg, prec, layers)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, ids, labels):
        loss, g = jax.value_and_grad(loss_of)(p, ids, labels)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        scale = clip / jnp.maximum(norm, clip)
        g = {k: x * scale for k, x in g.items()}
        gn = (leaf_norms(g), small_leaves(g))
        tf = t.astype(jnp.float32)
        c1, c2 = 1.0 - b1 ** tf, 1.0 - b2 ** tf
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            new_m[k] = b1 * m[k] + (1.0 - b1) * g[k]
            new_v[k] = b2 * v[k] + (1.0 - b2) * jnp.square(g[k])
            upd = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + eps)
            new_p[k] = p[k] - lr * (upd + wd * p[k])
        if fault == "state_unchanged":
            return p, m, v, loss, gn
        return new_p, new_m, new_v, loss, gn

    return step


def adamw_reference(family, cfg, layers: int, make_params0, batches: list,
                    hp: dict, mode: str = "f32", fault: str = None) -> dict:
    """Drive AdamW with global-norm clipping through ``batches``, from the
    leaves ``make_params0()`` gives (called again at the end, so that no
    second copy of the start has to stay on the device meanwhile).
    Returns the readings a training cell compares: each step's loss, the
    leaf norms of the first clipped gradient, the leaf norms of the
    parameters' change after the last step.

    ``fault`` plants one of the faults a training cell can have, for the
    readings that set the limits: ``half_batch`` leaves the second half of
    every batch out (of a batch of one row, the second half of its
    positions) and takes the mean over the rest; ``state_unchanged``
    is a step that returns the state it was given."""
    step = make_step(family, cfg, layers, hp, mode, fault)
    change = jax.jit(change_readings)

    p = {k: jnp.asarray(x, jnp.float32) for k, x in make_params0().items()}
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    losses, g1 = [], None
    for i, b in enumerate(batches):
        p, m, v, loss, gn = step(p, m, v, jnp.asarray(i + 1, jnp.int32),
                                 jnp.asarray(b["input_ids"]),
                                 jnp.asarray(b["labels"]))
        losses.append(float(loss))
        if i == 0:
            g1 = {k: float(x) for k, x in gn[0].items()}
            g1_small = {k: np.asarray(x) for k, x in gn[1].items()}
    del m, v
    dn, d_small = change(p, make_params0())
    return {"losses": losses, "grad1_norms": g1,
            "change_norms": {k: float(x) for k, x in dn.items()},
            "grad1_small": g1_small,
            "change_small": {k: np.asarray(x) for k, x in d_small.items()}}


def worst_leaf_gap(got: dict, ref: dict, leaves=None):
    """The worst leaf's gap between the two norms, measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Returns (gap, leaf)."""
    med = statistics.median(ref.values())
    worst, at = 0.0, None
    for k in (ref if leaves is None else leaves):
        gap = abs(got[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst or at is None:
            worst, at = gap, k
    return worst, at


def training_numbers(got: dict, ref: dict) -> dict:
    """The numbers of a training cell's ``correct``: name -> (value, where).

    Leaves whose first gradient is nought to rounding in the reference
    (under a thousandth of the median leaf's norm) move under Adam by
    round-off alone; they are left out of the change, by that rule and
    not by name.  The same rule by element inside a one-dimensional leaf
    (a packed qkv bias holds the key's bias, whose gradient is nought
    under softmax): elements whose reference gradient is under a
    thousandth of that leaf's root mean square are left out of its
    change."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = (abs(a - b), f"{a:.6f} vs {b:.6f}")
    out["grad1_norm_gap"] = worst_leaf_gap(got["grad1_norms"],
                                           ref["grad1_norms"])
    med = statistics.median(ref["grad1_norms"].values())
    moving = [k for k, g in ref["grad1_norms"].items() if g >= 1e-3 * med]
    got_c, ref_c = dict(got["change_norms"]), dict(ref["change_norms"])
    for k, g in ref.get("grad1_small", {}).items():
        keep = np.abs(g) >= 1e-3 * np.sqrt(np.mean(np.square(g)))
        if k in got.get("change_small", {}) and not keep.all():
            got_c[k] = float(np.linalg.norm(got["change_small"][k][keep]))
            ref_c[k] = float(np.linalg.norm(ref["change_small"][k][keep]))
    out["change_norm_gap"] = worst_leaf_gap(got_c, ref_c, moving)
    return out


# -- serving ----------------------------------------------------------------

def served_token_gaps(family, cfg, layers: int, weights_of, sequences: list,
                      pad_to: int, max_out: int, control: str = None) -> dict:
    """For every served token, how far its logit lies below the
    reference's best at that position: one full forward of the reference
    over each prompt with its served tokens, layer by layer so that one
    layer's float32 weights live at a time.

    ``sequences``: [(prompt ids, served ids)].  ``weights_of(names)`` makes
    the named leaves from the seed.  Every sequence is padded on the right
    to ``pad_to`` (causal attention keeps the padding out of the real
    positions) and ``max_out`` positions are read for each, so one
    compiled shape serves every seed; ``pad_to`` is at least the longest
    prompt plus ``max_out``.

    With ``control`` (a precision mode) the same positions are also read
    in that precision, and the gap is that of the token which the lower
    precision puts first: the control need not decode.

    Returns {"widest", "at", "gaps": [per sequence], "control_widest"}."""
    modes = ["f32"] + ([control] if control else [])
    ids = []
    for prompt, served in sequences:
        row = list(prompt) + list(served)
        if len(row) > pad_to:
            raise ValueError(f"sequence of {len(row)} > pad_to {pad_to}")
        ids.append(jnp.asarray(row + [0] * (pad_to - len(row)), jnp.int32))

    outer = weights_of(family.outer_names(cfg))
    embed = jax.jit(lambda p, i: family.embed(p, i, cfg))
    xs = {m: [embed(outer, i) for i in ids] for m in modes}
    layer_fns = {}          # one compiled layer per (mode, kind)
    for i in range(layers):
        kind, prefix = family.layer_kind(cfg, i), family.layer_prefix(i)
        lp = layer_leaves(family, weights_of(
            [prefix + k for k in family.layer_shapes(cfg, i)]), cfg, i)
        for m in modes:
            if (m, kind) not in layer_fns:
                layer_fns[m, kind] = jax.jit(
                    lambda x, lp, m=m, kind=kind: family.layer(
                        x, lp, cfg, Precision(m), kind))
            xs[m] = [layer_fns[m, kind](x, lp) for x in xs[m]]
        del lp

    def read(x, start, tok, outer, m):
        """(logits of the ``max_out`` positions from ``start``, the gap of
        ``tok`` below the best there)."""
        logits = family.head(
            jax.lax.dynamic_slice_in_dim(x, start, max_out), outer, cfg,
            Precision(m))
        best = jnp.max(logits, axis=-1)
        return logits, best - jnp.take_along_axis(
            logits, tok[:, None], -1)[:, 0]

    read = jax.jit(read, static_argnums=(4,))
    out = {"widest": 0.0, "at": None, "gaps": [], "control_widest": None}
    ctl_widest = 0.0
    for s, (prompt, served) in enumerate(sequences):
        n, start = len(served), len(prompt) - 1
        tok = jnp.asarray(list(served) + [0] * (max_out - n), jnp.int32)
        logits, gaps = read(xs["f32"][s], start, tok, outer, "f32")
        gaps = np.asarray(gaps)[:n]
        g = float(gaps.max())
        out["gaps"].append(g)
        if g >= out["widest"]:
            out["widest"] = g
            out["at"] = f"sequence {s}, served token {int(gaps.argmax())}"
        if control:
            low, _ = read(xs[control][s], start, tok, outer, control)
            first = jnp.argmax(low, axis=-1)
            best = jnp.max(logits, axis=-1)
            cg = best - jnp.take_along_axis(logits, first[:, None], -1)[:, 0]
            ctl_widest = max(ctl_widest, float(np.asarray(cg)[:n].max()))
    if control:
        out["control_widest"] = ctl_widest
    return out
