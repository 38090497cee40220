#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

One process, the entry points a user calls, at the published widths of
Llama-2-7B (``PRESETS["llama2-7b"]``: hidden 4096, 32 heads x d128, ffn
11008, vocab 32000) with only the depth cut so that one 16 GB v5e chip
holds it::

    python chip_smoke.py            # one chip: train, reference, serve
    python chip_smoke.py --chips 4  # four chips: hybrid-parallel train only

It needs a TPU.  Without one it exits non-zero before any model is built
and prints no result line; the tests drive the same phase functions at
``tiny`` size on the CPU (tests/test_chip_smoke.py).

Each phase prints one JSON line and raises if what came out is wrong.
The last line of standard output is the verdict the driver reads::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` next to this file (gitignored): a second
run of the same tree reports compile seconds far below the first.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import gc
import http.client
import json
import os
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

PRESET = "llama2-7b"
# The largest depth whose bf16 params + fp32 master + AdamW moments +
# activations at batch 1 x seq 2048 fit one 16 GB chip: the deviceless
# v5e compile counts 8.7 GiB of state + 3.0 GiB of temporaries at two
# layers (tests/test_multichip_pallas_compile.py keeps that compile).
LAYERS = 2
SEQ = 2048

# bf16 keeps 8 significant bits.  Against the kernels-off reference the
# logits may differ by at most 8 bf16 ulps of their largest magnitude
# (2^-5 * max|logit|) anywhere and by one ulp (2^-8) on average; the
# loss, a float32 mean over thousands of tokens, by 1e-2.
LOGIT_MAX_ERR = 2.0 ** -5
LOGIT_MEAN_ERR = 2.0 ** -8
LOSS_ERR = 1e-2
GRAD_COSINE = 0.99
GRAD_NORM_ERR = 0.02
# hybrid-parallel vs one device: the same arithmetic in another
# reduction order, drifting apart through Adam's normalised update
HYBRID_LOSS_ERR = 5e-2


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def pallas_kernels(hlo: str) -> dict:
    """Name -> count of the Pallas (Mosaic) custom calls in a compiled
    program's text; the kernels carry stable names (ops/pallas)."""
    names = re.findall(        # under autodiff: transpose(jvp(<name>))
        r'custom_call_target="tpu_custom_call"[^\n]*?'
        r'op_name="[^"]*?/(?:\w+\()*(\w+)\)*/pallas_call', hlo)
    return dict(collections.Counter(names))


def collectives(hlo: str) -> dict:
    """Count of each cross-device operation in a compiled program's
    text.  Over an axis of two devices XLA writes all-gather and
    reduce-scatter as collective-permute, so that is counted too."""
    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo))
            for op in ("all-reduce", "reduce-scatter", "all-gather",
                       "collective-permute")}


def release() -> None:
    """Free what the last phase left on the device."""
    gc.collect()
    jax.clear_caches()


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# -- train -------------------------------------------------------------------

def build_train(preset: str, layers: int, seq: int, *, zero_stage=None):
    """Model -> amp O2 -> AdamW -> TrainStep, as bench.py and the docs
    build it.  Under ``fleet.init`` the step picks up the hybrid mesh."""
    from paddle_tpu import amp, nn, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import causal_lm_loss, llama

    model = llama(preset, num_hidden_layers=layers,
                  max_position_embeddings=seq)
    opt = optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0),
                          parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    return model, TrainStep(model, causal_lm_loss, opt,
                            zero_stage=zero_stage)


def make_batch(vocab: int, batch: int, seq: int, seed: int) -> dict:
    ids = jax.random.randint(jax.random.key(seed), (batch, seq), 0, vocab)
    return {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}


def run_steps(step, state, batch, steps: int):
    """AOT-compile (timed; the HLO is what the checks read), then take
    ``steps`` steps through ``step(...)``, each timed to
    ``block_until_ready``.  Nothing may compile after the first step.
    Returns (state, report)."""
    from paddle_tpu.observability.recompile import RecompileSentinel

    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    losses, times, compiles = [], [], []
    sentinel = RecompileSentinel()
    sentinel.install()
    try:
        for _ in range(steps):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
            times.append(time.perf_counter() - t0)
            losses.append(float(metrics["loss"]))
            compiles.append(sentinel.compiles())
    finally:
        sentinel.uninstall()
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall on a fixed batch: {losses}")
    require(compiles[-1] == compiles[0],
            f"{compiles[-1] - compiles[0]} compiles after the first step "
            f"(step seconds {[round(t, 3) for t in times]})")
    return state, {
        "compile_s": round(compile_s, 2),
        # the first call loads the program the AOT compile just cached
        "first_step_s": round(times[0], 2),
        "ms_per_step": round(1e3 * statistics.median(times[1:]), 2),
        "step_ms": [round(1e3 * t, 1) for t in times[1:]],
        "losses": [round(x, 4) for x in losses],
        "argument_bytes": int(mem.argument_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "pallas_kernels": pallas_kernels(hlo),
        "collectives": collectives(hlo),
    }


def train_phase(preset: str, layers: int, seq: int, *, steps: int = 4,
                batch: int = 1, seed: int = 0) -> dict:
    import paddle_tpu as pt

    pt.seed(seed)
    model, step = build_train(preset, layers, seq)
    data = make_batch(model.cfg.vocab_size, batch, seq, seed)
    state = step.init_state(seed=seed)
    state, rep = run_steps(step, state, data, steps)
    rep["peak_bytes_in_use"] = peak_bytes(jax.devices()[0])
    rep["tokens_per_step"] = batch * seq
    del state, step, model
    release()
    say("train", **rep)
    return rep


# -- reference ---------------------------------------------------------------

def _loss_logits_grads(model, params, data):
    """(loss, logits, grads) of the model's own forward, jitted anew so
    the kernel dispatch is resolved under the flags in force now."""
    from paddle_tpu.nn.layer import functional_call

    def loss_fn(p):
        return functional_call(model, p, data["input_ids"],
                               labels=data["labels"], training=True)

    def fwd(p):
        loss, grads = jax.value_and_grad(loss_fn)(p)
        logits = functional_call(model, p, data["input_ids"],
                                 training=False)
        return loss, logits, grads

    return jax.jit(fwd)(params)


def logits_agree(got, ref, what: str) -> dict:
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    scale = float(jnp.max(jnp.abs(ref)))
    err = jnp.abs(got - ref)
    max_err, mean_err = float(jnp.max(err)), float(jnp.mean(err))
    require(np.isfinite(max_err), f"{what}: non-finite logits")
    require(max_err <= LOGIT_MAX_ERR * scale,
            f"{what}: max |dlogit| {max_err:.4g} > 2^-5 * {scale:.4g}")
    require(mean_err <= LOGIT_MEAN_ERR * scale,
            f"{what}: mean |dlogit| {mean_err:.4g} > 2^-8 * {scale:.4g}")
    return {"logit_scale": round(scale, 4),
            "logit_max_err": round(max_err, 5),
            "logit_mean_err": round(mean_err, 6)}


def reference_phase(preset: str, layers: int, seq: int, *,
                    train_loss0: float, batch: int = 1,
                    seed: int = 0) -> dict:
    """The same weights and batch, once through the kernels and once
    with every Pallas kernel off: first-step loss, logits and gradients
    must agree within the bf16 tolerances above."""
    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.models.llama import llama
    from paddle_tpu.nn.layer import raw_params

    pt.seed(seed)
    kw = dict(num_hidden_layers=layers, max_position_embeddings=seq)
    model = llama(preset, **kw)
    model.astype("bfloat16")
    with nn.meta_init():     # only its code runs; the weights are swapped in
        plain = llama(preset, fused_ops="off", **kw)
    params = raw_params(model)
    data = make_batch(model.cfg.vocab_size, batch, seq, seed)

    loss_k, logits_k, grads_k = _loss_logits_grads(model, params, data)
    pt.set_flags({"use_pallas_kernels": False})
    try:
        loss_r, logits_r, grads_r = _loss_logits_grads(plain, params, data)
    finally:
        pt.set_flags({"use_pallas_kernels": True})

    loss_k, loss_r = float(loss_k), float(loss_r)
    rep = {"loss_kernels": round(loss_k, 4), "loss_reference": round(loss_r, 4),
           "loss_train_step0": round(train_loss0, 4)}
    require(abs(loss_k - loss_r) <= LOSS_ERR,
            f"loss {loss_k} vs reference {loss_r}")
    require(abs(train_loss0 - loss_r) <= LOSS_ERR,
            f"TrainStep first loss {train_loss0} vs reference {loss_r}")
    rep.update(logits_agree(logits_k, logits_r, "train-path logits"))

    def norm(tree):
        return float(jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(tree))))

    def cosine(a, b):
        a, b = a.astype(jnp.float32).ravel(), b.astype(jnp.float32).ravel()
        return float(jnp.vdot(a, b) / (jnp.linalg.norm(a)
                                       * jnp.linalg.norm(b) + 1e-30))

    cos = {k: cosine(grads_k[k], grads_r[k]) for k in grads_r}
    worst = min(cos, key=cos.get)
    nk, nr = norm(grads_k), norm(grads_r)
    rep.update(grad_norm_kernels=round(nk, 5), grad_norm_reference=round(nr, 5),
               grad_min_cosine=round(cos[worst], 5), grad_min_cosine_at=worst)
    require(cos[worst] >= GRAD_COSINE,
            f"gradient of {worst}: cosine {cos[worst]} to the reference")
    require(abs(nk - nr) <= GRAD_NORM_ERR * nr,
            f"gradient norm {nk} vs reference {nr}")
    del model, plain, params, logits_k, logits_r, grads_k, grads_r
    release()
    say("reference", **rep)
    return rep


# -- serve -------------------------------------------------------------------

def _post(host, port, body: dict):
    conn = http.client.HTTPConnection(host, port, timeout=300)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    require(resp.status == 200, f"POST /v1/completions -> {resp.status}: "
                                f"{raw[:200]!r}")
    if not body.get("stream"):
        choice = json.loads(raw)["choices"][0]
        return choice["token_ids"], choice["finish_reason"]
    tokens, done, reason = [], False, None
    for line in raw.decode().splitlines():
        if line == "data: [DONE]":
            done = True
        elif line.startswith("data: "):
            choice = json.loads(line[6:])["choices"][0]
            tokens.append(choice["token_id"])
            reason = choice["finish_reason"] or reason
    require(done, "stream ended without [DONE]")
    return tokens, reason


def _get_json(host, port, path: str) -> dict:
    conn = http.client.HTTPConnection(host, port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    require(resp.status == 200, f"GET {path} -> {resp.status}")
    return json.loads(raw)


def build_engine(preset: str, layers: int, *, max_batch: int,
                 max_seq_len: int):
    """The bf16 serving model and its (not yet warmed) Engine."""
    from paddle_tpu import serving
    from paddle_tpu.models.llama import llama

    model = llama(preset, num_hidden_layers=layers,
                  max_position_embeddings=max_seq_len, dtype="bfloat16")
    model.astype("bfloat16")
    model.eval()
    return model, serving.Engine(model, max_batch=max_batch,
                                 max_seq_len=max_seq_len)


def serve_step_hlo(eng, sharding=None) -> str:
    """Compiled text of the engine's one ragged step at the shapes
    ``Engine.warmup`` compiles (after a warm-up: a compile-cache hit).
    ``sharding`` places the arguments on a described device."""
    b, mb, c = eng.max_batch, eng.max_blocks_per_seq, eng.prefill_chunk

    def on(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    # what the cache kind adds to the step's inputs (None for one table)
    aux = eng.cache_spec.aux_shapes(b, c)
    if aux is not None:
        aux = {k: i32(*shape) for k, (shape, _) in aux.items()}
    return eng._step_fn.lower(
        jax.tree.map(on, eng.params), jax.tree.map(on, eng.kv.caches),
        i32(b, c), i32(b, mb), i32(b), i32(b),
        jax.ShapeDtypeStruct((b,), jnp.float32, sharding=sharding),
        on(eng._key), i32(b), i32(b), None, i32(b), aux).compile().as_text()


def serve_phase(preset: str, layers: int, *, prompt_lens=(5, 23, 61),
                max_new: int = 12, max_batch: int = 4,
                max_seq_len: int = 128, seed: int = 0) -> dict:
    """ServingServer -> FrontDoor -> Engine on a localhost port: warm up,
    two requests of each prompt length (every other one streamed) sent at
    once, /healthz, drain.  Then the references: ``model.generate()`` and
    a kernels-off teacher-forced forward over what the engine emitted."""
    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.nn.layer import functional_call, raw_params

    pt.seed(seed)
    model, eng = build_engine(preset, layers, max_batch=max_batch,
                              max_seq_len=max_seq_len)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=n).tolist()
               for n in prompt_lens for _ in range(2)]

    tel = obs.enable(crash_hooks=False)
    try:
        t0 = time.perf_counter()
        eng.warmup()
        warmup_s = time.perf_counter() - t0
        compiles0 = tel.sentinel.compiles()
        srv = serving.ServingServer(eng, port=0)
        host, port = srv.start()
        try:
            require(_get_json(host, port, "/healthz")["status"] == "serving",
                    "/healthz before traffic")
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(len(prompts)) as ex:
                futs = [ex.submit(_post, host, port,
                                  {"prompt": p, "max_tokens": max_new,
                                   "stream": bool(i % 2)})
                        for i, p in enumerate(prompts)]
                outs = [f.result(timeout=600) for f in futs]
            serve_s = time.perf_counter() - t0
            health = _get_json(host, port, "/healthz")
            srv.begin_drain()
            require(srv.wait_drained(timeout=60), "server did not drain")
        finally:
            srv.close()
        compiles = tel.sentinel.compiles() - compiles0
    finally:
        obs.disable()
    for toks, reason in outs:
        require(len(toks) == max_new and reason == "length",
                f"request ended early: {len(toks)} tokens, {reason}")
    require(compiles == 0, f"{compiles} backend compiles after warm-up")
    require(eng.kv_blocks_used == 0,
            f"{eng.kv_blocks_used} KV blocks not reclaimed")
    require(health["active_requests"] == 0, f"/healthz after: {health}")
    kernels = pallas_kernels(serve_step_hlo(eng))

    # reference 1: the engine's tokens, teacher-forced through the plain
    # dense forward with every kernel off (one padded batch; causal
    # attention keeps the padding out of the real positions)
    width = max(prompt_lens) + max_new
    seqs = np.zeros((len(prompts), width), np.int32)
    for r, (p, (toks, _)) in enumerate(zip(prompts, outs)):
        seqs[r, :len(p) + max_new] = p + toks
    params = raw_params(model)
    pt.set_flags({"use_pallas_kernels": False})
    try:
        ref = np.asarray(jax.jit(
            lambda p, ids: functional_call(model, p, ids, training=False)
        )(params, jnp.asarray(seqs)), np.float32)
    finally:
        pt.set_flags({"use_pallas_kernels": True})
    scale = float(np.max(np.abs(ref)))
    tie = 2 * LOGIT_MAX_ERR * scale

    def margin(r, pos, tok):
        """How far below the reference's best token ``tok`` scores."""
        return float(ref[r, pos].max() - ref[r, pos, tok])

    worst = 0.0
    for r, (p, (toks, _)) in enumerate(zip(prompts, outs)):
        for i, tok in enumerate(toks):
            m = margin(r, len(p) + i - 1, tok)
            worst = max(worst, m)
            require(m <= tie, f"request {r} token {i}: engine chose {tok}, "
                              f"{m:.4g} below the reference's best "
                              f"(near-tie bound {tie:.4g})")

    # reference 2: model.generate(), the repo's own greedy rollout.
    # Tokens must match up to a near-tie of the reference logits.
    identical = 0
    for r, (p, (toks, _)) in enumerate(zip(prompts, outs)):
        gen = np.asarray(model.generate(
            jnp.asarray([p], jnp.int32), max_new_tokens=max_new,
            max_len=max_seq_len))[0, len(p):].tolist()
        if gen == toks:
            identical += 1
            continue
        i = next(j for j in range(max_new) if gen[j] != toks[j])
        m = margin(r, len(p) + i - 1, gen[i])
        require(m <= tie, f"request {r} token {i}: generate() chose "
                          f"{gen[i]}, engine {toks[i]}, no near-tie "
                          f"({m:.4g} > {tie:.4g})")
    rep = {"requests": len(prompts), "prompt_lens": list(prompt_lens),
           "max_new_tokens": max_new, "warmup_s": round(warmup_s, 2),
           "serve_s": round(serve_s, 3),
           "compiles_after_warmup": compiles, "kv_blocks_used": 0,
           "pallas_kernels": kernels, "logit_scale": round(scale, 4),
           "worst_margin_to_reference": round(worst, 5),
           "near_tie_bound": round(tie, 5),
           "token_identical_to_generate": f"{identical}/{len(prompts)}",
           "peak_bytes_in_use": peak_bytes(jax.devices()[0])}
    del eng, model, params, ref
    release()
    say("serve", **rep)
    return rep


# -- four chips --------------------------------------------------------------

def hybrid_phase(preset: str, layers: int, seq: int, *, steps: int = 4,
                 batch: int = 2, seed: int = 0) -> dict:
    """Fleet hybrid-parallel training (mp 2 x sharding 2, ZeRO-1) over
    four devices against the same model and batch on one device."""
    import paddle_tpu as pt
    from paddle_tpu.distributed import fleet

    devices = jax.devices()[:4]
    require(len(devices) == 4, f"need four devices, have {len(devices)}")
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"mp_degree": 2, "sharding_degree": 2}
    fleet.init(is_collective=True, strategy=strategy, devices=devices)
    try:
        pt.seed(seed)
        model, step = build_train(preset, layers, seq, zero_stage=1)
        data = make_batch(model.cfg.vocab_size, batch, seq, seed)
        state = step.init_state(seed=seed)
        # the model object still holds the unsharded parameters it was
        # built with; the step only ever reads the sharded state
        held = {d.id: 0 for d in devices}
        for leaf in jax.tree.leaves(state):
            for shard in getattr(leaf, "addressable_shards", ()):
                held[shard.device.id] += shard.data.nbytes
        state, rep = run_steps(step, state, data, steps)
        in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                  for d in devices]
        mesh_shape = dict(step.mesh.shape)
        del state, step, model
    finally:
        fleet._reset()
    release()
    rep.update(mesh={k: v for k, v in mesh_shape.items() if v > 1},
               state_bytes_per_device=list(held.values()),
               bytes_in_use_per_device=in_use)
    require(max(held.values()) <= 2 * min(held.values()),
            f"state is not spread over the devices: {held}")
    if any(in_use):      # the CPU backend reports no memory statistics
        require(max(in_use) <= 2 * min(in_use),
                f"device memory is not spread over the devices: {in_use}")
    require(rep["collectives"]["all-reduce"] > 0,
            f"no all-reduce in the hybrid step: {rep['collectives']}")
    say("hybrid", **rep)

    pt.seed(seed)
    model, step = build_train(preset, layers, seq)
    state = step.init_state(seed=seed)
    state, one = run_steps(step, state, data, steps)
    del state, step, model
    release()
    say("hybrid_reference", **one)
    diffs = [abs(a - b) for a, b in zip(rep["losses"], one["losses"])]
    require(max(diffs) <= HYBRID_LOSS_ERR,
            f"hybrid losses {rep['losses']} vs one device {one['losses']}")
    say("hybrid_agreement", max_loss_diff=round(max(diffs), 5),
        bound=HYBRID_LOSS_ERR)
    return rep


# -- entry -------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the hybrid-parallel training phase "
                         "and its one-device comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r} - nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU devices, JAX found {len(devices)}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(HERE, ".jax_cache"))

    from paddle_tpu.models.llama import PRESETS
    full = PRESETS[PRESET]
    say("config", model=PRESET, hidden_size=full.hidden_size,
        num_attention_heads=full.num_attention_heads,
        intermediate_size=full.intermediate_size, vocab_size=full.vocab_size,
        seq=SEQ, reduced={"num_hidden_layers": [full.num_hidden_layers,
                                               LAYERS]},
        jax=jax.__version__, devices=len(devices),
        compile_cache=jax.config.jax_compilation_cache_dir)
    if args.chips == 4:
        hybrid_phase(PRESET, LAYERS, SEQ)
    else:
        train = train_phase(PRESET, LAYERS, SEQ)
        for name in ("flash_attention_fwd", "flash_attention_bwd"):
            require(name in train["pallas_kernels"],
                    f"{name} is not in the compiled train step: "
                    f"{train['pallas_kernels']}")
        reference_phase(PRESET, LAYERS, SEQ,
                        train_loss0=train["losses"][0])
        serve = serve_phase(PRESET, LAYERS)
        require("ragged_paged_attention" in serve["pallas_kernels"],
                f"ragged_paged_attention is not in the compiled serve "
                f"step: {serve['pallas_kernels']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
