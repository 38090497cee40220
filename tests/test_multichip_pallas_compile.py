"""Deviceless compiles for a described TPU v5e — what interpret mode and
the CPU mesh cannot show.

The chip's own compiler (Mosaic + XLA:TPU, installed here) compiles for
a ``v5e:2x2`` topology that is described, not attached.  Three groups:

- Pallas kernels under SPMD partitioning (the flash-chunk ring, the
  quantized kernel under an ``mp`` mesh): the two M107 multi-chip ring
  bugs only reproduce when compiling FOR a multi-chip TPU topology;
- every kernel of ``paddle_tpu/ops/pallas/`` at Llama-2-7B widths, or at
  the widest preset its ``supported()`` gate admits — a kernel whose
  gate says yes must get through Mosaic, and what Mosaic refuses the
  gate must decline (VMEM limits, operand types: none of it shows in
  interpret mode);
- the two programs ``chip_smoke.py`` runs on the chip, built by its own
  builders: the 7B-width ``TrainStep`` and the engine's ragged step;
- the step of each benchmark cell, built from the cell's own files under
  ``benchmark/`` at its own widths and shapes: which kernels are in it.

Nothing runs, so nothing here says a result is right or fast.  The
topology is described inside a module-scoped fixture: only the xdist
worker that is handed this file loads libtpu, and it skips where the
topology cannot be described.  Keep every such test in THIS file.
"""

import importlib
import json
import math
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    os.environ.get("PDTPU_SKIP_DEVICELESS") == "1",
    reason="deviceless TPU compile disabled by env")

# Llama-2-7B widths (models/llama.py PRESETS["llama2-7b"])
H, I, NH, HD = 4096, 11008, 32, 128
# llama-350m-hd128: the widest preset whose weights the weight-resident
# fused_norm_qkv kernel can hold in VMEM
H350 = 1024
BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        td = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless compile is written to the persistent cache but cannot
    # be read back without a chip (the next run warns and recompiles)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield td
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    """``shape((m, n), dtype)`` — an abstract array on one described chip."""
    def make(dims, dtype=BF16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


@pytest.fixture
def as_tpu(monkeypatch):
    """The suite pins the PROCESS backend to cpu (conftest), but these
    programs are traced FOR a TPU: every "which backend?" the package
    asks while tracing answers tpu, so the kernel registry and the
    ``supported()`` gates decide as they do on the chip."""
    from paddle_tpu.ops import dispatch
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _hybrid_llama_step(topo, hybrid_configs, *, heads, zero_stage=None):
    """A tiny ring-attention llama TrainStep lowered for the four
    described chips under ``hybrid_configs``."""
    from jax.sharding import NamedSharding

    from paddle_tpu import amp, nn, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, causal_lm_loss, llama

    s = fleet.DistributedStrategy()
    s.hybrid_configs = hybrid_configs
    fleet.init(is_collective=True, strategy=s, devices=list(topo.devices))
    cfg = LlamaConfig(hidden_size=128, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=heads,
                      num_key_value_heads=heads, vocab_size=256,
                      max_position_embeddings=512, dtype="bfloat16",
                      context_parallel="ring")
    with nn.meta_init():
        model = llama(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    step = TrainStep(model, causal_lm_loss, opt, zero_stage=zero_stage)
    bsh = NamedSharding(step.mesh, step.batch_spec)
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 512), jnp.int32,
                                               sharding=bsh),
             "labels": jax.ShapeDtypeStruct((2, 512), jnp.int64,
                                            sharding=bsh)}
    return step.lower(step.abstract_state(), batch).compile()


def test_flash_ring_compiles_for_multichip_tpu(topo, as_tpu, monkeypatch):
    """ring(sep2) x ZeRO-3(2): guards PartitionId from lax.axis_index
    under partial-manual shard_map, and Mosaic kernels landing in the
    SPMD partitioner when any mesh axis stays auto."""
    from paddle_tpu.distributed import fleet

    # chunk is 256 here; drop the ring's flash threshold so the Pallas
    # path (the thing under test) is what compiles
    monkeypatch.setenv("PDTPU_RING_FLASH_MIN_CHUNK", "64")
    fleet._reset()
    try:
        compiled = _hybrid_llama_step(
            topo, {"sharding_degree": 2, "sep_degree": 2}, heads=2,
            zero_stage=3)
        # the Pallas kernel must actually BE in the program (flash path
        # engaged, not the einsum fallback silently covering for it)
        assert "tpu_custom_call" in compiled.as_text(), \
            "flash ring did not engage — einsum fallback compiled instead"
        assert compiled.memory_analysis().temp_size_in_bytes > 0
    finally:
        fleet._reset()


def test_flash_ring_with_mp_head_sharding(topo, as_tpu, monkeypatch):
    """The hspec path: heads sharded over mp WHILE the flash ring runs —
    exercises the manual-over-all axis set with a >1 mp axis."""
    from paddle_tpu.distributed import fleet

    monkeypatch.setenv("PDTPU_RING_FLASH_MIN_CHUNK", "64")
    fleet._reset()
    try:
        compiled = _hybrid_llama_step(
            topo, {"mp_degree": 2, "sep_degree": 2}, heads=4)
        assert "tpu_custom_call" in compiled.as_text(), \
            "flash ring with mp head sharding did not engage"
    finally:
        fleet._reset()


def test_int4_kernel_compiles_for_multichip_mp(topo, monkeypatch):
    """The int4 dequant kernel under an mp mesh: the column-parallel
    layer routes through an explicit shard_map (GSPMD cannot partition
    Mosaic kernels); the generic weight_only_linear entry and the
    row-parallel layer fall back to XLA under a mesh.  Both must COMPILE
    for a real multichip TPU topology."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as pt
    import paddle_tpu.nn.quant as QN
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mp_layers import (ColumnParallelLinear,
                                                  RowParallelLinear)
    from paddle_tpu.nn.layer import functional_call, raw_params

    monkeypatch.setattr(QN, "_use_int4_kernel", lambda: True)
    # spy on the function the column layer CALLS: it must actually
    # engage the shard_map path (a stale branch condition silently
    # compiling the XLA fallback would keep this test green for no
    # coverage) — the one guard that the quantized kernel serves under mp
    engaged = []
    real = QN._kernel_column_sharded

    def spy(*a, **k):
        engaged.append(1)
        return real(*a, **k)
    monkeypatch.setattr(QN, "_kernel_column_sharded", spy)

    fleet._reset()
    try:
        s = fleet.DistributedStrategy()
        s.hybrid_configs = {"mp_degree": 2, "dp_degree": 2}
        hcg = fleet.init(is_collective=True, strategy=s,
                         devices=list(topo.devices))
        pt.seed(0)
        col = QN.QuantizedColumnParallelLinear(
            ColumnParallelLinear(256, 512, has_bias=False),
            algo="weight_only_int4")
        row = QN.QuantizedRowParallelLinear(
            RowParallelLinear(512, 256, has_bias=False),
            algo="weight_only_int4")

        def fwd(params, x):
            h = functional_call(col, {k[4:]: v for k, v in params.items()
                                      if k.startswith("col.")}, x)
            return functional_call(row, {k[4:]: v for k, v in params.items()
                                         if k.startswith("row.")}, h)

        params = {**{f"col.{k}": v for k, v in raw_params(col).items()},
                  **{f"row.{k}": v for k, v in raw_params(row).items()}}
        ps = {k: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype,
                                      sharding=NamedSharding(hcg.mesh, P()))
              for k, v in params.items()}
        xs = jax.ShapeDtypeStruct((2, 1, 256), jnp.bfloat16,
                                  sharding=NamedSharding(hcg.mesh, P()))
        with hcg.mesh:
            compiled = jax.jit(fwd).lower(ps, xs).compile()
        assert engaged, "column layer never took the shard_map kernel path"
        assert "tpu_custom_call" in compiled.as_text()
    finally:
        fleet._reset()


# -- every kernel, one chip, real widths -------------------------------------

def _pools(shape, page, heads=NH, batch=8, ctx=2048):
    mb = ctx // page
    pool = shape((batch * mb, page, heads, HD))
    return pool, shape((batch, mb), I32), shape((batch,), I32)


def _case_fused_swiglu_mlp(shape):
    from paddle_tpu.ops.pallas import fused_mlp as m
    x, wg, wd = shape((2048, H)), shape((H, I)), shape((I, H))
    assert m.supported(x, wg, wd)
    return m.fused_swiglu_mlp, (x, wg, wg, wd), ["fused_swiglu_mlp"]


def _case_fused_swiglu_mlp_live(ffn):
    """The serving step's call: ``(32 x 16, 4096)`` lanes and the step's
    live count, so the weight-stationary order; x, the float32
    accumulator and the output whole in VMEM beside the weight blocks,
    within ``VMEM_LIMIT``."""
    def case(shape):
        from paddle_tpu.ops.pallas import fused_mlp as m
        x, wg, wd = shape((512, 4096)), shape((4096, ffn)), \
            shape((ffn, 4096))
        assert m.supported(x, wg, wd) and m.holds_live(x, wg)
        return m.fused_swiglu_mlp, (x, wg, wg, wd, shape((), I32)), \
            ["fused_swiglu_mlp"]
    return case


def _case_flash_attention(shape):
    from paddle_tpu.ops.pallas import flash_attention as m
    q = shape((1, 2048, NH, HD))
    assert m.supported(q, q, q, causal=True)

    def fwd_bwd(q, k, v):
        return jax.value_and_grad(
            lambda *a: m.flash_attention(*a, causal=True)
            .astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)
    return fwd_bwd, (q, q, q), ["flash_attention_fwd", "flash_attention_bwd"]


def _case_ragged(page, kv_heads=NH, batch=8, ctx=2048):
    def case(shape):
        from paddle_tpu.ops.pallas import ragged_attention as m
        pool, tables, lens = _pools(shape, page, heads=kv_heads,
                                    batch=batch, ctx=ctx)
        args = (shape((batch, 16, NH, HD)), pool, pool, tables, lens, lens)
        assert m.supported(*args)
        return m.ragged_paged_attention, args, ["ragged_paged_attention"]
    return case


def _case_paged_attention(shape):
    from paddle_tpu.ops.pallas import decode_attention as m
    pool, tables, lens = _pools(shape, 16)
    args = (shape((8, NH, HD)), pool, pool, tables, lens)
    assert m.supported(*args)
    return m.paged_attention, args, ["paged_attention"]


def _case_fused_adamw(dims=(H, I), g_dtype=BF16):
    """float32 master and moments, the gradient as it arrives; under amp
    O2 (a bfloat16 gradient) the bfloat16 copy out."""
    def case(shape):
        from paddle_tpu.ops.pallas import fused_adamw as m
        low = None if g_dtype == F32 else BF16
        p, g, c = shape(dims, F32), shape(dims, g_dtype), shape((), F32)
        assert m.eligible(p, g, low)

        def update(p, g, mom, v, lr, c1, c2):
            return m.fused_adamw_update(p, g, mom, v, lr, c1, c2, beta1=0.9,
                                        beta2=0.999, eps=1e-8, wd=0.1,
                                        low_dtype=low)
        return update, (p, g, p, p, c, c, c), ["fused_adamw"]
    return case


def _case_int8_matmul(shape):
    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul
    args = (shape((8, H)), shape((H, I), I8), shape((I,), F32))
    return int8_matmul, args, ["int8_matmul"]


def _case_int4_matmul(shape):
    from paddle_tpu.ops.pallas.int4_matmul import int4_matmul
    args = (shape((8, I)), shape((I // 2, H), I8), shape((H,), F32))
    return int4_matmul, args, ["int4_matmul"]


def _case_lora_bgmv(shape):
    from paddle_tpu.ops.pallas import lora_matmul as m
    x, a, b = shape((8, 16, H)), shape((4, H, 16)), shape((4, 16, I))
    assert m.supported(x, a, b)
    return m.grouped_bgmv, (x, a, b, shape((8,), I32)), ["lora_bgmv"]


def _case_fused_rms_rope_qkv(shape):
    from paddle_tpu.ops.pallas import fused_norm_qkv as m
    x, w = shape((2048, H350)), shape((H350, H350))
    assert m.supported(x, w, w, HD)
    rope = shape((2048, HD), F32)        # the tables arrive in float32

    def qkv(x, g, wq, wk, wv, cos, sin):
        return m.fused_rms_rope_qkv(x, g, wq, wk, wv, cos, sin, HD)
    return qkv, (x, shape((H350,)), w, w, w, rope, rope), \
        ["fused_rms_rope_qkv"]


KERNEL_CASES = {
    "fused_swiglu_mlp-7b": _case_fused_swiglu_mlp,
    # the two serving cells' own calls (Mistral's ffn, EvaByte's)
    "fused_swiglu_mlp-live-ffn14336": _case_fused_swiglu_mlp_live(14336),
    "fused_swiglu_mlp-live-ffn11008": _case_fused_swiglu_mlp_live(11008),
    "flash_attention-7b": _case_flash_attention,
    "ragged_paged_attention-7b-page16": _case_ragged(16),
    "ragged_paged_attention-7b-page64": _case_ragged(64),
    # the serving cell's own shape: Mistral-7B's GQA 32/8, 32 slots of
    # 256 table entries, pages of 16
    "ragged_paged_attention-mistral-gqa8": _case_ragged(
        16, kv_heads=8, batch=32, ctx=4096),
    "paged_attention-7b": _case_paged_attention,
    "fused_adamw-7b": _case_fused_adamw(),
    "int8_matmul-7b": _case_int8_matmul,
    "int4_matmul-7b": _case_int4_matmul,
    "lora_bgmv-7b": _case_lora_bgmv,
    "fused_rms_rope_qkv-350m-hd128": _case_fused_rms_rope_qkv,
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(case, shape, as_tpu, smoke):
    """The kernel's own ``supported()`` admits the shapes, Mosaic
    compiles them, and the kernel is in the program under its name."""
    fn, args, names = KERNEL_CASES[case](shape)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    found = smoke.pallas_kernels(hlo)
    assert all(n in found for n in names), (names, found)


# every two-dimensional leaf the benchmark's two training cells and
# chip_smoke.py train (PERF.md section 4), and a stacked 3-D leaf
ADAMW_LEAVES = [
    (4096, 14336), (14336, 4096), (4096, 4096), (4096, 1024),
    (32000, 4096), (4096, 32000),                         # mistral-7b
    (4096, 12288), (4096, 16384), (16384, 4096), (50304, 4096),
    (2048, 4096),                                         # gpt3-6.7b
    (H, I), (I, H),                                       # llama2-7b
    (8, 4096, 1024),
]


def _sized(hlo: str, sizes) -> list:
    """(name, opcode, line) of the entry computation's instructions whose
    result, or one element of a tuple result, has as many elements as
    ``sizes`` holds."""
    out = []
    for line in hlo[hlo.index("\nENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m and any(math.prod(map(int, dims.split(","))) in sizes
                     for dims in re.findall(r"\w+\[([\d,]+)\]",
                                            m.group(2))):
            out.append((m.group(1), m.group(3), line.strip()))
    return out


@pytest.mark.parametrize("g_dtype", [BF16, F32], ids=["bf16grad", "f32grad"])
@pytest.mark.parametrize("dims", ADAMW_LEAVES,
                         ids=["x".join(map(str, d)) for d in ADAMW_LEAVES])
def test_fused_adamw_takes_the_leaf_as_it_is(dims, g_dtype, shape, as_tpu):
    """State donated: nothing of the leaf's size is in the program but
    the kernel (and the views of its operands), and nothing is kept
    beside the state.  With ``(n/128, 128)`` operands this read seven
    ``reshape`` copies and 704,772,096 temporary bytes at ``(H, I)``."""
    update, args, _ = _case_fused_adamw(dims, g_dtype)(shape)
    compiled = jax.jit(update, donate_argnums=(0, 2, 3)).lower(
        *args).compile()
    moved = [name for name, opcode, _ in _sized(compiled.as_text(),
                                                {math.prod(dims)})
             if opcode not in ("parameter", "bitcast", "get-tuple-element",
                               "tuple")
             and not name.startswith("fused_adamw")]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_gates_decline_what_mosaic_refuses(as_tpu):
    """At Llama-2-7B widths the weight-resident kernel does not fit
    VMEM: its gate says no, and the model keeps the XLA composition."""
    from paddle_tpu.ops.pallas import fused_norm_qkv, ragged_attention

    def z(*dims):
        return jax.ShapeDtypeStruct(dims, BF16)
    x, w = z(2048, H), z(H, H)
    assert not fused_norm_qkv.supported(x, w, w, HD)
    # a bf16 page whose kv heads do not fill its HBM tiles (MQA, 6 or 12
    # kv heads) cannot be DMA'd whole; 2, 4 and multiples of 8 can
    span, i32 = z(8, 16, 24, HD), jax.ShapeDtypeStruct((8,), I32)
    for kv_heads, ok in ((1, False), (6, False), (12, False), (2, True),
                         (4, True), (8, True), (24, True)):
        kv = z(64, 16, kv_heads, HD)
        assert ragged_attention.supported(
            span, kv, kv, jax.ShapeDtypeStruct((8, 8), I32), i32, i32) == ok
    # llama-1b widths: compiles to 50 MiB of scoped VMEM, past the limit
    x1, w1 = z(2048, 2048), z(2048, 2048)
    assert not fused_norm_qkv.supported(x1, w1, w1, HD)


def test_smoke_train_step_compiles_for_v5e(topo, as_tpu, smoke):
    """chip_smoke's TrainStep at its real size: Llama-2-7B widths, its
    depth, batch 1 x seq 2048, ``fused_ops="auto"``."""
    from jax.sharding import NamedSharding

    from paddle_tpu import nn
    from paddle_tpu.distributed import fleet

    fleet._reset()
    try:
        fleet.init(is_collective=True, devices=[topo.devices[0]])
        with nn.meta_init():
            _, step = smoke.build_train(smoke.PRESET, smoke.LAYERS,
                                        smoke.SEQ)
        bsh = NamedSharding(step.mesh, step.batch_spec)
        ids = jax.ShapeDtypeStruct((1, smoke.SEQ), jnp.int32, sharding=bsh)
        compiled = step.lower(step.abstract_state(),
                              {"input_ids": ids, "labels": ids}).compile()
    finally:
        fleet._reset()
    found = smoke.pallas_kernels(compiled.as_text())
    layers = smoke.LAYERS
    assert found.get("flash_attention_fwd") == layers, found
    assert found.get("flash_attention_bwd") == layers, found
    assert found.get("fused_swiglu_mlp") == layers, found
    # every matrix goes through the kernel: q, k, v, o, gate, up, down a
    # layer, the embedding and the head; the norm weights do not
    assert found.get("fused_adamw") == 7 * layers + 2, found
    assert "fused_rms_rope_qkv" not in found, found
    # the kernel takes each leaf in the shape the step holds it: nothing
    # of a trained leaf's size is copied or reshaped around it
    sizes = {math.prod(s.shape) for s in jax.tree.leaves(
        step.abstract_state()["params"]) if len(s.shape) > 1}
    moved = [name for name, opcode, line in _sized(compiled.as_text(), sizes)
             if opcode in ("reshape", "copy", "transpose")
             and "/optimizer/" in line]
    assert not moved, moved
    # state + temporaries leave room on a 16 GiB chip for the phases
    # that follow in the same process
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 13 * 2 ** 30


def test_smoke_serve_step_compiles_for_v5e(one_chip, as_tpu, smoke):
    """chip_smoke's Engine at Llama-2-7B widths: the one ragged step."""
    from paddle_tpu import nn

    with nn.meta_init():
        _, eng = smoke.build_engine(smoke.PRESET, smoke.LAYERS,
                                    max_batch=4, max_seq_len=128)
    found = smoke.pallas_kernels(
        smoke.serve_step_hlo(eng, sharding=one_chip))
    assert found.get("ragged_paged_attention") == smoke.LAYERS, found
    assert found.get("fused_swiglu_mlp") == smoke.LAYERS, found
    assert len(found) == 2, found


# -- the benchmark's cells: which kernels their steps hold --------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_DEPTH = 2
# PERF.md section 4, a layer's share times CELL_DEPTH: attention and MLP
# kernels once a layer; fused_adamw once a matrix (Mistral 7 a layer, the
# embedding and the head; GPT 4 a layer and both embeddings)
CELL_KERNELS = {
    "mistral-7b.train-8k": {"flash_attention_fwd": CELL_DEPTH,
                            "flash_attention_bwd": CELL_DEPTH,
                            "fused_swiglu_mlp": CELL_DEPTH,
                            "fused_adamw": 7 * CELL_DEPTH + 2},
    "gpt3-6.7b.train-8k": {"flash_attention_fwd": CELL_DEPTH,
                           "flash_attention_bwd": CELL_DEPTH,
                           "fused_adamw": 4 * CELL_DEPTH + 2},
    "mistral-7b.serve-chat": {"ragged_paged_attention": CELL_DEPTH,
                              "fused_swiglu_mlp": CELL_DEPTH},
    # EVA's step walks its summary pages then its window pages through the
    # ragged kernel under a name of its own (PR 32)
    "evabyte.serve-doc-bytes": {"eva_ragged_paged_attention": CELL_DEPTH,
                                "fused_swiglu_mlp": CELL_DEPTH},
    # one whole period (PR 34): flash attention in its one full layer, at
    # d = 256; fused_adamw once a matrix whose last two dimensions fill
    # its tiles: 2 a gated-delta layer (in_proj_qkvz, out_proj), 4 the
    # full layer, 7 a sparse block (router, the three stacked expert
    # leaves, the shared expert's three) and the embedding; the head's
    # 18,992 columns are no whole 128 lanes and take XLA's composition;
    # the gated delta rule's kernel pair once a gated-delta layer (PR 35:
    # no jax.checkpoint around it, so the forward runs once)
    "qwen3-next-80b-a3b.train-8k": {"flash_attention_fwd": 1,
                                    "flash_attention_bwd": 1,
                                    "gated_delta_rule_fwd": 3,
                                    "gated_delta_rule_bwd": 3,
                                    "fused_adamw": 3 * 2 + 4 + 4 * 7 + 1},
}
# the cells compiled at another depth than CELL_DEPTH: a whole period
CELL_DEPTHS = {"qwen3-next-80b-a3b.train-8k": 4}


def _cell_files(name):
    """The cell's and its configuration's files, read as the benchmark
    reads them; ``benchmark.builders`` imports from the repo's root."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    with open(os.path.join(REPO, "benchmark", "workloads",
                           name + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    return cell, config, importlib.import_module(
        "benchmark.builders." + config["builder"])


def _train_cell_hlo(name, topo):
    """``benchmark/runners/train.py``'s Program without the weights."""
    from jax.sharding import NamedSharding

    from paddle_tpu import amp, nn, optimizer
    from paddle_tpu.distributed import fleet
    from paddle_tpu.jit import TrainStep

    cell, config, builder = _cell_files(name)
    hp, traffic = cell["optimizer"], cell["traffic"]
    fleet._reset()
    try:
        fleet.init(is_collective=True, devices=[topo.devices[0]])
        with nn.meta_init():
            model = builder.build_model(config,
                                        CELL_DEPTHS.get(name, CELL_DEPTH),
                                        config["max_position_embeddings"])
        opt = optimizer.AdamW(
            learning_rate=hp["learning_rate"], beta1=hp["beta1"],
            beta2=hp["beta2"], epsilon=hp["epsilon"],
            weight_decay=hp["weight_decay"],
            grad_clip=nn.ClipGradByGlobalNorm(hp["clip_norm"]),
            parameters=model.parameters())
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
        step = TrainStep(model, builder.loss_fn(), opt)
        ids = jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq"]), jnp.int32,
            sharding=NamedSharding(step.mesh, step.batch_spec))
        return step.lower(step.abstract_state(),
                          {"input_ids": ids, "labels": ids}
                          ).compile().as_text()
    finally:
        fleet._reset()


def _serve_cell_hlo(name, topo, smoke):
    """``benchmark/runners/serve.py``'s Program as far as its Engine: the
    one ``(max_batch, prefill_chunk)`` step that ``warmup`` compiles."""
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu import nn, serving

    cell, config, builder = _cell_files(name)
    with nn.meta_init():
        model = builder.build_model(
            config, CELL_DEPTH, config["max_position_embeddings"],
            dtype="bfloat16")
        model.astype("bfloat16")
        model.eval()
        eng = serving.Engine(model, **cell["engine"])
    assert (eng.max_batch, eng.prefill_chunk) == (32, 16)
    return smoke.serve_step_hlo(
        eng, sharding=SingleDeviceSharding(topo.devices[0]))


@pytest.fixture(scope="module")
def cell_hlo(topo, smoke):
    """``cell_hlo(name)``: the cell's step compiled for v5e at depth
    ``CELL_DEPTH``, as text; one compile a cell."""
    from paddle_tpu.ops import dispatch

    found = {}

    def hlo(name):
        if name not in found:
            with pytest.MonkeyPatch.context() as mp:     # as ``as_tpu``
                mp.setattr(dispatch, "_backend", lambda: "tpu")
                mp.setattr(jax, "default_backend", lambda: "tpu")
                found[name] = (
                    _serve_cell_hlo(name, topo, smoke)
                    if _cell_files(name)[0]["runner"] == "serve"
                    else _train_cell_hlo(name, topo))
        return found[name]
    return hlo


@pytest.fixture(scope="module")
def cell_kernels(cell_hlo, smoke):
    """``cell_kernels(name)``: the Pallas calls by name in that step."""
    return lambda name: smoke.pallas_kernels(cell_hlo(name))


@pytest.mark.parametrize(
    "cell,kernel",
    [(c, k) for c, ks in CELL_KERNELS.items() for k in ks])
def test_benchmark_cell_holds_its_kernel(cell, kernel, cell_kernels):
    """Every cell's program runs the kernels PERF.md says it runs, as
    often: a gate that starts to decline at a cell's widths, or a model
    path that stops reaching its kernel, shows here and not on the chip."""
    found = cell_kernels(cell)
    assert found.get(kernel) == CELL_KERNELS[cell][kernel], found


@pytest.mark.parametrize("cell", sorted(CELL_KERNELS))
def test_benchmark_cell_holds_no_other_kernel(cell, cell_kernels):
    assert sorted(cell_kernels(cell)) == sorted(CELL_KERNELS[cell])


QWEN = "qwen3-next-80b-a3b.train-8k"


def _kernel_calls(hlo, kernel):
    """The compiled step's custom-call lines of one Pallas kernel."""
    return [ln for ln in hlo.splitlines() if " custom-call(" in ln
            and re.match(r"\s*(?:ROOT )?%?" + kernel + r"[.\d]* = ", ln)]


def test_qwen3_next_cell_runs_flash_attention_at_head_size_256(cell_hlo):
    """16 query heads on 2 kv heads, d = 256, 8,192 positions: the
    kernels' operands in the compiled step."""
    hlo = cell_hlo(QWEN)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        (call,) = _kernel_calls(hlo, kernel)
        assert "bf16[1,16,8192,256]" in call and "bf16[1,2,8192,256]" in call


def _delta_rule_operands(shape):
    """The cell's own: 8,192 positions, 16 key heads serving 32 value
    heads of 128; q and k float32 from the normalisation, v bfloat16."""
    return (shape((1, 8192, 16, 128), F32), shape((1, 8192, 16, 128), F32),
            shape((1, 8192, 32, 128)), shape((1, 8192, 32), F32),
            shape((1, 8192, 32), F32))


def test_gated_delta_rule_pair_compiles_for_v5e_at_the_cell_operands(
        shape, as_tpu):
    """The public entry takes the kernel pair at the cell's operands, and
    Mosaic compiles both: q and k read unrepeated as ``(1, 8192, 2048)``
    views, the forward keeps a state a value head and chunk and an
    inverse a head pair and chunk, the backward returns dq and dk a key
    head."""
    from paddle_tpu.incubate.nn import functional as IF
    args = _delta_rule_operands(shape)

    def loss(q, k, v, g, beta):
        return jnp.sum(IF.gated_delta_rule(q, k, v, g, beta)
                       .astype(jnp.float32))
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    (fwd,) = _kernel_calls(hlo, "gated_delta_rule_fwd")
    (bwd,) = _kernel_calls(hlo, "gated_delta_rule_bwd")
    for call in (fwd, bwd):
        assert "f32[1,8192,2048]" in call and "bf16[1,8192,4096]" in call
        assert "f32[1,32,128,128,128]" in call      # a state a chunk
        assert "f32[1,16,128,128,128]" in call      # an inverse a chunk
        assert "f32[1,16,128,1,128]" in call        # G and beta, packed
    outs = bwd.split(" custom-call(")[0]
    assert outs.count("f32[1,8192,2048]") == 2, outs    # dq, dk


def test_gated_delta_rule_gate_at_the_cell_operands(shape):
    from paddle_tpu.ops.pallas import gated_delta as m
    q, k, v, g, beta = _delta_rule_operands(shape)
    assert m.supported(q, k, v, g, beta, 64)
    assert not m.supported(q, k, v, g, beta, 128)
    odd = shape((1, 8192, 16, 192), F32)
    assert not m.supported(odd, odd, v, g, beta, 64)


def test_qwen3_next_cell_updates_the_stacked_expert_leaves_fused(cell_hlo):
    """``(32, 2048, 512)`` and ``(32, 512, 2048)`` go through
    ``fused_adamw`` folded to ``(65536, 512)`` and ``(16384, 2048)``: 12
    such calls in four layers."""
    calls = _kernel_calls(cell_hlo(QWEN), "fused_adamw")
    folded = [c for c in calls
              if "f32[65536,512]" in c or "f32[16384,2048]" in c]
    assert len(folded) == 12, len(folded)


@pytest.mark.parametrize("scope", ["gated_delta_rule", "moe_router",
                                   "moe_experts"])
def test_qwen3_next_cell_carries_its_scopes(scope, cell_hlo):
    """The three scopes that the cell's per-layer metrics read are in the
    compiled step's ``op_name``s, under the regions they belong to."""
    region = "attn_core" if scope == "gated_delta_rule" else "mlp"
    names = re.findall(r'op_name="([^"]*)"', cell_hlo(QWEN))
    mine = [n for n in names if f"/{scope}/" in n]
    assert mine, scope
    assert all(f"/{region}/" in n.split(scope)[0] + "/" for n in mine), \
        [n for n in mine if f"/{region}/" not in n][:3]
    # forward and backward alike
    assert any("transpose(jvp(forward))" in n for n in mine)


def test_fused_swiglu_mlp_block_width_at_evabyte_ffn():
    """11008 = 43 x 256 columns: the widest 128-multiple that divides it
    and is at most the default 512 is 256, a block width the Mistral
    cells (14336 = 28 x 512) never take; the gate admits it.  Token-tiled
    (training, ``generate``): token tiles of 256 rows, so a ``(512, H)``
    call reads its weights twice."""
    from paddle_tpu.ops.pallas import fused_mlp

    assert fused_mlp._blocks(512, 4096, 11008, None, None, 2) == (256, 256)
    assert fused_mlp._blocks(512, 4096, 14336, None, None, 2) == (256, 512)
    assert fused_mlp._blocks(8192, 4096, 14336, None, None, 2) == (256, 512)
    x = jax.ShapeDtypeStruct((512, 4096), jnp.bfloat16)
    w1 = jax.ShapeDtypeStruct((4096, 11008), jnp.bfloat16)
    w2 = jax.ShapeDtypeStruct((11008, 4096), jnp.bfloat16)
    assert fused_mlp.supported(x, w1, w2)


@pytest.mark.parametrize("ffn,block_i", [(14336, 512), (11008, 256)])
def test_fused_swiglu_mlp_geometry_the_serving_step_takes(ffn, block_i):
    """With the step's live count the ``(512, 4096)`` lanes take the
    weight-stationary order: the I-blocks are the token-tiled path's own
    (28 x 512 at Mistral's ffn, 43 x 256 at EvaByte's: one grid step
    each, every weight byte fetched once), token tiles of 128 rows under
    them, 41.6 / 29.4 MiB of VMEM by the estimate; a training batch's
    ``(8192, 4096)`` cannot be held and stays token-tiled."""
    from paddle_tpu.ops.pallas import _common, fused_mlp

    x = jax.ShapeDtypeStruct((512, 4096), jnp.bfloat16)
    w1 = jax.ShapeDtypeStruct((4096, ffn), jnp.bfloat16)
    assert fused_mlp._blocks(512, 4096, ffn, None, None, 2)[1] == block_i
    assert fused_mlp._live_tile(512) == 128
    assert fused_mlp.holds_live(x, w1)
    est = fused_mlp._live_vmem_estimate(512, 128, block_i, 4096, 2)
    assert est == {512: 43646976, 256: 30736384}[block_i]
    assert est <= fused_mlp.LIVE_VMEM_BUDGET < _common.VMEM_LIMIT
    assert not fused_mlp.holds_live(
        jax.ShapeDtypeStruct((8192, 4096), jnp.bfloat16), w1)


@pytest.mark.parametrize("cell,live", [("mistral-7b.serve-chat", True),
                                       ("evabyte.serve-doc-bytes", True),
                                       ("mistral-7b.train-8k", False)])
def test_serving_cells_hand_the_mlp_their_live_count(cell, live, cell_hlo):
    """Both serving cells' steps call the kernel in its weight-stationary
    order (a one-element int32 scalar-prefetch operand before x); the
    training cell's call has none."""
    calls = [ln for ln in cell_hlo(cell).splitlines()
             if re.search(r"= \S+ custom-call\(", ln)
             and "fused_swiglu_mlp" in ln.split(" = ")[0]]
    assert len(calls) == CELL_DEPTH, calls
    assert all(("operand_layout_constraints={s32[1]" in c) == live
               for c in calls), calls
