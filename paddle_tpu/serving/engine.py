"""Continuous-batching serving engine over the paged KV cache.

The throughput story (docs/SERVING.md): instead of one ``generate()``
call per tenant — dense per-sequence caches, per-sequence latency —
``Engine`` keeps ``max_batch`` slots running through ONE compiled ragged
step and admits/retires requests between steps.  Every step dispatches a
single fixed-shape batch of per-slot token SPANS — chunked-prefill
segments and single decode tokens side by side — through
:func:`incubate.nn.functional.ragged_paged_attend` (the ragged Pallas
kernel on TPU, the XLA gather fallback elsewhere), over a global block
pool shared by all requests.  Repeated prompt prefixes share physical
blocks via the hash-based prefix cache (block_allocator.PrefixCache):
admission maps hit pages into the new table, reserves only the
remainder, and the step copy-on-writes any borrowed page before writing
into it.

Recompile contract: after :meth:`warmup` — ONE compile for the unified
step, one for the program that unpacks its inputs (they cross to the
device as one buffer) and one for the CoW page-copy helper — requests of
ANY length mix joining and leaving the batch trigger ZERO further
compiles (fixed span shapes, see ``scheduler.py``; enforced by the
``serving-smoke`` CI gate).
Chunked prefill is what keeps that single shape honest: a 2k-token
prompt and a decode token ride the same ``(B, C)`` dispatch, so heavy
admission can no longer stall decode behind per-bucket prefill programs
(head-of-line TTFT — the "Ragged Paged Attention" design, PAPERS.md).

Step anatomy (one :meth:`step` call):

1. **admit**: waiting requests move into free slots while blocks last;
   prefix-cache hits skip straight to their first uncached token;
2. **plan + CoW**: each active slot gets its span (next prefill chunk,
   bounded by the per-step token budget, or its pending decode token),
   and the rows of slots that hold no request go to the prompts that
   still have tokens left, oldest admission first — a prefilling
   request may hold several rows of one step (``scheduler.plan_spans``);
   spans landing in borrowed pages trigger the copy-on-write dispatch;
3. **one ragged step**: every span's KV is scattered at its positions,
   every query row attends its prefix, one token is sampled per row —
   consumed only by requests that completed their prompt (TTFT, read
   from the row that holds the prompt's last token) or decoded;
4. **retire**: EOS / max-token requests leave their slot; their private
   full-prompt pages stay indexed in the prefix cache (evictable LRU),
   everything else returns to the free list.

Robustness (docs/SERVING.md "Front door", docs/RESILIENCE.md):
:meth:`preempt` swaps a running request's KV pages to host RAM
(``SwapManager``) instead of rejecting new work, and re-admission
restores it token-identical; a host-side failure in one request's
bookkeeping — or an injected fault at the ``serve.admit`` /
``serve.prefill`` / ``serve.step`` / ``serve.cow`` / ``serve.swap``
sites — is confined to THAT request (rewind → preempt → re-admit),
never tearing down the compiled step or the other slots.  Admission
rejections are typed (``serving.errors``).  ``serving.FrontDoor``
layers multi-tenant SLO admission on top.

Telemetry (all zero-overhead when observability is disabled):
``serve.ttft_ms``, ``serve.step_ms``, ``serve.tok_s``,
``serve.queue_depth``, ``serve.kv_blocks_used``, ``serve.active_requests``,
``serve.ragged_occupancy``, ``serve.mlp_live_tiles``,
``serve.prefill_rows``, ``serve.prefill_steps``,
``serve.prefix_hits``/``misses``,
``serve.shared_blocks``, ``serve.cached_blocks``, ``serve.cow_copies``,
``serve.preemptions``/``restores``/``swapped_pages``/
``isolated_failures``, and — with speculative decoding on —
``serve.spec.proposed``/``accepted``/``draft_errors`` +
``serve.spec.accept_len``
+ ``serve_request`` / ``serve_step`` / ``serve_finish`` /
``serve_preempt`` / ``serve_restore`` / ``serve_isolated_failure``
events and ``serve.step`` / ``serve.step.finish`` flight-recorder spans
per step (dispatch and sync/post-processing phases), tiled by the leaf
phases ``serve.step.admit`` / ``draft`` / ``plan`` / ``dispatch`` /
``sync`` / ``emit`` / ``account``, which a live profiler session sees as
``pdtpu.*`` host events (docs/OBSERVABILITY.md "Trace spans").  With request
tracing on, every lifecycle transition additionally feeds the
per-request timeline (``observability/trace.py``: submit → admit →
prefill chunks → first token → preempt/restore → retire, with exact
queue/prefill/decode phase accounting) plus the ``serve.queue_ms`` /
``serve.prefill_ms`` / ``serve.decode_ms_per_token`` histograms and
their ``serve.tenant[<t>].*`` twins — docs/OBSERVABILITY.md "Tracing a
request".
"""

from __future__ import annotations

import collections
import contextlib
import time
import traceback
import warnings
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import observability as obs
from ..observability import _state as _obs_state
from ..observability.regions import region
from ..observability.spans import span
from ..nn.layer import _swapped_params, functional_call, serving_params
from ..ops.pallas import ragged_attention as _ra
from ..ops.pallas.fused_mlp import LIVE_TILE as _MLP_LIVE_TILE
from ..resilience import _state as _rs_state
from ..resilience.retry import RetryPolicy
from .block_allocator import (PagedKVCache, PrefixCache, SwapManager,
                              cache_spec_of)
from .errors import (AdmissionError, BudgetUnsatisfiable, QueueFull,
                     UnknownAdapter)
from .scheduler import Request, RequestState, Scheduler, by_request

__all__ = ["Engine", "TokenEvent"]

# Incremental detokenization re-runs the tokenizer over a bounded tail
# window of this many tokens (re-anchoring at half-window), keeping
# streaming-text cost linear in output length instead of quadratic.
_DETOK_WINDOW = 64


class TokenEvent(NamedTuple):
    """One emitted token, as returned by ``step()``/``stream()``."""

    request_id: str
    token_id: int
    text: Optional[str]          # incremental detokenized text, if enabled
    finished: bool
    finish_reason: Optional[str]  # "eos" | "length" when finished


def _kv_geometry(model):
    """(num_layers, kv_heads, head_dim) of a CausalLM's cache pages."""
    declared = getattr(model, "kv_cache_spec", None)
    if declared is not None:
        d = declared()
        return d["layers"], d["kv_heads"], d["head_dim"]
    cfg = model.cfg
    kv = getattr(cfg, "num_key_value_heads", None) or \
        cfg.num_attention_heads
    return cfg.num_hidden_layers, kv, cfg.head_dim


def _paged_supported(model) -> bool:
    mdl = getattr(model, "model", None)
    if mdl is None or getattr(model.cfg, "pipeline_stages", 1) != 1:
        return False
    cls = getattr(type(mdl), "decoder_layer_cls", None)
    return cls is not None and getattr(cls, "supports_paged", False)


def _sample(logits, temps, key, seeds, emit):
    """Per-slot greedy (temp==0) or temperature sampling, on device.

    PRNG keys are derived per EMITTED-TOKEN INDEX, never per step:
    slot ``b``'s token at emit index ``emit[b]`` draws from
    ``fold_in(fold_in(key, seeds[b]), emit[b])``, a pure function of
    (engine key, request sample seed, emit index).  A speculative
    engine emitting several tokens in one step therefore draws the
    SAME stream as the non-speculative engine emitting one per step —
    the reproducibility contract that makes spec-on/spec-off
    temperature sampling comparable (docs/SERVING.md "Speculative
    decoding")."""
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1)
    scaled = lg / jnp.maximum(temps, 1e-6)[:, None]

    def draw(seed, idx, row):
        k = jax.random.fold_in(jax.random.fold_in(key, seed), idx)
        return jax.random.categorical(k, row)

    sampled = jax.vmap(draw)(seeds, emit, scaled)
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)


def _sample_span(logits, temps, key, seeds, emit):
    """Per-POSITION sampling over a whole ``(B, C, V)`` span — the
    speculative verify step's sampler.  Position ``j`` of slot ``b``
    uses emit index ``emit[b] + j``, so the token drawn at any given
    emit index matches :func:`_sample`'s bit-for-bit (same fold chain),
    whatever mix of spans produced it."""
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1)
    c = lg.shape[1]
    scaled = lg / jnp.maximum(temps, 1e-6)[:, None, None]

    def draw_row(seed, base, rows):
        kb = jax.random.fold_in(key, seed)

        def draw(j, row):
            return jax.random.categorical(
                jax.random.fold_in(kb, base + j), row)

        return jax.vmap(draw)(jnp.arange(c, dtype=jnp.int32), rows)

    sampled = jax.vmap(draw_row)(seeds, emit, scaled)
    return jnp.where(temps[:, None] > 0.0, sampled, greedy).astype(
        jnp.int32)


class Engine:
    """Continuous-batching serving engine (docs/SERVING.md).

    ``model`` is a Llama/GPT-family CausalLM (any config with
    ``supports_paged`` decoder layers and ``pipeline_stages == 1``);
    weights are shared with the dense training/generate() paths via
    ``serving_params``.  ``kv_cache_dtype="int8"`` allocates quantized
    pools (the :func:`quantize_kv` scales, halved KV traffic).

    ``prefill_chunk``: span width C of the unified step (default
    ``min(16, max_seq_len)``) — prompts prefill in ≤C-token chunks
    interleaved with decode, so one compiled
    ``(B, C)`` program serves every batch mix; a prompt also takes the
    rows of slots that hold no request, a chunk each, so it advances by
    up to ``(free rows + 1) * C`` tokens a step.  ``prefill_token_budget``
    caps the TOTAL prefill tokens scheduled per step, over all rows
    (default: unbounded, i.e. ``max_batch * prefill_chunk``) — on TPU
    the ragged kernel skips dead pages, so a tighter budget bounds
    per-step latency under bursty admission; a budget of one chunk
    leaves each prompt its own row only.

    ``enable_prefix_caching``: hash-based sharing of page-aligned prompt
    prefixes across requests (copy-on-write on shared-page writes, LRU
    eviction of unreferenced cached blocks).  Greedy outputs remain
    token-identical to ``model.generate()`` either way.

    ``detokenize``: optional ``callable(list[int]) -> str``; when given,
    token events and ``on_token`` callbacks carry the incremental text.
    For streaming it is called on a sliding tail window of the output
    (last ``_DETOK_WINDOW`` tokens), so tokenizers whose suffix output
    differs from the suffix of the full output may see a character-level
    seam at window re-anchors (docs/SERVING.md).

    ``keep_finished``: how many finished requests stay queryable via
    :meth:`output_ids` after completion — older ones are evicted so a
    long-running engine's per-request state stays bounded.

    ``max_queue``: bound on the waiting queue; beyond it
    ``add_request`` raises :class:`serving.errors.QueueFull` (default
    unbounded — the FrontDoor applies its own shed policy).
    ``retry``: the :class:`resilience.RetryPolicy` wrapped around
    host-side serving I/O (the preemption swap dispatches); defaults to
    3 attempts with 20 ms base backoff.

    ``weight_quant``: ``"int8"``/``"int4"`` applies the weight-only
    serving transform (``nn.quant.quantize_linears``, IN PLACE on
    ``model``) before the step traces, so decode's projection GEMVs
    stream quantized bytes — on TPU through the fused dequant-in-matmul
    kernels (ops/pallas/int8_matmul.py, int4_matmul.py).  ``page_size``
    and ``prefill_chunk`` also accept ``"auto"``: the values come from
    ``tools/tuned_configs.json`` (per model geometry and backend,
    resolved at construction — never per step).

    ``slo_capture``: an :class:`observability.SLOCapture` (or anything
    with ``on_step()``) consulted after each non-empty step — arms a
    bounded ``jax.profiler`` capture when TTFT p95 breaches its SLO for
    K consecutive windows (docs/OBSERVABILITY.md "Tracing a request").

    ``spec_decode``: self-speculative decoding (docs/SERVING.md
    "Speculative decoding") — a host-side n-gram proposer
    (``serving.spec.NgramProposer``) drafts up to ``draft_depth``
    tokens per decode slot per step and the SAME unified ragged step
    verifies the whole ``[pending, d_1..d_k]`` span like a prefill
    chunk, emitting the accepted prefix plus one bonus token.  Greedy
    outputs stay token-identical to the non-speculative engine;
    temperature slots ride the same program at ``draft_len = 0`` (v1).
    Enabling it widens the compiled span to
    ``max(prefill_chunk, draft_depth + 1)`` — ONE step program per
    engine either way, and the zero-recompile contract is unchanged
    (a slot with no viable draft is just ``draft_len = 0`` data).

    ``mesh``: a serving mesh (``serving.distributed.serving_mesh``)
    makes this engine TENSOR-PARALLEL: parameters land sharded by their
    partition specs, the paged KV pools shard their head axis over the
    mesh's ``mp`` axis (block axis replicated, so the allocator/prefix
    cache/CoW host bookkeeping is untouched), and the one compiled step
    + CoW + swap programs partition under GSPMD — same zero-recompile
    contract, greedy outputs token-identical to the single-chip engine
    (docs/SERVING.md "Sharded serving").

    ``lora``: a :class:`serving.LoRAPool` makes this engine MULTI-LORA
    (docs/SERVING.md "Multi-LoRA"): many fine-tuned adapters resident
    at once as stacked low-rank deltas, each request naming its adapter
    at ``add_request(adapter=...)`` (``FrontDoor`` maps tenants via
    ``TenantPolicy(adapter=)``).  The per-slot adapter index rides
    ``span_arrays`` as DATA into the one compiled step, where the
    grouped BGMV (``incubate.nn.functional.lora_bgmv``) adds
    ``x @ A_i @ B_i`` to every LoRA-targeted projection — mixed
    adapters in one batch, zero recompiles on adapter load/evict
    (buffer writes into the fixed-shape stacks), and base-model
    requests ride slot 0's exact no-op bitwise-unchanged.  Greedy
    outputs under adapter ``k`` are token-identical to a merged-weight
    (``W + B_k A_k``) model.  The LoRA engine pins the UNFUSED
    qkv/MLP projection path (the deltas inject pre-RoPE and around the
    activation, which the fused single-pass kernels cannot expose).

    **Cache kinds** (docs/SERVING.md "Cache kinds").  What a request's
    pages are is the model's to declare (``kv_cache_spec()``) and
    ``serving/block_allocator.py``'s to answer for: full causal
    attention's one growing table (``PagedKVSpec``: every model without
    a declaration), or EVA's exact window pages beside chunk-summary
    pages (``WindowSummarySpec``: ``models/evabyte.py``), which are
    taken as positions are written and returned when a window closes.
    The same entry points, scheduler, span fan-out and sampler serve
    both; a feature that does not serve a kind yet raises
    ``NotImplementedError`` HERE, naming both — on the window+summary
    kind: prefix caching (so say ``enable_prefix_caching=False``),
    ``role`` other than ``"both"``, ``spec_decode``, ``lora``,
    ``weight_quant``, int8 pools and a ``mesh``.

    ``role``: disaggregated serving (docs/SERVING.md "Disaggregated
    serving").  ``"both"`` (default) is the colocated engine above.
    ``"prefill"`` retires every request at prefill-complete — the first
    token is sampled and emitted (TTFT stops on this replica), the KV
    pages swap to host, the slot frees — and parks the state on
    ``handed_off`` for a ``serving.DisaggReplicaSet`` (or any driver)
    to stream to a decode replica.  ``"decode"`` receives transferred
    ``KVHandout``s via :meth:`admit_handout` and resumes decode at
    ``kv_len`` through the restore path; its ``add_request`` still
    accepts fresh prompts (the re-prefill fallback after a hard
    transfer failure).  All three roles run the SAME compiled step —
    role changes which host paths fire, never the program.
    """

    def __init__(self, model, *, max_batch: int = 8,
                 max_seq_len: int = 256, page_size: int = 16,
                 num_blocks: Optional[int] = None,
                 kv_cache_dtype=None,
                 prefill_chunk: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 enable_prefix_caching: bool = True,
                 detokenize: Optional[Callable] = None, seed: int = 0,
                 keep_finished: int = 1024,
                 max_queue: Optional[int] = None,
                 retry: Optional[RetryPolicy] = None,
                 mesh=None,
                 weight_quant: Optional[str] = None,
                 slo_capture=None,
                 spec_decode: bool = False,
                 draft_depth: int = 4,
                 role: str = "both",
                 lora=None):
        if not _paged_supported(model):
            # a model file may say what serving it still needs
            # (``paged_serving_needs``: docs/SERVING.md "Cache kinds")
            needs = getattr(model, "paged_serving_needs", None)
            raise NotImplementedError(
                f"{type(model).__name__} does not support the paged "
                "serving path (needs supports_paged decoder layers and "
                "pipeline_stages == 1)"
                + (f": serving it needs {needs}" if needs else ""))
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got "
                f"{role!r} (docs/SERVING.md \"Disaggregated serving\")")
        n_layers, kv_heads, head_dim = _kv_geometry(model)
        if page_size == "auto" or prefill_chunk == "auto":
            # tuned serving knobs (tools/tuned_configs.json): resolved
            # HERE, before any trace — warmup compiles against the
            # resolved values and steady state never re-reads them (the
            # zero-recompile contract; ops.tuning docstring)
            from ..ops import tuning
            scfg = tuning.tuned_config(
                "serving", tuning.geom_key(
                    h=model.cfg.hidden_size, l=n_layers, kv=kv_heads,
                    hd=head_dim))
            if page_size == "auto":
                page_size = scfg.get("page_size", 16)
            if prefill_chunk == "auto":
                prefill_chunk = scfg.get("prefill_chunk", None)
        if max_batch < 1 or max_seq_len < page_size:
            raise ValueError(
                f"bad geometry: max_batch={max_batch}, "
                f"max_seq_len={max_seq_len}, page_size={page_size}")
        if prefill_chunk is None:
            prefill_chunk = min(16, int(max_seq_len))
        if not 1 <= prefill_chunk <= max_seq_len:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be in "
                f"[1, max_seq_len={max_seq_len}]")
        self.spec = None
        self.draft_depth = 0
        if spec_decode:
            if not 1 <= int(draft_depth) <= max_seq_len - 1:
                raise ValueError(
                    f"draft_depth={draft_depth} must be in "
                    f"[1, max_seq_len-1={max_seq_len - 1}]")
            self.draft_depth = int(draft_depth)
            from .spec import NgramProposer
            self.spec = NgramProposer(self.draft_depth)
            # the verify span [pending, d_1..d_K] must fit the one
            # compiled (B, C) step: widen C once, HERE, before any
            # trace — warmup compiles against the widened span and
            # every draft depth 0..K rides it as span-length DATA
            prefill_chunk = max(int(prefill_chunk), self.draft_depth + 1)
        max_pos = getattr(model.cfg, "max_position_embeddings", None)
        if max_pos is not None and max_seq_len > max_pos:
            raise ValueError(
                f"max_seq_len={max_seq_len} exceeds the model's "
                f"max_position_embeddings={max_pos}")
        # the cache kind the model declares (block_allocator.py): full
        # attention's one growing table, or EVA's window pages beside
        # summary pages.  What does not serve a kind yet is refused HERE,
        # by name, never ignored (docs/SERVING.md "Cache kinds")
        cache_spec = cache_spec_of(model, page_size, max_seq_len)
        if cache_spec.kind != "kv":
            if prefill_chunk % cache_spec.page_size:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be a multiple of "
                    f"the {cache_spec.kind} cache's chunk_size="
                    f"{cache_spec.page_size} (= page_size={page_size}): a "
                    "row's span completes whole chunks")
            from ..models.generation import _is_int8
            asked = {"prefix caching (enable_prefix_caching=True)":
                     enable_prefix_caching,
                     f"role={role!r}": role != "both",
                     "spec_decode": spec_decode,
                     "lora": lora is not None,
                     "weight_quant": weight_quant is not None,
                     "int8 pools (kv_cache_dtype)": _is_int8(kv_cache_dtype),
                     "mesh": mesh is not None}
            for feature, on in asked.items():
                if on:
                    raise NotImplementedError(
                        f"{feature} does not serve a {cache_spec.kind} "
                        f"cache ({type(model).__name__}) yet — "
                        "docs/SERVING.md \"Cache kinds\"")
        if weight_quant is not None and lora is not None:
            # the stacked-delta path targets the model's float 2-D
            # projection weights; quantized layers keep int codes +
            # separate scales, so the pool's geometry check (and the
            # merged-weight identity contract) cannot hold — reject
            # loudly instead of failing with a misleading shape error
            raise ValueError(
                "Engine(lora=...) does not compose with weight_quant "
                "yet — serve LoRA adapters on the float decode path "
                "(docs/SERVING.md \"Multi-LoRA\")")
        if weight_quant is not None:
            # decode weight path (docs/KERNELS.md): swap the model's
            # Linears for weight-only quantized variants IN PLACE (the
            # serving transform, nn.quant) so the decode GEMVs stream
            # int8/int4 — on TPU through the fused dequant-in-matmul
            # kernels.  Done AFTER every constructor validation above (a
            # rejected construction must not corrupt the caller's still-
            # usable model) and before serving_params below, so the
            # quantized buffers ride the compiled step as inputs;
            # model.generate() on the same object sees the same weights,
            # keeping greedy token-identity checkable.
            from ..nn.quant import quantize_linears
            algo = {"int8": "weight_only_int8",
                    "int4": "weight_only_int4"}.get(weight_quant,
                                                    weight_quant)
            quantize_linears(model, algo=algo)
        model.eval()
        self.model = model
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.page_size = int(page_size)
        self.prefill_chunk = int(prefill_chunk)
        # a zero/negative budget would idle every prefilling slot forever
        self.prefill_token_budget = None if prefill_token_budget is None \
            else max(1, int(prefill_token_budget))
        self.cache_spec = cache_spec
        # the width of a row's block table in the step
        self.max_blocks_per_seq = cache_spec.table_width
        if num_blocks is None:
            # enough for every slot to run a full-length sequence
            num_blocks = self.max_batch * cache_spec.blocks_for(
                self.max_seq_len)
        dtype = kv_cache_dtype if kv_cache_dtype is not None else \
            getattr(model.cfg, "dtype", "float32")
        self.mesh = mesh
        self.kv = PagedKVCache(n_layers, num_blocks, self.page_size,
                               kv_heads, head_dim, dtype=dtype, mesh=mesh)
        # pages a block of the step's ragged attention kernel, set when
        # the step traces; None where it attends otherwise: what the
        # registry's count of the KV it walks needs (_attn_walk)
        self._attn_ppb: Optional[int] = None
        self.prefix_cache = PrefixCache(self.kv.allocator, self.page_size) \
            if enable_prefix_caching else None
        self.scheduler = Scheduler(self.max_batch, self.page_size,
                                   self.max_blocks_per_seq,
                                   self.kv.allocator, self.kv.oob_block,
                                   prefix_cache=self.prefix_cache,
                                   spec=cache_spec)
        # preemption/restore machinery: host-RAM page swap plus the
        # retry policy wrapped around serving host I/O (swap dispatches)
        # so a transient (or injected) fault becomes a logged retry, not
        # a dead request
        self.max_queue = None if max_queue is None else int(max_queue)
        self._retry = retry if retry is not None else \
            RetryPolicy(max_attempts=3, backoff_s=0.02)
        self._swap = SwapManager(self.kv, chunk=cache_spec.swap_chunk)
        self.params = serving_params(model)
        if mesh is not None:
            from .distributed import shard_serving_params
            self.params = shard_serving_params(model, self.params, mesh)
        self._detokenize = detokenize
        self._key = jax.random.key(seed)
        # Cross-thread state (the HTTP-handler / engine-loop boundary,
        # serving/server.py): when the engine sits behind a
        # ServingServer, handler threads reach these through
        # FrontDoor.submit while the loop thread mutates them in
        # step().  The guarding lock is ServingServer._lock; methods
        # marked `# requires-lock: _lock` must be entered with it held
        # (single-threaded drivers — tests, benches — satisfy that
        # trivially).  Checked by pdtpu-lint's lock-discipline rule.
        self._states: Dict[str, RequestState] = {}   # guarded_by: _lock
        # a long-running engine must not leak one RequestState (plus its
        # token list) per request served: only the `keep_finished` most
        # recently finished requests stay queryable via output_ids()
        self.keep_finished = int(keep_finished)
        self._finished_order: "collections.deque[str]" = \
            collections.deque()                      # guarded_by: _lock
        # set by run() while draining: finish-time output capture that
        # eviction can't outrun (None outside run(), so step()/stream()
        # users accumulate no unbounded side state)
        self._drain_capture: Optional[Dict[str, List[int]]] = \
            None                                     # guarded_by: _lock
        self._cow_copies = 0
        # lifetime serving-work accounting: seconds this engine spent in
        # its own step phases (dispatch + sync/post-processing — NOT
        # time a replica-set loop spent on its siblings) and tokens it
        # emitted.  tokens_emitted / busy_s is the per-replica rate the
        # DP aggregate-throughput projection sums (tools/decode_bench).
        self.busy_s = 0.0
        self.tokens_emitted = 0
        # SLO-triggered on-chip capture (observability.trace.SLOCapture
        # or anything with on_step()): consulted once per non-empty
        # step_finish — None (the default) costs one falsy check
        self._slo_capture = slo_capture
        # disaggregated serving (docs/SERVING.md "Disaggregated
        # serving"): a role="prefill" engine RETIRES each request at
        # prefill-complete — first token sampled and emitted (TTFT stops
        # here), pages swapped to host, slot freed — parking the state
        # on `handed_off` for a DisaggReplicaSet (or any driver) to
        # stream to a decode replica.  A role="decode" engine's work
        # arrives as transferred KVHandouts via admit_handout(); its
        # add_request path still accepts fresh prompts, which is the
        # re-prefill fallback after a hard transfer failure.  _handoff_ok
        # is an optional veto hook the replica set installs (e.g. "no
        # healthy decode replica right now" → keep decoding locally).
        # batched multi-LoRA (docs/SERVING.md "Multi-LoRA"): the stacked
        # adapter pools ride every step as fixed-shape jit inputs, so
        # the pool may be hot-loaded/evicted between steps (value edits
        # only — the zero-recompile contract extends to adapter churn)
        if lora is not None:
            lora.validate(model)
        self.lora = lora
        self.role = role
        self._handoff_ok: Optional[Callable[[], bool]] = None
        self.handed_off: "collections.deque[RequestState]" = \
            collections.deque()                  # guarded_by: _lock
        self.handoffs = 0            # lifetime prefill-complete handoffs
        self._warmed = False
        # analytic roofline minimums per warmup program (filled by
        # _publish_compiled_obs when the compiled-artifact ledger is
        # active; None keeps the disabled path at one falsy check)
        self._roofline_min_ms: Optional[Dict[str, float]] = None
        self._build_fns()

    # -- compiled paths ----------------------------------------------------

    def _build_fns(self):
        model = self.model
        spec = self.spec is not None

        def _logits_of(params, hidden):
            with _swapped_params(model, params):
                return model.logits(hidden)[:, 0]

        def step_fn(params, caches, tokens, tables, starts, lens, temps,
                    key, seeds, emit, lora_ab, adapters, cache_aux=None):
            """The ONE serving program: every slot's span (prefill
            chunk, decode token, or decode-plus-draft verify span)
            writes its KV and attends in a single ragged dispatch.
            Non-speculative engines sample one token per slot from the
            last real span position (hosts of mid-prefill slots discard
            it); speculative engines sample EVERY span position — the
            per-position argmax IS the verification (position ``j``'s
            sample is the model's token after consuming draft ``j``),
            so accept/reject needs no second dispatch.  ``lora_ab`` is
            the stacked adapter pytree (None on non-LoRA engines — the
            model path is then byte-for-byte today's) and ``adapters``
            the per-slot stack indices the grouped BGMV gathers by.
            ``cache_aux`` is what the cache kind adds to the step's
            inputs (``cache_spec.step_aux``; None for full attention's
            one table, whose program is then the one it always was)."""
            mp = {k[len("model."):]: v for k, v in params.items()
                  if k.startswith("model.")}
            kind_kw = {} if cache_aux is None else {"cache_aux": cache_aux}
            if lora_ab is not None:
                kind_kw["lora"] = (lora_ab, adapters)
            with _ra.traced_blocks() as seen:
                hidden, caches = functional_call(
                    model.model, mp, tokens, caches=caches, seq_lens=lens,
                    block_tables=tables, span_starts=starts,
                    training=False, **kind_kw)
            # trace time: the kernel's block where the dispatch chose it
            self._attn_ppb = seen[0] if seen else None
            # the model's own regions end with the final norm; the head
            # and the sampler are one region (observability/regions.py)
            with region("lm_head_loss"):
                if spec:
                    with _swapped_params(model, params):
                        lg = model.logits(hidden)          # (B, C, V)
                    return _sample_span(lg, temps, key, seeds, emit), caches
                # the last REAL span token's hidden state, not the
                # padding's
                idx = jnp.clip(lens - 1, 0,
                               tokens.shape[1] - 1)[:, None, None]
                h_last = jnp.take_along_axis(hidden, idx, axis=1)
                lg = _logits_of(params, h_last)
                return _sample(lg, temps, key, seeds, emit), caches

        def cow_fn(caches, src, dst):
            """Copy-on-write page copies src[i] → dst[i] in every layer's
            pools; padded entries carry the OOB sentinel (dropped)."""
            from ..incubate.nn.functional import paged_copy_blocks
            return [paged_copy_blocks(c, src, dst) for c in caches]

        # pools are donated: the engine owns exactly one copy in HBM
        self._step_fn = jax.jit(step_fn, donate_argnums=(1,))
        self._cow_fn = jax.jit(cow_fn, donate_argnums=(0,))

        # the step's per-row inputs cross to the device as ONE int32
        # buffer (_step_args): each array an (empty) plan's inputs hold
        # at a fixed (offset, size, shape, dtype), which the engine's own
        # sizes and its cache kind's aux_shapes fix; float32 temps travel
        # as their bits
        self._aux_keys = tuple(self.cache_spec.aux_shapes(
            self.max_batch, self.prefill_chunk) or ())
        self._layout, off = [], 0
        for a in self._host_inputs([]):
            self._layout.append((off, a.size, a.shape, a.dtype))
            off += a.size
        self._packed = np.zeros((off,), np.int32)
        # where the buffer lands: a single-chip engine's on the default
        # device, uncommitted, as jnp.asarray puts an array (so the step
        # program is the one separate arrays give); a mesh engine's
        # replicated over its own devices, so that a DP replica's unpack
        # never queues behind another replica's step on the default device
        self._packed_to = None if self.mesh is None else \
            jax.sharding.NamedSharding(self.mesh,
                                       jax.sharding.PartitionSpec())
        layout, aux_keys = tuple(self._layout), self._aux_keys

        def unpack_fn(packed):
            """The step's per-row inputs, cut from the one buffer by
            static slices: the eight arrays of ``span_arrays`` and the
            cache kind's aux dict (None where it has none)."""
            out = []
            for o, n, shape, dtype in layout:
                seg = packed[o:o + n]
                if dtype != np.int32:
                    seg = jax.lax.bitcast_convert_type(seg, dtype)
                out.append(seg.reshape(shape))
            return (*out[:8],
                    dict(zip(aux_keys, out[8:])) if aux_keys else None)

        self._unpack_fn = jax.jit(unpack_fn)

    def _lora_stacks(self):
        """The stacked adapter pytree threaded through every step — the
        pool's cached device arrays (fixed shapes, so a hot load/evict
        between steps is a new VALUE at the same jit entry), or None
        when this engine serves the base model only."""
        return self.lora.device_stacks() if self.lora is not None \
            else None

    def _trace_mesh(self):
        """Mesh-override context for trace-triggering calls: under a
        serving mesh the model's TP sharding constraints
        (``mp_layers.constrain``) must see THIS engine's mesh while the
        step traces — DP replicas each trace under their own submesh, so
        the global fleet state cannot carry it.  No-op single-chip;
        steady-state dispatches hit the jit cache and never re-enter."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from .distributed import trace_mesh
        return trace_mesh(self.mesh)

    def warmup(self) -> "Engine":
        """Compile the unified ragged step, the CoW helper, and the two
        swap programs (preemption gather/scatter) up front.

        Uses all-out-of-range block tables and zero span lengths, so the
        warmup traffic's writes are dropped — no allocator interaction,
        no pool pollution.  After this, serving traffic compiles NOTHING
        — preemption, restore, and fault-isolation churn included (the
        serving-smoke and chaos-serving gates' contract).

        Each compile group runs inside a recompile-sentinel site scope
        (serve.step / serve.cow / serve.swap / serve.lora) so the
        compiled-artifact ledger's rows land with attribution — a pure
        labelling change; the program set and compile count are
        byte-for-byte the pre-ledger warmup's."""
        tel = obs.get_telemetry()
        sent = tel.sentinel if tel is not None else None

        def _site(name):
            # warmup=True: these compiles are the expected one-per-group
            # set — attributed and counted, but never storm candidates
            # (a process may legitimately warm many engines)
            return sent.site(name, warmup=True) if sent is not None \
                else contextlib.nullcontext()

        with span("serve.warmup"), self._trace_mesh():
            with _site("serve.step"):
                # the unpack program and the step, as step_begin runs
                # them: an empty plan's inputs
                nxt, caches = self._step_fn(
                    *self._step_args(self._host_inputs([])))
                jax.block_until_ready(nxt)
            self.kv.caches = caches
            pad = np.full((self.max_batch,), self.kv.oob_block, np.int32)
            with _site("serve.cow"):
                caches = self._cow_fn(self.kv.caches, jnp.asarray(pad),
                                      jnp.asarray(pad))
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(caches)[0])
            self.kv.caches = caches
            with _site("serve.swap"):
                self._swap.warmup()
            if self.lora is not None:
                # compile the pool's per-slot scatter programs here so
                # hot-load/evict under churn stays at 0 compiles
                with _site("serve.lora"):
                    self.lora.prime_updates()
        # only AFTER the work: a failed warmup must leave step_begin's
        # auto-warmup safety net armed for mesh engines
        self._warmed = True
        self._publish_compiled_obs()
        return self

    def _attn_walk(self, plan):
        """(positions, held) of the step's KV as the ragged kernel walks
        it, from the starts and lengths it is given (the span's start, or
        its place along EVA's table): ``positions`` over all rows, each
        walking its table from 0 in whole blocks; ``held`` the most any
        one row of a request walks, summed over the step's requests, what
        one pass over each request's table reads."""
        spec, page, ppb = self.cache_spec, self.page_size, self._attn_ppb
        at = spec.cache_start if spec.kind != "kv" else int
        walked, held = 0, 0
        for spans in by_request(plan):
            rows = [_ra.positions_walked(at(sp.start), sp.n, page, ppb)
                    for sp in spans]
            walked += sum(rows)
            held += max(rows)
        return walked, held

    def _device_aux(self, plan):
        """The cache kind's further step inputs for ``plan`` (an empty
        plan: the warm-up's, whose writes all drop), on the device, each
        array moved on its own: what a lowering of the step at its shapes
        passes (the step itself takes them from :meth:`_step_args`)."""
        aux = self.cache_spec.step_aux(plan, self.max_batch,
                                       self.prefill_chunk,
                                       self.kv.oob_block)
        return None if aux is None else \
            {k: jnp.asarray(v) for k, v in aux.items()}

    def _host_inputs(self, plan):
        """The step's per-row inputs for ``plan`` (an empty plan: the
        warm-up's) as host arrays, in the packed buffer's order: the
        eight of ``span_arrays``, then the cache kind's ``step_aux``."""
        arrays = self.scheduler.span_arrays(plan, self.prefill_chunk,
                                            spec_emit=self.spec is not None)
        aux = self.cache_spec.step_aux(plan, self.max_batch,
                                       self.prefill_chunk,
                                       self.kv.oob_block)
        return arrays if aux is None else \
            arrays + tuple(aux[k] for k in self._aux_keys)

    def _step_args(self, host):
        """The step program's thirteen arguments: ``host`` (from
        :meth:`_host_inputs`) written into the one preallocated int32
        buffer, moved by ONE ``device_put``, and cut back into arrays on
        the device by ``_unpack_fn``."""
        buf = self._packed
        for (off, n, _, _), a in zip(self._layout, host):
            buf[off:off + n] = a.reshape(-1).view(np.int32)
        (tokens, tables, starts, lens, temps, seeds, emit, adapters,
         aux) = self._unpack_fn(jax.device_put(buf, self._packed_to))
        return (self.params, self.kv.caches, tokens, tables, starts, lens,
                temps, self._key, seeds, emit, self._lora_stacks(),
                adapters, aux)

    def hbm_stats(self) -> Dict[str, int]:
        """Live HBM accounting: bytes owned by each device-resident
        pool — ``kv_pool_bytes`` (the paged KV pools), ``lora_pool_bytes``
        (stacked adapter pools), ``param_bytes`` (serving weights) —
        plus ``peak_temp_bytes``, the largest XLA scratch allocation any
        compiled program needs while running (from the compiled-artifact
        ledger's memory_analysis; 0 when telemetry is off).  Pure buffer
        arithmetic — safe without telemetry, used by worker exit
        reports."""

        def _nbytes(tree) -> int:
            return sum(int(getattr(leaf, "nbytes", 0) or 0)
                       for leaf in jax.tree_util.tree_leaves(tree))

        stats = {"kv_pool_bytes": int(self.kv.nbytes()),
                 "lora_pool_bytes": _nbytes(self._lora_stacks()),
                 "param_bytes": _nbytes(self.params),
                 "peak_temp_bytes": 0}
        led = _obs_state.LEDGER[0]
        if led is not None:
            stats["peak_temp_bytes"] = max(
                (r.get("temp_bytes", 0) for r in led.snapshot()),
                default=0)
        return stats

    def _publish_compiled_obs(self) -> None:
        """Post-warmup: the ``serve.hbm.*`` gauge block and per-program
        analytic roofline minimums (``serve.roofline.<prog>.min_ms``)
        from the compiled-artifact ledger.  Cold path (runs once per
        warmup); with telemetry off it is exactly two falsy checks."""
        reg = obs.get_registry()
        led = _obs_state.LEDGER[0]
        if reg is None and led is None:
            return
        hbm = self.hbm_stats()
        if led is not None:
            # snapshot for exit reports / postmortems: the memory
            # picture survives even after the engine is gone
            led.set_hbm(hbm)
            mins: Dict[str, float] = {}
            for key, site in (("step", "serve.step"), ("cow", "serve.cow"),
                              ("swap", "serve.swap"),
                              ("lora", "serve.lora")):
                m = led.min_ms_for(site)
                if m:
                    mins[key] = m
            self._roofline_min_ms = mins
        if reg is not None:
            for k, v in hbm.items():
                reg.gauge(f"serve.hbm.{k}").set(v)
            for key, m in (self._roofline_min_ms or {}).items():
                reg.gauge(f"serve.roofline.{key}.min_ms").set(round(m, 6))

    # -- request lifecycle -------------------------------------------------

    # requires-lock: _lock — touches _states (see __init__)
    def add_request(self, prompt_ids, max_new_tokens: int = 16,
                    temperature: float = 0.0,
                    eos_token_id: Optional[int] = None,
                    on_token: Optional[Callable] = None,
                    request_id: Optional[str] = None,
                    tenant: Optional[str] = None,
                    adapter: Optional[str] = None,
                    _page_keys: Optional[List[bytes]] = None) -> str:
        """Queue one request; returns its id.  The request joins the
        running batch at the next ``step()`` with a free slot and enough
        free blocks for its budget (prompt + max_new_tokens, minus any
        prefix-cache hit).  ``adapter`` names a LoRA adapter resident in
        this engine's pool (``Engine(lora=...)``); the request then
        decodes through ``W + B_k A_k`` while sharing the batch, the
        cache and the one compiled step with every other tenant.

        Rejections are typed (``serving.errors``, all ``ValueError``
        subclasses): :class:`QueueFull` when ``max_queue`` is set and
        the waiting queue is at capacity (transient — retry later),
        :class:`BudgetUnsatisfiable` when the request can never fit this
        engine's geometry, :class:`UnknownAdapter` for an adapter this
        engine has not loaded (validated HERE, at admission — a bad
        tenant→model mapping must never strand a half-admitted
        request), plain :class:`AdmissionError` for a duplicate
        ``request_id``."""
        req = Request(prompt_ids=prompt_ids,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature),
                      eos_token_id=eos_token_id, on_token=on_token,
                      request_id=request_id, tenant=tenant,
                      adapter=adapter)
        if adapter is not None:
            if self.lora is None:
                raise UnknownAdapter(
                    f"request names adapter {adapter!r} but this engine "
                    "has no LoRA pool (Engine(lora=serving.LoRAPool(...)))")
            req.adapter_slot = self.lora.slot_of(adapter)
            # refcount from the moment the slot resolves (released below
            # on any rejection): an evict racing the admission checks
            # must hit typed AdapterInUse, never strand a half-admitted
            # request on a vanished slot
            self.lora.acquire(adapter, req.request_id)
        try:
            self._admission_checks(req, _page_keys=_page_keys)
        except Exception:
            if adapter is not None:
                self.lora.release(adapter, req.request_id)
            raise
        tr = _obs_state.TRACE[0]
        if tr is not None:
            # get-or-create: a door-submitted request already began its
            # trace at door submit (queue time there is queue time here)
            req.trace_id = tr.begin(
                req.request_id, tenant=req.tenant, trace_id=req.trace_id,
                prompt_len=int(req.prompt_ids.size),
                max_new=req.max_new_tokens)
        reg = obs.get_registry()
        if reg is not None:
            reg.counter("serve.requests").inc()
            reg.gauge("serve.queue_depth").set(self.scheduler.queue_depth())
            if adapter is not None:
                reg.counter(
                    f"serve.lora.adapter[{adapter}].requests").inc()
        return req.request_id

    # add_request's validate+submit body, split out so the adapter
    # refcount above wraps EVERY rejection path
    # requires-lock: _lock — touches _states
    def _admission_checks(self, req: Request,
                          _page_keys: Optional[List[bytes]] = None):
        if req.request_id in self._states:
            # a silent overwrite would orphan the first request's slot /
            # blocks bookkeeping and lose its output
            raise AdmissionError(
                f"request_id {req.request_id!r} is already in use by a "
                "live or retained request")
        if self.max_queue is not None \
                and self.scheduler.queue_depth() >= self.max_queue:
            raise QueueFull(
                f"waiting queue is at max_queue={self.max_queue} — "
                "retry later (or put a serving.FrontDoor in front for "
                "retry-after answers instead of exceptions)")
        p = int(req.prompt_ids.size)
        if p + req.max_new_tokens > self.max_seq_len:
            raise BudgetUnsatisfiable(
                f"prompt ({p}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds max_seq_len={self.max_seq_len}")
        need = self.scheduler.blocks_for(p + req.max_new_tokens)
        if need > self.kv.num_blocks:
            # an unsatisfiable reservation would sit at the queue head
            # forever and make run()/stream() spin — reject it up front
            raise BudgetUnsatisfiable(
                f"request needs {need} KV blocks (prompt {p} + "
                f"max_new_tokens {req.max_new_tokens} @ page "
                f"{self.page_size}) but the pool has only "
                f"{self.kv.num_blocks} — raise num_blocks or lower the "
                "budget")
        # _page_keys: prompt page digests a router already computed for
        # its affinity probe — forwarded so submit() does not re-run the
        # O(prompt) blake2b chain (serving/distributed.py)
        st = self.scheduler.submit(req, page_keys=_page_keys)
        self._states[req.request_id] = st

    # requires-lock: _lock
    def output_ids(self, request_id: str) -> List[int]:
        return list(self._states[request_id].output_ids)

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    @property
    def kv_blocks_used(self) -> int:
        return self.kv.allocator.used_blocks

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-cache counters (hits/misses/hit_rate/registered_pages/
        evictions) plus the current CoW copy count — zeros when prefix
        caching is disabled."""
        s = self.prefix_cache.stats() if self.prefix_cache is not None \
            else {"hits": 0, "misses": 0, "hit_rate": 0.0,
                  "registered_pages": 0, "evictions": 0}
        s["cow_copies"] = self._cow_copies
        return s

    # -- preemption / restore / fault isolation ----------------------------

    # requires-lock: _lock — reads _states
    def preempt(self, request_id: str, *, requeue_head: bool = False,
                reason: str = "preempted") -> bool:
        """Swap a RUNNING request's KV pages to host RAM, free its
        blocks and slot, and requeue it for transparent restoration —
        the front door's alternative to rejecting new work when the
        pool is tight (docs/SERVING.md "Front door").

        Returns False when the request is not currently in a slot
        (waiting, already preempted, finished, or unknown).  The
        restored request resumes token-identical under greedy decoding:
        the swap round-trips the exact page bytes (int8 scales
        included), and shared prefix pages are only COPIED — never
        pulled out from under the other slots referencing them."""
        st = self._states.get(request_id)
        if st is None or st.finished or st.slot is None:
            return False
        self._preempt_state(st, head=requeue_head, reason=reason)
        return True

    def _preempt_state(self, st: RequestState, head: bool,
                       reason: str) -> None:
        # the pages that hold content, of whatever kind, in the order
        # the restore lists them again (cache_spec.held_ids)
        ids = self.cache_spec.held_ids(st)
        pages = len(ids)
        host = None
        if pages:
            host = self._retry.run(self._swap.swap_out, ids,
                                   site="serve.swap")
        self.scheduler.release_slot(st)
        # everything comes back private at restore: for the shared-pages
        # gauge the borrowed pages count as privatized from here on
        st.num_cowed = st.num_shared
        st.swapped = (pages, host)
        st.preempts += 1
        self.scheduler.requeue(st, head=head)
        tr = _obs_state.TRACE[0]
        if tr is not None:
            tr.transition(st.request.request_id, "queue", event="preempt",
                          reason=reason, pages=pages, kv_len=st.kv_len)
        reg = obs.get_registry()
        if reg is not None:
            reg.counter("serve.preemptions").inc()
            if pages:
                reg.counter("serve.swapped_pages").inc(pages)
        obs.emit_event("serve_preempt", id=st.request.request_id,
                       tenant=st.request.tenant, pages=pages,
                       kv_len=st.kv_len, reason=reason,
                       preempts=st.preempts)

    def _restore(self, st: RequestState) -> None:
        """Scatter a freshly re-admitted request's host payload into its
        new (all-private) blocks; prefill/decode resumes at kv_len."""
        pages, host = st.swapped
        if pages:
            self._retry.run(self._swap.swap_in,
                            self.cache_spec.held_ids(st), host,
                            site="serve.swap")
        st.swapped = None
        tr = _obs_state.TRACE[0]
        if tr is not None:
            tr.point(st.request.request_id, "restore", pages=pages,
                     kv_len=st.kv_len)
        reg = obs.get_registry()
        if reg is not None:
            reg.counter("serve.restores").inc()
        obs.emit_event("serve_restore", id=st.request.request_id,
                       tenant=st.request.tenant, pages=pages,
                       kv_len=st.kv_len)

    def _isolate(self, st: RequestState, exc: Exception) -> None:
        """Confine a failing request to ITS slot: the compiled step and
        the batch's other requests survive; the victim is preempted to
        host and transparently re-admitted (queue head — it was
        mid-flight).  Greedy outputs stay token-identical because the
        caller rewound the host bookkeeping to the pre-span snapshot
        and re-running a span is idempotent (same values, same
        positions)."""
        rid = st.request.request_id
        warnings.warn(
            f"request {rid!r} failed host-side and was isolated "
            f"(preempt + re-admit; {type(exc).__name__}: {exc})",
            RuntimeWarning, stacklevel=3)
        reg = obs.get_registry()
        if reg is not None:
            reg.counter("serve.isolated_failures").inc()
        obs.emit_event("serve_isolated_failure", id=rid,
                       tenant=st.request.tenant,
                       exc=type(exc).__name__, message=str(exc)[:200])
        tr = _obs_state.TRACE[0]
        if tr is not None:
            tr.point(rid, "isolated", exc=type(exc).__name__)
        self._preempt_state(st, head=True, reason="isolated_failure")

    # requires-lock: _lock — drains scheduler.waiting
    def _admit_all(self) -> None:
        """Admission loop with the ``serve.admit`` fault site: an
        injected/host fault here leaves the queue intact (nothing has
        been allocated yet) and admission simply resumes next step."""
        fi = _rs_state.FAULTS[0]
        while self.scheduler.waiting:
            if fi is not None:
                try:
                    fi("serve.admit")
                except Exception as e:  # noqa: BLE001
                    reg = obs.get_registry()
                    if reg is not None:
                        reg.counter("serve.isolated_failures").inc()
                    obs.emit_event(
                        "serve_isolated_failure", id=None, tenant=None,
                        exc=type(e).__name__, message=str(e)[:200],
                        site="serve.admit")
                    break
            st = self.scheduler.admit_next()
            if st is None:
                break
            tr = _obs_state.TRACE[0]
            if tr is not None:
                # queue→slot transition: closes the queue-wait segment
                # (first admission AND each post-preempt re-admission)
                tr.transition(
                    st.request.request_id,
                    "prefill" if st.prefilling else "decode",
                    event="admit", slot=st.slot, kv_len=st.kv_len,
                    cached_tokens=st.cached_tokens)
            if st.swapped is not None:
                self._restore(st)

    # -- the loop ----------------------------------------------------------

    def _grow_pages(self, plan):
        """(A cache kind that reserves nothing ahead: window+summary.)
        Take the pages this step's spans write, oldest admission first.
        Where the pool cannot give them, a request first gives up its
        fan-out rows, last first (its prompt advances by less this
        step); where its own row still cannot be served, the YOUNGEST
        running request is preempted to host, itself if that is it, and
        comes back when the pool can hold its peak (``spec.restore``).
        The oldest request always gets its pages: ``add_request`` refuses
        a peak above the pool.  Returns the plan less what was given up.
        Runs inside ``serve.step.plan``."""
        spec, alloc = self.cache_spec, self.kv.allocator
        keeps = {}                       # id(request state) -> spans kept
        for spans in sorted(by_request(plan),
                            key=lambda g: g[0].st.admit_seq):
            st = spans[0].st
            keep = len(spans)
            while st.slot is not None:   # else: preempted for an older one
                end = spans[keep - 1].start + spans[keep - 1].n
                if alloc.can_allocate(spec.short(st, end)):
                    spec.grow(st, end, alloc)
                    keeps[id(st)] = keep
                    break
                if keep > 1:
                    keep -= 1
                    continue
                victim = max((s_ for _, s_ in self.scheduler.active()),
                             key=lambda s_: s_.admit_seq)
                self._preempt_state(victim, head=True,
                                    reason="pool_exhausted")
        # the plan's own order (by_request relies on it), less the rows
        # given up: a request's spans come in start order
        out, seen = [], {}
        for sp in plan:
            k = seen.get(id(sp.st), 0)
            seen[id(sp.st)] = k + 1
            if sp.st.slot is not None and k < keeps.get(id(sp.st), 0):
                out.append(sp)
        return out

    def _close_windows(self, advanced) -> None:
        """(window+summary caches.)  After a step's consumption: count
        the summary rows its spans completed, and give the pages of every
        window a request has left back to the allocator — in the
        ``step_finish`` that consumed the window's last position.  Runs
        inside ``serve.step.emit``, after every request's events: a
        request isolated by a fault was rewound first and is not here."""
        spec, alloc = self.cache_spec, self.kv.allocator
        reg = obs.get_registry()
        tr = _obs_state.TRACE[0]
        rows = closed = freed = 0
        for st, kv_before in advanced:
            rows += st.kv_len // spec.page_size - kv_before // spec.page_size
            if st.pages is None:
                continue                 # finished: its pages all went
            n, f = spec.close(st, alloc)
            closed, freed = closed + n, freed + f
            if n and tr is not None:
                tr.point(st.request.request_id, "window_close",
                         windows=n, pages_freed=f, kv_len=st.kv_len)
        if reg is not None:
            reg.counter("serve.eva.summary_rows").inc(rows)
            reg.counter("serve.eva.windows_closed").inc(closed)
            reg.counter("serve.eva.pages_freed").inc(freed)

    def _run_cow(self, plan):
        """Copy-on-write: any request about to write into a borrowed
        (shared) page gets a private copy first — the reserved spare
        block takes the page's content via one fixed-shape device copy,
        the table is repointed, and the shared reference is dropped.
        The pages are those of the request's whole fan this step, once
        per request.  Returns the plan minus any request isolated by a
        ``serve.cow`` fault (fired BEFORE that request's tables are
        touched, so isolation sees consistent state)."""
        fi = _rs_state.FAULTS[0]
        copies = []
        dropped = set()
        for spans in by_request(plan):
            st = spans[0].st
            if not st.borrowed:
                continue
            first = spans[0].start // self.page_size
            last = (spans[-1].start + spans[-1].n - 1) // self.page_size
            pgs = [pg for pg in range(first, last + 1) if pg in st.borrowed]
            if not pgs:
                continue
            if fi is not None:
                try:
                    fi("serve.cow")
                except Exception as e:  # noqa: BLE001
                    # nothing mutated for this request yet this step:
                    # plain isolation, and its spans leave the plan
                    self._isolate(st, e)
                    dropped.add(id(st))
                    continue
            for pg in pgs:
                src = int(st.table[pg])
                dst = st.cow_spare.pop(pg)
                st.table[pg] = dst
                st.borrowed.discard(pg)
                st.num_cowed += 1
                st.blocks.remove(src)
                self.kv.allocator.free([src])   # drop OUR shared ref
                copies.append((src, dst))
        if dropped:
            plan = [sp for sp in plan if id(sp.st) not in dropped]
        if not copies:
            return plan
        k = self.max_batch
        for lo in range(0, len(copies), k):
            batch = copies[lo:lo + k]
            src = np.full((k,), self.kv.oob_block, np.int32)
            dst = np.full((k,), self.kv.oob_block, np.int32)
            for j, (s_, d_) in enumerate(batch):
                src[j], dst[j] = s_, d_
            self.kv.caches = self._cow_fn(self.kv.caches,
                                          jnp.asarray(src),
                                          jnp.asarray(dst))
        self._cow_copies += len(copies)
        reg = obs.get_registry()
        if reg is not None:
            reg.counter("serve.cow_copies").inc(len(copies))
        return plan

    def _register_prefix(self, st: RequestState) -> None:
        """Index this request's freshly-written full prompt pages so
        later requests with the same prefix hit them.  Pages borrowed
        from the cache are already indexed (register no-ops on a live
        key); first writer wins when two identical prompts prefill
        concurrently."""
        if self.prefix_cache is None:
            return
        for pg, key in enumerate(st.page_keys):
            self.prefix_cache.register(key, int(st.table[pg]))

    # -- disaggregated roles (docs/SERVING.md "Disaggregated serving") -----

    def _prepare_handoff(self, st: RequestState, tok: int):
        """Stage a prefill-complete request's handoff to a decode
        replica: when this engine is ``role="prefill"``, the request
        will keep decoding, and the handoff hook (if any) approves,
        gather its KV pages to host and return ``(pages, payload)`` for
        :meth:`_commit_handoff`.  Returns None to decode locally — a
        finishing request, a vetoed handoff (no decode capacity), or a
        hard swap failure (nothing has mutated yet, so degrading to
        local decode is free and the request is never lost)."""
        if self.role != "prefill":
            return None
        req = st.request
        if req.eos_token_id is not None and tok == req.eos_token_id:
            return None              # finishes right here: plain retire
        if len(st.output_ids) + 1 >= req.max_new_tokens:
            return None
        ok = self._handoff_ok
        if ok is not None and not ok():
            return None              # the set vetoed: decode locally
        pages = -(-st.kv_len // self.page_size)
        try:
            ids = [int(b) for b in st.table[:pages]]
            return pages, self._retry.run(self._swap.swap_out, ids,
                                          site="serve.swap")
        except Exception as e:  # noqa: BLE001 — hard swap failure
            warnings.warn(
                f"handoff swap-out for request {req.request_id!r} "
                f"failed ({type(e).__name__}: {e}); decoding locally",
                RuntimeWarning, stacklevel=3)
            reg = obs.get_registry()
            if reg is not None:
                reg.counter("serve.handoff_failures").inc()
            return None

    # requires-lock: _lock — parks onto handed_off
    def _commit_handoff(self, st: RequestState, handoff) -> None:
        """Retire a prefill-complete request from THIS engine: free its
        slot and blocks (the prompt pages stay indexed in the prefix
        cache as evictable capacity — the prefill tier keeps its hit
        rate), park the swapped state on ``handed_off`` for the replica
        set to stream to a decode replica.  The state carries the
        emitted first token as ``pending_token``, so the decode side
        resumes exactly where a colocated engine would."""
        pages, host = handoff
        self.scheduler.release_slot(st)
        # everything comes back private at restore: for the shared-pages
        # gauge the borrowed pages count as privatized from here on
        st.num_cowed = st.num_shared
        st.swapped = (pages, host)
        st.handoffs += 1
        self.handoffs += 1
        if self.lora is not None and st.request.adapter is not None:
            # the request leaves THIS engine; the decode tier's
            # admit_handout re-acquires on its pool (same object
            # in-process — the id-keyed refcount makes that a no-op
            # overlap, not a double count)
            self.lora.release(st.request.adapter, st.request.request_id)
        self.handed_off.append(st)
        reg = obs.get_registry()
        if reg is not None:
            reg.counter("serve.handoffs").inc()
            if pages:
                reg.counter("serve.swapped_pages").inc(pages)
        obs.emit_event("serve_handoff", id=st.request.request_id,
                       tenant=st.request.tenant, pages=pages,
                       kv_len=st.kv_len, handoffs=st.handoffs)

    # requires-lock: _lock — touches _states
    def admit_handout(self, handout, *, on_token: Optional[Callable] = None,
                      head: bool = False) -> str:
        """Queue a transferred :class:`~paddle_tpu.serving.KVHandout`
        (bytes straight off a transport, or an already-decoded one) for
        admission — the disaggregated decode replica's intake path.  The
        next step with a free slot and enough blocks restores the pages
        through the compiled swap scatter and decode resumes at
        ``kv_len``; no token is ever re-prefilled.  ``on_token``
        re-attaches a host-local streaming callback (callbacks cannot
        ride the wire format; in-process drivers pass the original)."""
        from .disagg import KVHandout
        if isinstance(handout, (bytes, bytearray, memoryview)):
            handout = KVHandout.from_bytes(bytes(handout))
        st = handout.to_state(on_token=on_token)
        req = st.request
        rid = req.request_id
        if rid in self._states:
            raise AdmissionError(
                f"request_id {rid!r} is already in use by a live or "
                "retained request")
        if req.adapter is not None:
            # the adapter NAME is the wire identity; the slot index is
            # engine-local and re-resolves against THIS engine's pool
            # (typed UnknownAdapter before any state lands — disagg
            # tiers must load the same adapters)
            if self.lora is None:
                raise UnknownAdapter(
                    f"handout {rid!r} names adapter {req.adapter!r} but "
                    "this engine has no LoRA pool")
            req.adapter_slot = self.lora.slot_of(req.adapter)
        total = int(req.prompt_ids.size) + req.max_new_tokens
        if total > self.max_seq_len or \
                self.scheduler.blocks_for(total) > self.kv.num_blocks:
            raise BudgetUnsatisfiable(
                f"handout {rid!r} needs {total} positions / "
                f"{self.scheduler.blocks_for(total)} KV blocks but this "
                f"engine caps at max_seq_len={self.max_seq_len}, "
                f"{self.kv.num_blocks} blocks — disaggregated roles "
                "must share geometry")
        if st.swapped is not None:
            _pages, host = st.swapped
            ok = len(host) == len(self.kv.caches) and all(
                len(hl) == len(cl) and all(
                    tuple(h.shape[1:]) == tuple(c.shape[1:])
                    and np.dtype(h.dtype) == np.dtype(c.dtype)
                    for h, c in zip(hl, cl))
                for hl, cl in zip(host, self.kv.caches))
            if not ok:
                # a mismatched payload would retrace the swap scatter
                # (breaking the zero-recompile contract) or silently
                # corrupt the restored KV — reject before any state lands
                raise ValueError(
                    "handout payload geometry does not match this "
                    "engine's paged pools (page_size / kv heads / "
                    "head_dim / cache dtype must agree across roles)")
        self._states[rid] = st
        self.scheduler.requeue(st, head=head)
        if self.lora is not None and req.adapter is not None:
            # request-id keyed: re-acquire after a shared-pool handoff
            # is idempotent, a distinct-pool decode tier counts its own
            self.lora.acquire(req.adapter, rid)
        tr = _obs_state.TRACE[0]
        if tr is not None:
            # get-or-create keyed by request id: in-process, the trace
            # begun at the door/prefill side just continues; on a
            # separate decode host a fresh timeline begins under the
            # trace id carried by the handout
            req.trace_id = tr.begin(rid, tenant=req.tenant,
                                    trace_id=req.trace_id,
                                    prompt_len=int(req.prompt_ids.size),
                                    max_new=req.max_new_tokens)
        reg = obs.get_registry()
        if reg is not None:
            reg.counter("serve.handouts_admitted").inc()
            reg.gauge("serve.queue_depth").set(self.scheduler.queue_depth())
        return rid

    # requires-lock: _lock — retires into _states/_finished_order/_drain_capture
    def _emit(self, st: RequestState, token: int,
              events: List[TokenEvent]):
        req = st.request
        st.output_ids.append(token)
        text = None
        if self._detokenize is not None:
            # linear-cost streaming: detokenize only a bounded tail
            # window, emit its growth, and re-anchor at half-window so
            # per-token work never scales with the full output length
            w = st.detok_offset
            full = self._detokenize(list(st.output_ids[w:]))
            text = full[st.text_len:]
            st.text_len = len(full)
            if len(st.output_ids) - w >= _DETOK_WINDOW:
                st.detok_offset = len(st.output_ids) - _DETOK_WINDOW // 2
                st.text_len = len(self._detokenize(
                    list(st.output_ids[st.detok_offset:])))
        done_eos = (req.eos_token_id is not None
                    and token == req.eos_token_id)
        done_len = len(st.output_ids) >= req.max_new_tokens
        if done_eos or done_len:
            self.scheduler.finish(st, "eos" if done_eos else "length")
            if self.lora is not None and req.adapter is not None:
                # the adapter's slot becomes evictable once its last
                # live reader retires
                self.lora.release(req.adapter, req.request_id)
            if self.spec is not None:
                # bounded proposer retention: the n-gram index dies
                # with the request (it rebuilds lazily if the id is
                # ever reused)
                self.spec.drop(req.request_id)
            tr = _obs_state.TRACE[0]
            if tr is not None:
                spec_kw = {} if self.spec is None else {
                    "spec_proposed": st.spec_proposed,
                    "spec_accepted": st.spec_accepted}
                tr.retire(req.request_id, reason=st.finish_reason,
                          tokens=len(st.output_ids), **spec_kw)
            if self._drain_capture is not None:
                # BEFORE the eviction below: when more requests than
                # keep_finished retire in one step, the state may be
                # gone by the time run() sees the events
                self._drain_capture[req.request_id] = list(st.output_ids)
                st.drained = True
            self._finished_order.append(req.request_id)
            while len(self._finished_order) > self.keep_finished:
                self._states.pop(self._finished_order.popleft(), None)
            reg = obs.get_registry()
            if reg is not None:
                reg.counter("serve.finished").inc()
            obs.emit_event(
                "serve_finish", id=req.request_id,
                reason=st.finish_reason, tokens=len(st.output_ids),
                ms=round((time.perf_counter() - st.submit_t) * 1e3, 3))
        else:
            st.pending_token = token
        events.append(TokenEvent(req.request_id, token, text, st.finished,
                                 st.finish_reason))
        if req.on_token is not None:
            try:
                req.on_token(req.request_id, token, text)
            except Exception:
                # a raising callback must not tear down the whole step:
                # the batch's other requests already produced events this
                # step and their consumers would silently lose them
                warnings.warn(
                    f"on_token callback for request "
                    f"{req.request_id!r} raised; continuing "
                    f"({traceback.format_exc(limit=3).strip()})",
                    RuntimeWarning, stacklevel=2)

    def _propose_drafts(self) -> None:
        """Attach this step's n-gram draft to every eligible decode
        slot (``serving/spec.py``).  Drafting is BEST-EFFORT: a propose
        failure — including an injected ``serve.spec`` fault — degrades
        THAT slot to ``draft_len = 0`` (a plain decode step through the
        same compiled program); it never isolates the request or tears
        into the step.  The cap keeps speculative KV inside the pages
        the request reserved at admission and accepted tokens inside
        its remaining output budget — rollback can then always be pure
        kv_len bookkeeping."""
        fi = _rs_state.FAULTS[0]
        for _i, st in self.scheduler.active():
            st.draft = []
            if st.prefilling or st.request.temperature > 0.0:
                continue             # v1: greedy slots only
            cap = min(self.draft_depth,
                      st.total_len - (st.kv_len + 1),
                      st.request.max_new_tokens - len(st.output_ids) - 1)
            if cap < 1:
                continue
            try:
                if fi is not None:
                    fi("serve.spec")
                st.draft = self.spec.propose(st, cap)
            except Exception as e:  # noqa: BLE001
                self.spec.errors += 1
                reg = obs.get_registry()
                if reg is not None:
                    reg.counter("serve.spec.draft_errors").inc()
                obs.emit_event("serve_spec_error",
                               id=st.request.request_id,
                               exc=type(e).__name__,
                               message=str(e)[:200])
                st.draft = []

    def spec_stats(self) -> Dict[str, float]:
        """Speculative-decoding counters (proposed/accepted/
        accept_rate/verifies/draft_hits/draft_misses/errors/
        tracked_requests) — zeros when ``spec_decode`` is off."""
        if self.spec is None:
            return {"proposed": 0, "accepted": 0, "accept_rate": 0.0,
                    "verifies": 0, "draft_hits": 0, "draft_misses": 0,
                    "errors": 0, "tracked_requests": 0}
        return self.spec.stats()

    def lora_stats(self) -> Dict[str, float]:
        """Multi-LoRA pool counters (active_adapters/max_adapters/rank/
        loads/evictions/live_refs) — zeros when no pool is attached."""
        if self.lora is None:
            return {"active_adapters": 0, "max_adapters": 0, "rank": 0,
                    "loads": 0, "evictions": 0, "live_refs": 0}
        return self.lora.stats()

    def step_begin(self):
        """Admit + plan + CoW + DISPATCH the compiled step without
        waiting for the device; returns the opaque pending handle
        :meth:`step_finish` consumes.  The two-phase split is what lets
        a DP replica set keep every replica's device busy: dispatch all
        replicas back-to-back, then finish them in order, so replica
        ``j``'s compute overlaps replica ``i``'s host bookkeeping
        (serving/distributed.py)."""
        if self.mesh is not None and not self._warmed:
            # a mesh engine must never trace its programs outside the
            # trace-mesh context (the TP constraints would resolve
            # against global fleet state, or nothing) — warm up now
            self.warmup()
        t0 = time.perf_counter()
        # serve.step stays for the flight ring's breadcrumbs; its leaf
        # phases tile it, so that on a profiler's trace a gap of the
        # device has one owner (docs/OBSERVABILITY.md "Trace spans")
        with span("serve.step", emit=False):
            with span("serve.step.admit", emit=False):
                self._admit_all()
            if self.spec is not None:
                with span("serve.step.draft", emit=False):
                    self._propose_drafts()
            with span("serve.step.plan", emit=False):
                plan = self.scheduler.plan_spans(self.prefill_chunk,
                                                 self.prefill_token_budget)
                if plan and not self.cache_spec.reserves_ahead:
                    plan = self._grow_pages(plan)
                if plan:
                    plan = self._run_cow(plan)
                live_tokens = sum(sp.n for sp in plan)
                if plan:
                    host = self._host_inputs(plan)
            nxt = None
            if plan:
                # ONE device_put of a ready numpy buffer, the step's
                # per-row arrays packed into it (_step_args), then the
                # compiled unpack: each transfer costs far more than its
                # bytes, so one replaces the eight (eleven with a cache
                # kind's aux).  Ready numpy only: jnp.asarray of a Python
                # list/scalar traces a tiny program whose one-off compile
                # would break the zero-compiles-after-warmup contract —
                # draft length reaches the step ONLY inside the packed
                # arrays (span lens/tokens), never as a per-step Python
                # scalar (pdtpu-lint R4f).  The same rule covers adapter
                # ids: per-slot DATA in the adapters array, never a
                # static argument.
                with span("serve.step.dispatch", emit=False):
                    args = self._step_args(host)
                    # two empty marks around the call of the step program,
                    # on the profiler's clock: the call's host time, and
                    # with the device's run the launch after it and the
                    # clocks' offset (docs/OBSERVABILITY.md "Trace spans")
                    with span("serve.step.launch", emit=False):
                        pass
                    nxt, caches = self._step_fn(*args)
                    with span("serve.step.launched", emit=False):
                        pass
                    self.kv.caches = caches
        # busy accounting covers THIS engine's own engagement only
        # (begin and finish timed separately): under a replica set the
        # phases interleave across engines, so begin-to-finish wall
        # clock would charge every engine for its siblings' slices.
        # The same own-time sum feeds serve.step_ms / serve.tok_s in
        # step_finish and the DP throughput projection (decode_bench).
        begin_s = time.perf_counter() - t0
        self.busy_s += begin_s
        return plan, nxt, live_tokens, begin_s

    # requires-lock: _lock — reads _states (per-adapter token counters)
    def step_finish(self, pending) -> List[TokenEvent]:
        """Wait for a :meth:`step_begin` dispatch and run its host
        post-processing: sample consumption, retirement, events,
        per-request fault isolation, telemetry.  ``step_begin`` and
        ``step_finish`` must alternate on one engine (the replica set's
        loop does); :meth:`step` composes them for everyone else."""
        plan, nxt, live_tokens, begin_s = pending
        tf = time.perf_counter()
        events: List[TokenEvent] = []
        # own span so a crash in device sync / post-processing still
        # lands inside a serve.step.* breadcrumb pair on the flight
        # ring (the serve.step span closed with step_begin's dispatch);
        # its three leaf phases tile it
        with span("serve.step.finish", emit=False):
            if plan:
                # np.asarray is the device sync (JAX dispatch is async):
                # the one phase in which an idle device is not the
                # host's doing, and the TTFT clock in _finish_events
                # stops only after the first token has materialized
                with span("serve.step.sync", emit=False):
                    nxt = np.asarray(nxt)
            with span("serve.step.emit", emit=False):
                self._finish_events(plan, nxt, events)
            with span("serve.step.account", emit=False):
                n_tok = len(events)
                now = time.perf_counter()
                # this engine's own step time: begin + finish phases,
                # excluding any sibling-replica slices interleaved
                # between them
                dt = begin_s + (now - tf)
                self.busy_s += now - tf
                self.tokens_emitted += n_tok
                self._account_step(plan, events, live_tokens, dt)
        return events

    # requires-lock: _lock — reads _states (per-adapter token counters)
    def _account_step(self, plan, events, live_tokens: int,
                      dt: float) -> None:
        """The registry / gauge / ``serve_step`` block of a finished
        step (the ``serve.step.account`` phase)."""
        n_tok = len(events)
        reg = obs.get_registry()
        if reg is not None and plan:
            reg.counter("serve.tokens").inc(n_tok)
            if self.lora is not None:
                # per-adapter token accounting AFTER isolation filtered
                # the events (a rewound span's tokens re-emit after
                # restore and must not count twice); aggregated first so
                # the registry sees one inc per adapter, not per token
                per_ad: Dict[str, int] = {}
                for ev in events:
                    est = self._states.get(ev.request_id)
                    ad = est.request.adapter if est is not None else None
                    if ad is not None:
                        per_ad[ad] = per_ad.get(ad, 0) + 1
                for ad, n in per_ad.items():
                    reg.counter(
                        f"serve.lora.adapter[{ad}].tokens").inc(n)
            reg.gauge("serve.tok_s").set(round(n_tok / max(dt, 1e-9), 1))
            reg.gauge("serve.queue_depth").set(self.scheduler.queue_depth())
            reg.gauge("serve.kv_blocks_used").set(
                self.kv.allocator.used_blocks)
            if not self.cache_spec.reserves_ahead:
                held = [self.cache_spec.counts(s_)
                        for _, s_ in self.scheduler.active()]
                reg.gauge("serve.eva.window_blocks").set(
                    sum(h[0] for h in held))
                reg.gauge("serve.eva.summary_blocks").set(
                    sum(h[1] for h in held))
            reg.gauge("serve.active_requests").set(
                len(self.scheduler.active()))
            reg.histogram("serve.step_ms").observe(dt * 1e3)
            # how full the ragged dispatch ran: real span tokens over the
            # (B, C) capacity — low occupancy means idle lanes, not bugs
            reg.histogram("serve.ragged_occupancy").observe(
                live_tokens / (self.max_batch * self.prefill_chunk))
            # token tiles the step's MLP kernel multiplied (its cost
            # follows the live tokens, ops/pallas/fused_mlp.py): of
            # ceil(B * C / tile), how few
            reg.histogram("serve.mlp_live_tiles").observe(
                -(-live_tokens // _MLP_LIVE_TILE))
            if self._attn_ppb is not None:
                walked, held = self._attn_walk(plan)
                reg.histogram("serve.attn_positions_walked").observe(walked)
                reg.histogram("serve.attn_positions_held").observe(held)
            reg.gauge("serve.cached_blocks").set(
                self.kv.allocator.cached_blocks)
            # pages still physically shared: admission hits minus the
            # ones CoW has since privatized
            reg.gauge("serve.shared_blocks").set(
                sum(s.num_shared - s.num_cowed
                    for _, s in self.scheduler.active()))
            # roofline attribution: measured step wall vs the analytic
            # minimum of the ONE compiled step program (constant per
            # warmup — serve.roofline.step.min_ms).  frac is limit over
            # measured (1.0 = running at the hardware roofline); the
            # step is classed prefill or decode by which token kind
            # dominated its span plan, so the two regimes' distance
            # from the limit is scrapeable separately.
            rf = self._roofline_min_ms
            if rf is not None:
                m = rf.get("step")
                if m:
                    frac = round(m / max(dt * 1e3, 1e-9), 4)
                    n_pref = sum(sp.n for sp in plan if sp.is_prefill)
                    cls = "prefill" if 2 * n_pref >= live_tokens \
                        else "decode"
                    reg.gauge("serve.roofline.step.frac").set(frac)
                    reg.gauge(f"serve.roofline.{cls}.frac").set(frac)
        if plan:
            obs.emit_event("serve_step", ms=round(dt * 1e3, 3),
                           tokens=n_tok, span_tokens=live_tokens,
                           active=len(self.scheduler.active()),
                           queue=self.scheduler.queue_depth(),
                           kv_blocks_used=self.kv.allocator.used_blocks)
            cap = self._slo_capture
            if cap is not None:
                # SLO-triggered capture bookkeeping: host-side counters
                # only, until a breach arms the bounded profiler window
                cap.on_step()

    def _finish_events(self, plan, nxt,
                       events: List[TokenEvent]) -> None:
        if plan:
            fi = _rs_state.FAULTS[0]
            tr = _obs_state.TRACE[0]
            reg = obs.get_registry()
            # one consumption per REQUEST, not per row: a prefilling
            # request's fan (its own row plus the free rows it was
            # dealt) advances kv_len once, by the sum of its rows
            advanced = []
            for spans in by_request(plan):
                first = spans[0]
                st = first.st
                advanced.append((st, st.kv_len))
                # pre-span snapshot: isolation rewinds to here, and
                # re-running the spans after restore is idempotent
                # (the dispatch above already wrote their KV; the
                # rewound re-run rewrites identical bytes — a
                # speculative span's rejected tail is re-proposed from
                # the same context, and kv_len only ever covered the
                # accepted prefix)
                snap = (st.kv_len, st.pending_token,
                        len(st.output_ids), st.text_len,
                        st.detok_offset, st.spec_proposed,
                        st.spec_accepted)
                try:
                    if fi is not None:
                        fi("serve.prefill" if first.is_prefill
                           else "serve.step")
                    if not first.is_prefill:
                        # decode: plain single token, or the
                        # speculative verify span (mid-verify faults
                        # fired above land in the rollback below)
                        self._consume_decode(st, first.row, first.n, nxt,
                                             events)
                        continue
                    n = sum(sp.n for sp in spans)
                    st.kv_len += n
                    st.prefill_steps += 1
                    if tr is not None:
                        tr.point(st.request.request_id, "prefill_chunk",
                                 tokens=n, kv_len=st.kv_len)
                    if reg is not None:
                        reg.histogram("serve.prefill_rows").observe(
                            len(spans))
                    if st.prefilling:
                        continue    # mid-prefill: samples discarded
                    # prompt complete: this sample is the request's
                    # first token — TTFT stops here.  first_token_t
                    # survives a hard replica-failure reset (the
                    # request re-prefills from scratch), so the
                    # re-completion must not re-emit serve_request /
                    # re-observe TTFT for the same request
                    # (serving/distributed.py).  The row that holds
                    # the prompt's last token carries the first token —
                    # at its last position on the speculative program,
                    # which samples every span position.
                    last = spans[-1]
                    tok = int(nxt[last.row]) if nxt.ndim == 1 \
                        else int(nxt[last.row, last.n - 1])
                    self._register_prefix(st)
                    # disaggregated prefill role: stage the handoff
                    # (swap the pages to host) BEFORE any state
                    # mutates — a failed swap degrades cleanly to
                    # local decode, and the trace phase below can
                    # honestly say which way the request went
                    handoff = self._prepare_handoff(st, tok)
                    if tr is not None:
                        # prefill→decode transition (closes the
                        # prefill segment) — or prefill→xfer when this
                        # prefill replica hands the request off to a
                        # decode replica.  A re-completion after a
                        # hard replica reset accumulates under its
                        # own event name, so `first_token` stays
                        # exactly-once per request — same dedupe
                        # marker as the serve_request event below.
                        tr.transition(
                            st.request.request_id,
                            "xfer" if handoff is not None else "decode",
                            event="first_token"
                            if st.first_token_t is None
                            else "re_prefilled")
                    if st.first_token_t is not None:
                        self._emit(st, tok, events)
                        if handoff is not None and not st.finished:
                            self._commit_handoff(st, handoff)
                        continue
                    st.first_token_t = time.perf_counter()
                    req = st.request
                    if reg is not None:
                        ttft = (st.first_token_t - st.submit_t) * 1e3
                        reg.histogram("serve.ttft_ms").observe(ttft)
                        reg.histogram("serve.prefill_steps").observe(
                            st.prefill_steps)
                        if req.tenant:
                            # the per-tenant aggregate the FrontDoor
                            # SLO policy reads (frontdoor._ttft_p95)
                            reg.histogram(
                                f"serve.tenant[{req.tenant}]"
                                ".ttft_ms").observe(ttft)
                        if st.num_shared:
                            reg.counter("serve.prefix_hits").inc(
                                st.num_shared)
                        misses = len(st.page_keys) - st.num_shared
                        if misses:
                            reg.counter(
                                "serve.prefix_misses").inc(misses)
                    obs.emit_event(
                        "serve_request", id=req.request_id,
                        tenant=req.tenant, adapter=req.adapter,
                        prompt_len=int(req.prompt_ids.size),
                        slot=st.slot, blocks=len(st.blocks),
                        cached_tokens=st.cached_tokens)
                    self._emit(st, tok, events)
                    if handoff is not None and not st.finished:
                        self._commit_handoff(st, handoff)
                except Exception as e:  # noqa: BLE001
                    st.kv_len, st.pending_token = snap[0], snap[1]
                    del st.output_ids[snap[2]:]
                    st.text_len, st.detok_offset = snap[3], snap[4]
                    st.spec_proposed, st.spec_accepted = snap[5], snap[6]
                    # a multi-token (speculative) span may have emitted
                    # part of its acceptance before failing: those
                    # tokens were rewound and will re-emit after
                    # restore, so their events must not ALSO be
                    # delivered from this step (already-fired on_token
                    # callbacks can't be recalled — same caveat as the
                    # hard replica-reset path)
                    rid = st.request.request_id
                    events[:] = [ev for ev in events
                                 if ev.request_id != rid]
                    advanced.pop()
                    self._isolate(st, e)
            if not self.cache_spec.reserves_ahead:
                self._close_windows(advanced)

    def _consume_decode(self, st: RequestState, i: int, n: int, nxt,
                        events: List[TokenEvent]) -> None:
        """Consume a decode slot's sample(s): a plain single-token
        decode (non-speculative program, or a spec slot with no
        draft), or the speculative VERIFY — greedy acceptance takes the
        longest draft prefix the per-position samples reproduce, plus
        one bonus token (so a total miss still emits one token, never
        worse than plain decode).  Rolling back the rejected tail is
        kv_len bookkeeping ONLY: the speculative writes sit in pages
        the request already reserved, beyond the new kv_len, where the
        next span overwrites them and attention never reads
        (serving/spec.py)."""
        if nxt.ndim == 1:               # non-speculative program: (B,)
            st.kv_len += 1
            self._emit(st, int(nxt[i]), events)
            return
        row = nxt[i]
        req = st.request
        k = n - 1
        a = 0
        while a < k and int(row[a]) == st.draft[a]:
            a += 1
        # eos-aware emission length, decided BEFORE emitting: an
        # accepted token that IS the eos finishes the request there and
        # the rest of the accepted span is dropped.  (The draft cap
        # already keeps a+1 inside the max_new budget.)
        will = a + 1
        if req.eos_token_id is not None:
            for j in range(will):
                if int(row[j]) == req.eos_token_id:
                    will = j + 1
                    break
        acc = will - 1                  # drafts actually consumed
        st.kv_len += 1 + acc
        if k:
            # PER-REQUEST accounting lands BEFORE emission (the last
            # emitted token may retire the request, and the retire
            # event/trace must carry this span's acceptance) — it is
            # part of the rollback snapshot, so a mid-emission failure
            # rewinds it with the rest of the state
            st.spec_proposed += k
            st.spec_accepted += acc
        for j in range(will):
            self._emit(st, int(row[j]), events)
            if st.finished:
                break                   # safety net: must match `will`
        if k:
            # GLOBAL counters land AFTER emission: they are not in the
            # snapshot, so counting before _emit could raise would
            # double-count this span when isolation re-runs it
            sp = self.spec
            sp.verifies += 1
            sp.proposed += k
            sp.accepted += acc
            reg = obs.get_registry()
            if reg is not None:
                reg.counter("serve.spec.proposed").inc(k)
                # created with the first proposal, at nought too: a rate
                # needs both counters
                reg.counter("serve.spec.accepted").inc(acc)
                reg.histogram("serve.spec.accept_len").observe(acc)

    def step(self) -> List[TokenEvent]:
        """Admit what fits, run ONE unified ragged step (prefill chunks
        + decode tokens together), retire what finished.  Returns the
        tokens emitted (one per decoded / prompt-completed request).
        Composes :meth:`step_begin` (dispatch) + :meth:`step_finish`
        (device sync + host post-processing).

        Per-request fault isolation (docs/RESILIENCE.md "Serving
        sites"): a host-side failure in one request's bookkeeping —
        admission, CoW, prefill/decode post-processing, or an injected
        ``serve.*`` fault — never tears down the compiled step or the
        other slots.  The victim is rewound to its pre-span snapshot,
        preempted to host RAM, and transparently re-admitted; everyone
        else's events are delivered normally."""
        return self.step_finish(self.step_begin())

    def stream(self):
        """Generator: run ``step()`` until drained, yielding each
        :class:`TokenEvent` as it is produced.  More requests may be
        added while streaming — they join the running batch."""
        while self.has_work():
            for ev in self.step():
                yield ev

    # requires-lock: _lock
    def _begin_drain(self) -> Dict[str, List[int]]:
        """Start a drain capture (shared by :meth:`run` and
        ``FrontDoor.run``): collect requests already finished since the
        last drain, and arm finish-time capture so eviction under
        ``keep_finished`` can't outrun the drain dict.  Pair with
        :meth:`_end_drain` in a finally."""
        drained: Dict[str, List[int]] = {}
        for rid, st in self._states.items():
            if st.finished and not st.drained:
                st.drained = True
                drained[rid] = list(st.output_ids)
        self._drain_capture = drained
        return drained

    # requires-lock: _lock
    def _end_drain(self) -> None:
        self._drain_capture = None

    def run(self) -> Dict[str, List[int]]:
        """Drain everything; returns {request_id: generated token ids}
        for every request finished since the last ``run()`` — including
        (still-retained) requests that finished during manual ``step()``
        calls before this one (staggered admission).  Outputs are
        captured at finish time, so the dict is complete even when more
        than ``keep_finished`` requests retire in one drain."""
        drained = self._begin_drain()
        try:
            while self.has_work():
                self.step()
        finally:
            self._end_drain()
        return drained
