"""The program's spans on the profiler's clock, and the regions of its
compiled programs (docs/OBSERVABILITY.md "Trace spans").

Host side: under ANY live ``jax.profiler`` session a ``span`` is a host
event ``pdtpu.<name>`` of the xplane; the serving loop's leaf phases tile
one iteration of ``ServingServer._loop`` on its own thread; ``TrainStep``
writes one numbered ``pdtpu.train`` step event per call.  Device side:
the eight region names reach the ``op_name`` metadata of the compiled
``TrainStep`` and of the compiled serving step, in every model family.
"""

import glob
import http.client
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu import amp, nn, optimizer, serving
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import gpt
from paddle_tpu.models.llama import causal_lm_loss, llama
from paddle_tpu.observability import _state as obs_state
from paddle_tpu.observability.regions import REGIONS, region
from paddle_tpu.observability.spans import TRACE_PREFIX, span, spans_active

# the leaf phases of one loop iteration, on the loop's own thread
LOOP_LEAVES = ("serve.loop.wait", "serve.pump", "serve.step.admit",
               "serve.step.draft", "serve.step.plan", "serve.step.dispatch",
               "serve.step.sync", "serve.step.emit", "serve.step.account",
               "serve.stream.route")


class _Session:
    """``jax.profiler.start_trace`` as an operator or the benchmark's
    harness would start it: nothing of paddle_tpu's own is switched on."""

    def __init__(self, path):
        self.path = str(path)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def lines(self):
        """[(thread's line name, [(name, start_ns, end_ns, stats)])] of the
        program's own host events."""
        files = sorted(glob.glob(os.path.join(
            self.path, "plugins", "profile", "*", "*.xplane.pb")))
        data = jax.profiler.ProfileData.from_file(files[-1])
        out = []
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                evs = [(e.name[len(TRACE_PREFIX):], e.start_ns,
                        e.start_ns + e.duration_ns, dict(e.stats))
                       for e in ln.events if e.name.startswith(TRACE_PREFIX)]
                if evs:
                    out.append((ln.name, sorted(evs, key=lambda e: e[1])))
        return out


def _post(conn, body):
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    return r.status, r.read()


def test_serving_phases_tile_the_loop_thread(tmp_path):
    """(a) every leaf phase is in the xplane under the prefix, the loop
    thread's leaves never overlap, and they cover the loop thread's time
    between the first and the last step."""
    assert not obs.enabled()
    pt.seed(0)
    eng = serving.Engine(llama("tiny"), max_batch=2, max_seq_len=64,
                         page_size=8, spec_decode=True,
                         draft_depth=2).warmup()
    srv = serving.ServingServer(eng, port=0, poll_s=0.001)
    host, port = srv.start()
    rng = np.random.default_rng(3)
    sess = _Session(tmp_path / "trace")
    try:
        with sess:
            conn = http.client.HTTPConnection(host, port, timeout=120)
            for i, stream in enumerate((False, True, True)):
                motif = rng.integers(0, 256, size=4)
                prompt = np.tile(motif, 3)[:10 + i].tolist()
                status, raw = _post(conn, {"prompt": prompt,
                                           "max_tokens": 6,
                                           "stream": stream})
                assert status == 200 and raw
    finally:
        srv.begin_drain()
        srv.wait_drained(timeout=30)
        srv.close()
    lines = sess.lines()
    names = {e[0] for _, evs in lines for e in evs}
    assert set(LOOP_LEAVES) | {"serve.stream.write", "serve.step",
                               "serve.step.finish"} <= names
    loop = [evs for _, evs in lines
            if any(e[0] == "serve.step.dispatch" for e in evs)]
    assert len(loop) == 1, "the step's phases lie on one thread"
    loop = loop[0]
    # the handlers' writes lie on other threads
    assert not any(e[0] == "serve.stream.write" for e in loop)
    leaves = [e for e in loop if e[0] in LOOP_LEAVES]
    for a, b in zip(leaves, leaves[1:]):
        assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"
    # every leaf of a step lies inside one of the two kept parents
    parents = [e for e in loop if e[0] in ("serve.step",
                                           "serve.step.finish")]
    for e in leaves:
        if e[0].startswith("serve.step."):
            assert any(p[1] <= e[1] and e[2] <= p[2] for p in parents), e[0]
    t0 = min(e[1] for e in loop if e[0] == "serve.step")
    t1 = max(e[2] for e in loop if e[0] == "serve.step.finish")
    covered = sum(min(e[2], t1) - max(e[1], t0) for e in leaves
                  if e[2] > t0 and e[1] < t1)
    assert covered >= 0.9 * (t1 - t0), (covered, t1 - t0)


def _tiny_linear_step():
    model = nn.Linear(8, 8)
    opt = optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    step = TrainStep(model, lambda m, b: ((m(b["x"]) - b["y"]) ** 2).mean(),
                     opt)
    return step, step.init_state(), {"x": jnp.ones((4, 8)),
                                     "y": jnp.zeros((4, 8))}


def test_train_step_events_one_per_call_in_order(tmp_path):
    """(b) one ``pdtpu.train`` step event per call, numbered in order by
    the host's count of calls, with telemetry off."""
    assert obs_state.MONITOR[0] is None
    step, state, batch = _tiny_linear_step()
    for _ in range(2):                    # compiled, and two calls counted
        state, _m = step(state, batch)
    sess = _Session(tmp_path / "trace")
    with sess:
        for _ in range(4):
            state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
    evs = [e for _, line in sess.lines() for e in line if e[0] == "train"]
    assert [e[3]["step_num"] for e in evs] == [2, 3, 4, 5]
    for a, b in zip(evs, evs[1:]):
        assert a[2] <= b[1]
    # a step built without __init__ (tools/ci.py's gate) counts too
    bare = TrainStep.__new__(TrainStep)
    assert bare._calls == 0


CONFIGS = {
    # family, overrides
    "llama": (llama, dict(num_key_value_heads=4, tie_word_embeddings=True)),
    "mistral_gqa": (llama, dict(num_key_value_heads=2,
                                intermediate_size=224, rope_theta=10000.0,
                                tie_word_embeddings=False)),
    "gpt": (gpt, dict()),
}
SERVING_REGIONS = ("embed", "norm", "attn_proj", "attn_core", "mlp",
                   "lm_head_loss")


def _regions_in(text: str) -> set:
    """The regions among the components of the text's scope paths, the
    wrappers of autodiff apart (``transpose(jvp(forward))``); ``jit(clip)``
    is a call of ``jnp.clip``, not the region."""
    found = set()
    for path in set(re.findall(r'op_name="([^"]*)"', text)):
        for part in path.split("/"):
            while (m := re.match(r"^(?:jvp|transpose|vmap)\((.*)\)$",
                                 part)) is not None:
                part = m.group(1)
            if part in REGIONS:
                found.add(part)
    return found


@pytest.fixture
def metadata_in_cache_key():
    """JAX's persistent compile cache leaves op metadata out of its key by
    default, so a program cached by a tree without the regions would come
    back with that tree's ``op_name``s: key these compiles by theirs."""
    name = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, name)
    jax.config.update(name, True)
    yield
    jax.config.update(name, was)


@pytest.mark.parametrize("family", sorted(CONFIGS))
def test_regions_reach_the_compiled_programs(family, metadata_in_cache_key):
    """(c) each of the eight names in the compiled TrainStep, the model's
    six in the compiled serving step, whichever model file traced it."""
    build, over = CONFIGS[family]
    pt.seed(0)
    model = build("tiny", **over)
    opt = optimizer.AdamW(learning_rate=1e-3, weight_decay=0.1,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0),
                          parameters=model.parameters())
    tmodel, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
    step = TrainStep(tmodel, causal_lm_loss, opt)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 16)),
                      jnp.int32)
    text = step.lower(step.init_state(seed=0),
                      {"input_ids": ids, "labels": ids}).compile().as_text()
    assert _regions_in(text) == set(REGIONS)
    assert "forward" in text

    pt.seed(0)
    eng = serving.Engine(build("tiny", **over), max_batch=2, max_seq_len=32,
                         page_size=8)
    b, mb, c = eng.max_batch, eng.max_blocks_per_seq, eng.prefill_chunk
    zi = jnp.zeros((b,), jnp.int32)
    text = eng._step_fn.lower(
        eng.params, eng.kv.caches, jnp.zeros((b, c), jnp.int32),
        jnp.full((b, mb), eng.kv.oob_block, jnp.int32), zi, zi,
        jnp.zeros((b,), jnp.float32), eng._key, zi, zi,
        eng._lora_stacks(), zi).compile().as_text()
    assert _regions_in(text) == set(SERVING_REGIONS)


def test_region_is_the_vocabulary_only():
    with region("mlp"):
        pass
    with pytest.raises(ValueError, match="not a region"):
        region("attention")


def test_span_without_a_session_touches_nothing(monkeypatch):
    """(d) no session, no telemetry, no Profiler: a span reaches neither
    registry nor sinks nor the chrome export (the 10 us budget of the
    disabled ``TrainStep`` path, step annotation and all, is
    ``test_ci_gates.py::test_telemetry_overhead_gate``'s)."""
    assert not obs.enabled() and obs_state.SPAN[0] is None
    assert obs_state.HOST_EVENTS[0] is None

    def boom(*a, **kw):
        raise AssertionError("a span with nothing live touched telemetry")

    for cls, name in ((obs.MetricsRegistry, "histogram"),
                      (obs.MetricsRegistry, "counter"),
                      (obs.Telemetry, "emit"),
                      (obs.FlightRecorder, "record")):
        monkeypatch.setattr(cls, name, boom)
    assert not spans_active()
    for emit in (True, False):
        with span("nothing.live", emit=emit, tag="x"):
            pass

