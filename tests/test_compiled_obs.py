"""Compiled-artifact observability (docs/OBSERVABILITY.md "Reading the
roofline"): the CompiledArtifactLedger's capture contract, the analytic
roofline math, the new prom surfaces (serve.hbm.*, serve.roofline.*,
recompiles_total{site=...}), and the perf-regression ledger
(tools/bench_compare.py)."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.observability as obs
from paddle_tpu.observability import _state as obs_state
from paddle_tpu.observability.compiled import (CHIP_SPECS, chip_spec,
                                               roofline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _always_disabled_after():
    yield
    obs.disable()


@pytest.fixture
def tiny_llama():
    from paddle_tpu.models.llama import llama
    pt.seed(0)
    return llama("tiny")


# -- ledger capture ----------------------------------------------------------

class TestLedgerCapture:
    def test_engine_warmup_full_ledger_zero_extra_compiles(self,
                                                           tiny_llama):
        """THE tentpole contract: warmup produces one ledger row per
        compiled program (row count == the sentinel's backend-compile
        count — the capture itself compiles NOTHING extra), rows carry
        cost/memory analysis with site attribution, and post-warmup
        serving stays at zero compiles with the jit caches at one
        entry, exactly the pre-ledger invariant."""
        from paddle_tpu import serving
        tel = obs.enable(crash_hooks=False)
        base = tel.sentinel.compiles()
        eng = serving.Engine(tiny_llama, num_blocks=32, page_size=8,
                             max_batch=2, max_seq_len=64).warmup()
        led = obs.get_ledger()
        assert led is tel.ledger is not None
        warmup_compiles = tel.sentinel.compiles() - base
        rows = led.snapshot()
        assert len(rows) == warmup_compiles > 0
        sites = {r["site"] for r in rows}
        assert {"serve.step", "serve.cow", "serve.swap"} <= sites
        step_rows = led.rows_for("serve.step")
        assert len(step_rows) == 1
        r = step_rows[0]
        # a real transformer step: nonzero flops, bytes, scratch, and
        # a measured compile wall
        assert r["flops"] > 0 and r["bytes_accessed"] > 0
        assert r["temp_bytes"] > 0 and r["compile_ms"] > 0
        assert r["peak_bytes"] > 0
        assert r["bound"] in ("compute", "bandwidth")
        assert r["min_ms"] > 0
        assert led.min_ms_for("serve.step") == pytest.approx(r["min_ms"])

        # serving traffic: zero additional compiles, zero new rows
        n0 = len(led.snapshot())
        c0 = tel.sentinel.compiles()
        eng.add_request(np.arange(5), max_new_tokens=4)
        while eng.has_work():
            eng.step()
        assert tel.sentinel.compiles() == c0
        assert len(led.snapshot()) == n0
        assert eng._step_fn._cache_size() == 1
        assert eng._cow_fn._cache_size() == 1

        # the hbm gauge block landed in the registry AND on the ledger
        snap = tel.registry.snapshot()
        hbm = led.hbm
        assert hbm["kv_pool_bytes"] == eng.kv.nbytes() > 0
        assert hbm["param_bytes"] > 0
        assert hbm["peak_temp_bytes"] == max(
            row["temp_bytes"] for row in rows)
        for k, v in hbm.items():
            assert snap[f"serve.hbm.{k}"] == v
        # roofline constants + measured-step attribution gauges
        assert snap["serve.roofline.step.min_ms"] > 0
        assert 0 < snap["serve.roofline.step.frac"] < 10
        assert ("serve.roofline.prefill.frac" in snap
                or "serve.roofline.decode.frac" in snap)

    def test_trainstep_first_compile_ledger(self, tiny_llama):
        import jax
        import jax.numpy as jnp
        from paddle_tpu import optimizer
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.llama import causal_lm_loss
        tel = obs.enable(crash_hooks=False)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=tiny_llama.parameters())
        step = TrainStep(tiny_llama, causal_lm_loss, opt)
        state = step.init_state(seed=0)
        ids = jax.random.randint(jax.random.key(0), (2, 16), 0,
                                 tiny_llama.cfg.vocab_size)
        batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
        state, m = step(state, batch)
        _ = float(m["loss"])
        led = obs.get_ledger()
        rows = led.rows_for(step._site)
        # the first call compiled the ONE step program, attributed to
        # the TrainStep site through timed_step's sentinel scope
        assert len(rows) == 1 and rows[0]["flops"] > 0
        n_rows = len(led.snapshot())
        # steady state: no new rows, and the post-warmup step publishes
        # the roofline attribution gauge for the site
        state, m = step(state, batch)
        _ = float(m["loss"])
        assert len(led.snapshot()) == n_rows
        snap = tel.registry.snapshot()
        frac = snap[f"train.roofline[{step._site}].frac"]
        # tiny cache-resident steps can beat the measured-CPU bandwidth
        # stand-in, so the frac may exceed 1 here — positive and sane
        # is the contract; exact math is pinned in TestRoofline
        assert 0 < frac < 100
        assert tel.monitor.last_event["roofline_frac"] == frac
        assert snap[f"train.roofline[{step._site}].min_ms"] > 0

    def test_disable_restores_compile_and_clears_hook(self):
        import jax
        import jax.numpy as jnp
        from jax._src.interpreters import pxla
        obs.enable(crash_hooks=False)
        assert obs_state.LEDGER[0] is not None
        assert pxla.MeshComputation.compile.__name__ == "_ledger_compile"
        obs.disable()
        assert obs_state.LEDGER[0] is None
        assert pxla.MeshComputation.compile.__name__ != "_ledger_compile"
        # compiles after disable land nowhere (no ledger, no crash)
        jax.jit(lambda x: x * 2)(jnp.ones((4,))).block_until_ready()

    def test_ledger_rows_reach_postmortem_and_sidecar(self, tmp_path):
        import jax
        import jax.numpy as jnp
        sink = obs.InMemorySink()
        tel = obs.enable(sinks=[sink], crash_hooks=False)
        with tel.sentinel.site("pm-site"):
            jax.jit(lambda x: (x @ x.T).sum())(
                jnp.ones((8, 8))).block_until_ready()
        obs.get_ledger().set_hbm({"kv_pool_bytes": 123})
        path = obs.write_postmortem(reason="test",
                                    path=str(tmp_path / "pm.jsonl"))
        lines = [json.loads(ln) for ln in
                 open(path).read().splitlines()]
        arts = [ln for ln in lines
                if ln.get("event") == "compiled_artifacts"]
        assert len(arts) == 1
        assert arts[0]["hbm"] == {"kv_pool_bytes": 123}
        assert any(r["site"] == "pm-site" and r["flops"] > 0
                   for r in arts[0]["rows"])
        # every capture also emitted one compiled_artifact event
        evs = sink.events("compiled_artifact")
        assert any(e["site"] == "pm-site" for e in evs)


# -- roofline math -----------------------------------------------------------

class TestRoofline:
    def test_hand_computed_bounds(self):
        spec = {"peak_flops": 100e12, "hbm_gbps": 1000.0}
        # compute-bound: 1e12 flops @ 100 TFLOP/s = 10 ms; 1 GB @
        # 1000 GB/s = 1 ms
        r = roofline(1e12, 1e9, spec)
        assert r["compute_ms"] == pytest.approx(10.0)
        assert r["memory_ms"] == pytest.approx(1.0)
        assert r["min_ms"] == pytest.approx(10.0)
        assert r["bound"] == "compute"
        # bandwidth-bound: 1e9 flops (0.01 ms) vs 10 GB (10 ms)
        r = roofline(1e9, 1e10, spec)
        assert r["min_ms"] == pytest.approx(10.0)
        assert r["bound"] == "bandwidth"
        # the ridge: ties classify as compute
        r = roofline(100e9, 1e9, spec)
        assert r["bound"] == "compute"

    def test_chip_spec_table_and_override(self):
        v4 = chip_spec("TPU v4")
        assert v4["peak_flops"] == 275e12 and v4["hbm_gbps"] == 1228.0
        v5e = chip_spec("TPU v5 lite chip")   # prefix match
        assert v5e["peak_flops"] == 197e12
        # v5p must not be swallowed by the shorter "TPU v5" prefix
        assert chip_spec("TPU v5p")["hbm_gbps"] == 2765.0
        # a kind the table does not know is an error, not the CPU row —
        # unless the override describes the chip in full
        with pytest.raises(ValueError, match="FancyChip 9000"):
            chip_spec("FancyChip 9000")
        with pytest.raises(ValueError):
            chip_spec("FancyChip 9000", override={"hbm_gbps": 999.0})
        fancy = chip_spec("FancyChip 9000", override={
            "peak_flops": 1e15, "hbm_gbps": 999.0})
        assert fancy["peak_flops"] == 1e15 and fancy["hbm_gbps"] == 999.0
        ov = chip_spec("TPU v4", override={"hbm_gbps": 999.0})
        assert ov["hbm_gbps"] == 999.0 and ov["peak_flops"] == 275e12
        # CPU stand-in is measured, positive, sane
        cpu = chip_spec("cpu")
        assert 1.0 <= cpu["hbm_gbps"] <= 1000.0

    def test_flops_column_pinned_to_mfu_table(self):
        # ONE source of truth for peak flops: compiled.py's chip table
        # must agree with mfu.PEAK_BF16_FLOPS wherever both know a chip
        from paddle_tpu.observability.mfu import PEAK_BF16_FLOPS
        for kind, spec in CHIP_SPECS.items():
            if kind in PEAK_BF16_FLOPS:
                assert spec["peak_flops"] == PEAK_BF16_FLOPS[kind], kind


# -- prom surface ------------------------------------------------------------

class TestPromSurface:
    def test_recompiles_total_labeled_counter(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.observability.sinks import registry_to_prometheus
        tel = obs.enable(crash_hooks=False)
        with tel.sentinel.site("site=[a,b]"):    # reserved chars squash
            jax.jit(lambda x: x + 1)(jnp.ones((3,))).block_until_ready()
        text = registry_to_prometheus(tel.registry)
        assert '# TYPE recompiles_total counter' in text
        m = re.search(r'recompiles_total\{site="site__a_b_"\} (\d+)',
                      text)
        assert m and int(m.group(1)) >= 1

    def test_hbm_and_roofline_gauges_roundtrip_fleet_fold(self):
        from paddle_tpu.observability.aggregate import (fleet_fold,
                                                        registry_to_wire)
        from paddle_tpu.observability.sinks import registry_to_prometheus
        reg = obs.MetricsRegistry()
        reg.gauge("serve.hbm.kv_pool_bytes").set(4096)
        reg.gauge("serve.roofline.step.min_ms").set(0.5)
        reg.counter("recompiles_total[site=serve.step]").inc(3)
        # local surface
        text = registry_to_prometheus(reg)
        assert "serve_hbm_kv_pool_bytes 4096" in text
        assert "serve_roofline_step_min_ms 0.5" in text
        assert 'recompiles_total{site="serve.step"} 3' in text
        # fleet surface: wire → fold → per-worker labels + rollup
        fleet = fleet_fold({"w0": {"role": "decode",
                                   "metrics": registry_to_wire(reg)}})
        ftext = registry_to_prometheus(fleet)
        assert ('serve_hbm_kv_pool_bytes{worker="w0",role="decode"} 4096'
                in ftext)
        assert 'recompiles_total{site="serve.step",worker="w0"' in ftext

    def test_worker_snapshot_hbm_block_folds_to_cluster_metrics(self):
        from paddle_tpu.serving.cluster import ClusterController

        class _Store:
            def __init__(self):
                self.kv = {}

            def set(self, k, v):
                self.kv[k] = v

            def get(self, k):
                return self.kv.get(k)

            def add(self, k, n):
                cur = int(self.kv.get(k, b"0")) + n
                self.kv[k] = str(cur).encode()
                return cur

            def delete(self, k):
                return self.kv.pop(k, None) is not None

            def compare_set(self, k, expected, new):
                if self.kv.get(k) == expected or (
                        expected in (b"", None) and k not in self.kv):
                    self.kv[k] = new
                    return True
                return False

            def keys(self, pfx):
                return [k for k in self.kv if k.startswith(pfx)]

        store = _Store()
        ctl = ClusterController(store)
        store.set("cluster/workers/w0", json.dumps(
            {"worker": "w0", "role": "decode", "epoch": 0,
             "version": "v0"}).encode())
        store.set("cluster/telemetry/w0", json.dumps(
            {"worker": "w0", "role": "decode", "metrics": {},
             "hbm": {"kv_pool_bytes": 8192,
                     "param_bytes": 1024}}).encode())
        text = ctl.metrics_text()
        assert ('serve_hbm_kv_pool_bytes{worker="w0",role="decode"} 8192'
                in text)
        assert ('serve_hbm_param_bytes{worker="w0",role="decode"} 1024'
                in text)


# -- standalone-load contract ------------------------------------------------

def test_compiled_module_loads_standalone():
    """compiled.py is importable with no package, no jax imported at
    module scope — the aggregate.py/sinks.py contract for offline
    tools."""
    path = os.path.join(REPO, "paddle_tpu", "observability",
                        "compiled.py")
    spec = importlib.util.spec_from_file_location("_compiled_sa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    r = mod.roofline(1e12, 1e9, {"peak_flops": 100e12,
                                 "hbm_gbps": 1000.0})
    assert r["bound"] == "compute"
    led = mod.CompiledArtifactLedger()
    assert led.snapshot() == [] and led.min_ms_for("x") is None

    class _Exec:
        def cost_analysis(self):
            return [{"flops": 2e9, "bytes accessed": 1e6}]

        def memory_analysis(self):
            class _MA:
                argument_size_in_bytes = 100
                output_size_in_bytes = 50
                temp_size_in_bytes = 30
                alias_size_in_bytes = 20
                generated_code_size_in_bytes = 10
            return _MA()

    row = led.record_executable(_Exec(), program="jit(x)",
                                compile_ms=5.0)
    assert row["flops"] == 2e9 and row["argument_bytes"] == 100
    assert row["peak_bytes"] == 100 + 50 + 30 + 10 - 20
    assert len(led) == 1


def test_bench_compare_loads_standalone():
    path = os.path.join(REPO, "tools", "bench_compare.py")
    spec = importlib.util.spec_from_file_location("_bc_sa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.direction("serve_cpu_tok_s") == "higher"
    assert mod.direction("ms_per_step") == "lower"
    assert mod.direction("loss") is None


# -- perf-regression ledger (tools/bench_compare.py) -------------------------

def _load_bench_compare():
    path = os.path.join(REPO, "tools", "bench_compare.py")
    spec = importlib.util.spec_from_file_location("_bc_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchCompare:
    def _round(self, n, ms, tok_s, provenance=None):
        extra = {"ms_per_step": ms, "serve_cpu_tok_s": tok_s,
                 "loss": 5.0, "serve_detail": {"requests": 6},
                 "window_ms_per_step": [ms, ms * 1.1]}
        if provenance is not None:
            extra["provenance"] = provenance
        return {"n": n, "cmd": "bench", "rc": 0, "tail": "",
                "parsed": {"metric": "llama_train_mfu", "value": 0.4,
                           "unit": "mfu_fraction", "extra": extra}}

    def test_trajectory_fold_with_sidecar_and_backfill(self, tmp_path):
        bc = _load_bench_compare()
        # two driver rounds (r01 has NO provenance → backfilled) + one
        # sidecar round
        p1 = tmp_path / "BENCH_r01.json"
        p1.write_text(json.dumps(self._round(1, 100.0, 50.0)))
        p2 = tmp_path / "BENCH_r02.json"
        p2.write_text(json.dumps(self._round(
            2, 80.0, 60.0, provenance={"git_sha": "abc123",
                                       "jax": "0.4.37"})))
        side = tmp_path / "bench_telemetry.jsonl"
        side.write_text(
            json.dumps({"event": "run_meta"}) + "\n" +
            json.dumps({"event": "bench_result",
                        **self._round(3, 90.0, 55.0)["parsed"]}) + "\n")
        rounds = []
        for p in (p1, p2, side):
            rounds.extend(bc.load_round(str(p)))
        assert [r["label"] for r in rounds] == \
            ["r01", "r02", "bench_telemetry.jsonl"]
        assert rounds[0]["provenance"]["git_sha"] is None  # backfilled
        assert rounds[1]["provenance"]["git_sha"] == "abc123"
        table = bc.fold_trajectory(rounds, baseline={
            "rows": {"ms_per_step": {"value": 100.0}}})
        ent = table["ms_per_step"]
        assert [v for _, v in ent["series"]] == [100.0, 80.0, 90.0]
        assert ent["best"] == 80.0 and ent["last"] == 90.0
        # lower-better: 90 vs baseline 100 is 10% BETTER
        assert ent["delta_vs_baseline"] == pytest.approx(0.1)
        assert table["serve_cpu_tok_s"]["best"] == 60.0
        # nested detail dicts and window lists never become rows
        assert "serve_detail" not in table
        assert "window_ms_per_step" not in table
        md = bc.render_md(table)
        assert "| `serve_cpu_tok_s` |" in md

    def test_regression_detection_and_noise_band(self):
        bc = _load_bench_compare()
        baseline = {"rows": {
            "serve_cpu_tok_s": {"value": 50.0, "band": 0.4,
                                "better": "higher"},
            "ms_per_step": {"value": 100.0, "band": 0.4,
                            "better": "lower"}}}
        # within-band noise (−10% tok/s, +10% ms) passes
        ok, _ = bc.check({"serve_cpu_tok_s": 45.0, "ms_per_step": 110.0},
                         baseline)
        assert ok
        # injected 2× slowdown is flagged
        ok, lines = bc.check({"serve_cpu_tok_s": 25.0,
                              "ms_per_step": 100.0}, baseline)
        assert not ok
        assert any("REGRESSION" in ln and "serve_cpu_tok_s" in ln
                   for ln in lines)
        ok, _ = bc.check({"serve_cpu_tok_s": 50.0, "ms_per_step": 200.0},
                         baseline)
        assert not ok
        # a row the fresh run lacks skips, never fails
        ok, lines = bc.check({"ms_per_step": 100.0}, baseline)
        assert ok and any("skip" in ln for ln in lines)
        # improvements never trip the gate
        ok, _ = bc.check({"serve_cpu_tok_s": 500.0, "ms_per_step": 10.0},
                         baseline)
        assert ok

    def test_check_cli_exit_codes_against_committed_baseline(
            self, tmp_path):
        """The acceptance contract end-to-end: --check exits 0 on the
        committed seed numbers and nonzero on a 2× CPU-plumbing
        slowdown, through the real CLI against the real baseline."""
        baseline_path = os.path.join(REPO, "tools",
                                     "bench_baseline.json")
        rows = json.load(open(baseline_path))["rows"]
        gated = {k: s for k, s in rows.items()
                 if s.get("better") in ("higher", "lower")}
        assert gated, "committed baseline must carry gateable rows"
        seed = {"metric": "llama_train_mfu",
                "value": rows.get("llama_train_mfu",
                                  {}).get("value", 0.0),
                "unit": "mfu_fraction",
                "extra": {k: s["value"] for k, s in rows.items()
                          if k != "llama_train_mfu"}}
        slow = json.loads(json.dumps(seed))
        victim = sorted(gated)[0]
        spec_ = gated[victim]
        tgt = slow["extra"] if victim in slow["extra"] else slow
        key = victim if victim in slow["extra"] else "value"
        tgt[key] = (spec_["value"] / 2.0
                    if spec_["better"] == "higher"
                    else spec_["value"] * 2.0)
        rcs = {}
        for name, payload in (("seed", seed), ("slow", slow)):
            p = tmp_path / f"{name}.json"
            p.write_text(json.dumps(payload))
            r = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "bench_compare.py"),
                 "--check", "--fresh", str(p)],
                capture_output=True, text=True, timeout=60)
            rcs[name] = r.returncode
        assert rcs["seed"] == 0
        assert rcs["slow"] != 0

    def test_check_skips_on_backend_mismatch(self, tmp_path):
        """Row NAMES are shared across platforms but scales are not: a
        TPU fresh run against the CPU baseline gates nothing instead of
        failing everything."""
        p = tmp_path / "tpu.json"
        p.write_text(json.dumps(
            {"metric": "llama_train_mfu", "value": 0.52,
             "unit": "mfu_fraction",
             "extra": {"ms_per_step": 203.0,
                       "provenance": {"backend": "tpu",
                                      "git_sha": "abc"}}}))
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "bench_compare.py"),
             "--check", "--fresh", str(p)],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 0
        assert "backend mismatch" in r.stdout


# -- bench provenance --------------------------------------------------------

def test_bench_provenance_block():
    sys.path.insert(0, REPO)
    try:
        import bench
        prov = bench.provenance("off")
        assert prov["fused"] == "off"
        assert prov["jax"] and prov["backend"]
        assert "device" in prov
        # git_sha resolves in a checkout (this repo is one)
        assert prov["git_sha"] is None or len(prov["git_sha"]) >= 7
    finally:
        sys.path.remove(REPO)
