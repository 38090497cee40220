"""The readers of the serving loop's phases, of the serving step's regions
and of the registry entries a metric file names, on the hand-built serving
trace; and the snapshot that carries those entries to them."""

import pytest

from benchmark import harness
from benchmark import run as brun
from benchmark.runners import serve
from benchmark.work import host_phases

CELL = "mistral-7b.serve-chat"


def context(serve_events, scopes=None, counters=None):
    return {"trace": serve_events,
            "scopes": serve_events["scopes"] if scopes is None else scopes,
            "cell": brun.load_json("workloads", CELL + ".json"),
            "config": brun.load_json("configs", "mistral-7b.json"),
            "layers": 16, "notes": [],
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": counters or {}}


def test_host_milliseconds_per_step_by_phase(serve_events):
    ctx = context(serve_events)
    table = host_phases.phase_table(ctx)
    # first run's start to the last run's start: two periods of 15 ms
    assert table["steps"] == 2
    read = lambda name: brun.load_metric(name).read(ctx)
    assert read("host_ms_per_step.dispatch") == pytest.approx(1.9)
    assert read("host_ms_per_step.plan") == pytest.approx(0.8)
    assert read("host_ms_per_step.emit") == pytest.approx(0.5)
    # admit 0.2 + account 0.2 + pump 0.2 + route 0.3; no draft phase ran
    assert read("host_ms_per_step.rest") == pytest.approx(0.9)
    # waiting is nobody's: sync is 10.1 ms a step, and in no reader
    assert table["ms"]["pdtpu.serve.step.sync"] == pytest.approx(10.1)
    # the four lie within the gap less what sync owns of it
    assert table["gap_ms"]["pdtpu.serve.step.sync"] == pytest.approx(0.1)
    assert sum(table["gap_ms"].values()) == pytest.approx(5.0)
    # the handler's thread is not the loop's
    assert "pdtpu.serve.stream.write" not in table["ms"]
    assert any(n.startswith("host ms per step") for n in ctx["notes"])
    # where the launch and the end fell: 1.9 ms into dispatch, 0.1 ms
    # before sync's end
    spans = [e for e in serve_events["host"] if e[3].endswith("/3:python")]
    lead, tail = host_phases.launch_and_end(
        spans, serve_events["devices"][0]["modules"])
    assert (lead, tail) == (pytest.approx(2.1), pytest.approx(0.1))


def test_phase_readers_read_nothing_without_the_programs_spans(serve_events):
    bare = dict(serve_events, host=[])
    ctx = context(bare)
    for name in ("dispatch", "plan", "emit", "rest"):
        assert brun.load_metric("host_ms_per_step." + name).read(ctx) is None
    one_run = dict(serve_events, devices=[dict(
        serve_events["devices"][0],
        modules=serve_events["devices"][0]["modules"][:1])])
    assert host_phases.phase_table(context(one_run)) is None


def test_the_usual_steps_regions(serve_events):
    ctx = context(serve_events)
    read = lambda name: brun.load_metric(name).read(ctx)
    # the third step is a fan-out step: 7 ms of mlp; the median is 5
    assert read("serve_region_ms.mlp") == pytest.approx(5.0)
    assert read("serve_region_ms.attn_proj") == pytest.approx(3.0)
    assert read("serve_region_ms.attn_core") == pytest.approx(1.0)
    # copy.4 has no scope path: 3 ms of the 32 in leaf operations
    assert read("unscoped_device_pct.serve") == pytest.approx(100 * 3 / 32)
    note = [n for n in ctx["notes"] if n.startswith("the median step")]
    assert "together 10.000 of a median step of 10.000" in note[0]
    # a compiled text without op_name: nothing to read, never 0
    ctx = context(serve_events, scopes={})
    for name in ("serve_region_ms.mlp", "serve_region_ms.attn_proj",
                 "serve_region_ms.attn_core", "unscoped_device_pct.serve"):
        assert brun.load_metric(name).read(ctx) is None


class FakeHistogram:
    def __init__(self):
        self.sum, self.count = 0.0, 0

    def observe(self, v):
        self.sum, self.count = self.sum + v, self.count + 1


class FakeCounter:
    value = 0


class FakeRegistry:
    def __init__(self, **entries):
        self.entries, self.asked = entries, []

    def get(self, name):
        self.asked.append(name)
        return self.entries.get(name)


def test_the_registry_entries_a_cells_metric_files_name():
    bench = brun.load_json("..", "BENCHMARK.json")
    per_layer = {m["name"]: m["unit"] for m in
                 brun.cell_metrics(bench, CELL, "per_layer")}
    names = harness.registry_names({"per_layer": per_layer,
                                    "load_metric": brun.load_metric})
    assert names == ["serve.mlp_live_tiles", "serve.prefill_steps",
                     "serve.queue_ms", "serve.ragged_occupancy"]
    eva = {m["name"]: m["unit"] for m in brun.cell_metrics(
        bench, "evabyte.serve-doc-bytes", "per_layer")}
    assert harness.registry_names({"per_layer": eva,
                                   "load_metric": brun.load_metric}) == [
        "serve.mlp_live_tiles", "serve.ragged_occupancy"]


def test_snapshot_reads_the_named_entries_and_no_other():
    occ, steps, closed = FakeHistogram(), FakeHistogram(), FakeCounter()
    reg = FakeRegistry(**{"serve.ragged_occupancy": occ,
                          "serve.eva.windows_closed": closed,
                          "serve.step_ms": FakeHistogram()})
    names = ["serve.ragged_occupancy", "serve.prefill_steps",
             "serve.eva.windows_closed"]
    occ.observe(0.25)
    closed.value = 3
    a = serve._registry_snapshot(reg, names)
    # prefill_steps does not exist yet; step_ms is in no file's list
    assert a == {"serve.ragged_occupancy": {"sum": 0.25, "count": 1},
                 "serve.eva.windows_closed": {"value": 3}}
    assert "serve.step_ms" not in reg.asked
    occ.observe(0.5)
    occ.observe(0.75)
    closed.value = 7
    reg.entries["serve.prefill_steps"] = steps      # made in the window
    steps.observe(2)
    steps.observe(5)
    delta = serve._registry_delta(a, serve._registry_snapshot(reg, names))
    assert delta == {"serve.ragged_occupancy": {"sum": 1.25, "count": 2},
                     "serve.prefill_steps": {"sum": 7.0, "count": 2},
                     "serve.eva.windows_closed": {"value": 4}}
    # the untraced run has no registry: nothing is read, nothing is kept
    assert serve._registry_snapshot(None) is None
    assert serve._registry_delta(None, None) == {}


def test_readers_of_registry_entries(serve_events):
    window = {"serve.ragged_occupancy": {"sum": 1.25, "count": 2},
              "serve.prefill_steps": {"sum": 7.0, "count": 2},
              "serve.queue_ms": {"sum": 30.0, "count": 4},
              "serve.mlp_live_tiles": {"sum": 9.0, "count": 8}}
    ctx = context(serve_events, counters={"window": window, "traced": {}})
    read = lambda name: brun.load_metric(name).read(ctx)
    assert read("ragged_occupancy_pct") == pytest.approx(62.5)
    assert read("prefill_steps_mean") == pytest.approx(3.5)
    assert read("queue_wait_mean_ms") == pytest.approx(7.5)
    assert read("mlp_live_tiles_mean") == pytest.approx(1.125)
    # an entry that observed nothing in the window reads nothing
    window["serve.queue_ms"] = {"sum": 0.0, "count": 0}
    del window["serve.prefill_steps"]
    assert read("queue_wait_mean_ms") is None
    assert read("prefill_steps_mean") is None
    assert read("mfu.serve") is None
