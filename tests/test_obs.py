"""``repro.obs`` — run-wide telemetry behind every compiled plan.

The contract under test:

  * disabled telemetry is genuinely free-ish: ``obs=None`` and a disabled
    ``ObsConfig`` share the no-op code path (shared null span, no files,
    no span, annotation or event over a 20-round run);
  * enabled spans are profiler annotations, and the compiled round's
    op -> scope map lands in the run dir;
  * spans nest, fence device work into ``sync_s``, and emit clean
    hierarchical paths (no duplicated segments);
  * the JSONL sink buffers, the manifest merges, ``plan``/``sweep``
    entries append;
  * ``RoundRecord.to_dict`` is JSON-round-trippable (numpy scalars and
    cohort tuples coerced);
  * the recompile counter demonstrably fires on a forced shape change;
  * an obs-enabled ``plan.run`` writes a run dir whose phase breakdown
    covers >= 95% of the root spans' wall clock, renders via
    ``tools/obs_report.py``, and decomposes UAV missions into
    travel/hover/comm dwell on the simulated clock;
  * Monte-Carlo sweeps stream ``mc/*`` spans + a ``sweep`` manifest entry
    without changing ``wall_s`` semantics;
  * the perf trend gate warns (not KeyError) on variants missing from the
    latest commit and passes vacuously on single-commit logs.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from repro.api import (ClientSpec, CutPolicy, DataSpec, EngineSpec,
                       ExperimentSpec, MissionSpec, ModelSpec,
                       compile_experiment)
from repro.api.records import RoundRecord
from repro.obs import (NULL_OBS, Obs, ObsConfig, fenced, host_rss_bytes,
                       pytree_bytes, time_fenced)
from repro.obs.gauges import RecompileCounter, global_counter
from repro.obs.profiler import ProfilerCapture
from repro.obs.sink import JsonlSink, NullSink, json_default
from repro.obs.timeline import NULL_SPAN, Timeline

NUM_CLASSES = 4

BASE = ExperimentSpec(
    model=ModelSpec(name="tinycnn", num_classes=NUM_CLASSES),
    data=DataSpec(kind="synthetic", image_size=16, classes_per_client=2),
    clients=ClientSpec(num_clients=4),
    cut_policy=CutPolicy(mode="fraction", fraction=0.4),
    engine=EngineSpec(kind="sl", client_axis="vmap"),
    global_rounds=2, local_steps=2, batch_size=4)


class ListSink:
    run_dir = None

    def __init__(self):
        self.events = []
        self.manifest = {}

    def emit(self, event):
        self.events.append(event)

    def write_manifest(self, fields):
        self.manifest.update(fields)

    def flush(self):
        pass

    def close(self):
        pass


def _load_events(run_dir):
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# records: JSON-serializable to_dict (satellite 1)
# ---------------------------------------------------------------------------

def test_round_record_to_dict_json_round_trip():
    rec = RoundRecord(
        round=np.int64(3), loss=np.float32(0.5),
        accuracy=np.float64("nan"), link_bytes=np.float32(1e6),
        link_time_s=0.1, link_energy_j=np.float64(2.0),
        client_energy_j=jnp.float32(3.0), server_energy_j=4.0,
        uav_energy_j=5.0, active_clients=np.int32(4),
        engine="sl/vmap",
        cohort_pids=tuple(np.asarray([7, 9], np.int64)))
    d = rec.to_dict()
    s = json.dumps(d)                      # must not raise on numpy scalars
    back = json.loads(s)
    assert back["round"] == 3
    assert isinstance(back["round"], int)
    assert back["cohort_pids"] == [7, 9]
    assert back["engine"] == "sl/vmap"
    assert abs(back["loss"] - 0.5) < 1e-6
    assert back["accuracy"] != back["accuracy"]        # NaN survives as NaN
    for v in d.values():                   # every leaf is a Python native
        if isinstance(v, tuple):
            assert all(isinstance(x, int) for x in v)
        else:
            assert not hasattr(v, "dtype")


# ---------------------------------------------------------------------------
# timeline
# ---------------------------------------------------------------------------

def test_span_nesting_paths_and_depth():
    sink = ListSink()
    tl = Timeline(sink)
    with tl.span("run", rounds=2):
        with tl.span("round", round=0):
            with tl.span("round/execute"):
                pass
        with tl.span("round", round=1):
            pass
    evs = sink.events
    assert [e["path"] for e in evs] == \
        ["run/round/execute", "run/round", "run/round", "run"]
    assert [e["depth"] for e in evs] == [2, 1, 1, 0]
    # hierarchical names splice without duplicating shared segments
    assert "round/round" not in evs[0]["path"]
    assert evs[0]["name"] == "round/execute"
    assert evs[-1]["rounds"] == 2
    # children are contained in the parent's wall clock
    assert evs[1]["dur_s"] >= evs[0]["dur_s"]
    assert evs[-1]["dur_s"] >= evs[1]["dur_s"] + evs[2]["dur_s"] - 1e-6


def test_span_fence_books_sync_and_note():
    sink = ListSink()
    tl = Timeline(sink)
    with tl.span("execute") as sp:
        y = jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64)))
        out = sp.fence(y)
        sp.note(flavor="matmul")
    ev = sink.events[0]
    assert out is y
    assert 0.0 <= ev["sync_s"] <= ev["dur_s"]
    assert ev["flavor"] == "matmul"
    # host-only values fence as no-ops
    with tl.span("host") as sp:
        assert sp.fence({"a": 1}) == {"a": 1}


def test_fenced_helpers():
    out, wall = fenced(lambda: jnp.arange(8).sum())
    assert int(out) == 28 and wall > 0
    calls = []
    wall = time_fenced(lambda: calls.append(1) or jnp.ones(4), repeats=5)
    assert len(calls) == 5 and wall > 0


def test_disabled_timeline_hands_out_shared_null_span():
    tl = Timeline(ListSink(), enabled=False)
    sp = tl.span("anything", round=3)
    assert sp is NULL_SPAN and tl.span("other") is NULL_SPAN
    with sp as s:
        assert s.fence(5) == 5
        s.note(ignored=True)


# ---------------------------------------------------------------------------
# sink
# ---------------------------------------------------------------------------

def test_jsonl_sink_buffers_and_manifest_appends(tmp_path):
    run_dir = str(tmp_path / "run")
    sink = JsonlSink(run_dir, buffer=3)
    ev_path = os.path.join(run_dir, "events.jsonl")
    sink.emit({"ev": "note", "i": 0})
    sink.emit({"ev": "note", "i": 1})
    assert not os.path.exists(ev_path)          # buffered, not yet on disk
    sink.emit({"ev": "note", "i": 2})           # buffer full -> flushed
    assert len(open(ev_path).readlines()) == 3
    sink.emit({"ev": "note", "i": 3, "x": np.float32(1.5)})
    sink.close()                                # close flushes the tail
    lines = [json.loads(line) for line in open(ev_path)]
    assert [e["i"] for e in lines] == [0, 1, 2, 3]
    assert lines[-1]["x"] == 1.5                # numpy coerced by default=

    sink.write_manifest({"a": 1, "plan": {"model": "m1"}})
    sink.write_manifest({"b": 2, "plan": {"model": "m2"},
                         "sweep": {"num_seeds": 4}})
    man = json.load(open(os.path.join(run_dir, "manifest.json")))
    assert man["a"] == 1 and man["b"] == 2
    assert [p["model"] for p in man["plans"]] == ["m1", "m2"]
    assert man["sweeps"] == [{"num_seeds": 4}]


def test_json_default_coercions():
    assert json_default(np.float32(2.5)) == 2.5
    assert json_default(np.arange(3)) == [0, 1, 2]
    assert json_default(object()).startswith("<object")


# ---------------------------------------------------------------------------
# gauges
# ---------------------------------------------------------------------------

def test_pytree_bytes_and_rss():
    tree = {"a": jnp.zeros((4, 4), jnp.float32),
            "b": (np.zeros(10, np.int64), "not-an-array", 3.0)}
    assert pytree_bytes(tree) == 4 * 4 * 4 + 10 * 8
    assert pytree_bytes(None) == 0
    assert host_rss_bytes() > 0


def test_recompile_counter_fires_on_shape_change():
    counter = global_counter()
    if not counter.available:
        pytest.skip("jax monitoring hooks unavailable in this jax build")

    @jax.jit
    def f(x):
        return (x * 2.0).sum()

    # unique prime-ish shapes so earlier tests' compile cache can't absorb
    # them; each new shape forces a fresh backend compile
    c0, s0 = counter.snapshot()
    jax.block_until_ready(f(jnp.zeros((3, 41))))
    c1, s1 = counter.snapshot()
    assert c1 > c0 and s1 >= s0
    jax.block_until_ready(f(jnp.zeros((3, 43))))   # forced shape change
    c2, _ = counter.snapshot()
    assert c2 > c1
    jax.block_until_ready(f(jnp.zeros((3, 43))))   # cache hit: no compile
    c3, _ = counter.snapshot()
    assert c3 == c2


def test_recompile_counter_install_uninstall():
    from jax._src import monitoring
    c = RecompileCounter()
    c.install()
    assert c.available
    n0 = c.snapshot()[0]
    jax.block_until_ready(jax.jit(lambda x: x + 1)(jnp.zeros(37)))
    assert c.snapshot()[0] > n0
    c.uninstall()
    assert not c.available
    # the listener is really gone: a later compile no longer reaches it
    assert c._listen not in monitoring.get_event_duration_listeners()
    n1 = c.snapshot()[0]
    jax.block_until_ready(jax.jit(lambda x: x + 2)(jnp.zeros(41)))
    assert c.snapshot()[0] == n1


# ---------------------------------------------------------------------------
# Obs facade
# ---------------------------------------------------------------------------

def test_null_obs_is_shared_and_writes_nothing(tmp_path):
    assert Obs.ensure(None) is NULL_OBS
    assert not NULL_OBS and NULL_OBS.run_dir is None
    assert isinstance(NULL_OBS.sink, NullSink)
    assert NULL_OBS.span("x") is NULL_SPAN
    NULL_OBS.event("note", x=1)
    NULL_OBS.gauge(0, engine_state={"w": jnp.zeros(4)})
    NULL_OBS.record(RoundRecord(0, 0., 0., 0., 0., 0., 0., 0., 0.))
    NULL_OBS.manifest(a=1)
    NULL_OBS.round_started(0)
    NULL_OBS.round_finished(0)
    NULL_OBS.flush()
    assert NULL_OBS.compiles_total() == 0
    assert list(tmp_path.iterdir()) == []
    # a disabled config behaves identically (and is its own instance)
    off = Obs.ensure(ObsConfig(enabled=False))
    assert not off and off.span("x") is NULL_SPAN and off.run_dir is None


def test_obs_ensure_normalization(tmp_path):
    cfg = ObsConfig(run_root=str(tmp_path), run_id="r1", gauge_every=2)
    obs = Obs.ensure(cfg)
    assert obs and obs.run_dir == str(tmp_path / "r1")
    assert Obs.ensure(obs) is obs
    obs.gauge(0, tally=1)
    obs.gauge(1, tally=1)      # throttled: gauge_every=2 skips odd rounds
    obs.gauge(2, tally=1)
    obs.close()
    gauges = [e for e in _load_events(obs.run_dir) if e["ev"] == "gauge"]
    assert [g["round"] for g in gauges] == [0, 2]
    assert all(g["rss_bytes"] > 0 for g in gauges)
    man = json.load(open(os.path.join(obs.run_dir, "manifest.json")))
    assert man["run_id"] == "r1" and man["jax_version"] == jax.__version__


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------

def test_profiler_capture_window(tmp_path):
    cap = ProfilerCapture((1, 2), str(tmp_path / "prof"))
    assert cap.status == "armed"
    cap.round_started(0)
    assert cap.status == "armed"               # before the window: idle
    cap.round_started(1)                       # window opens
    cap.round_finished(1)
    cap.round_started(2)
    cap.round_finished(2)                      # window closes
    cap.close()
    assert cap.status.startswith("captured") and not cap.active
    assert os.path.isdir(str(tmp_path / "prof"))


def test_profiler_failed_start_raises(tmp_path, monkeypatch):
    """A capture the caller asked for that cannot start fails the run
    instead of recording a status and carrying on."""
    import jax.profiler

    def broken(*a, **k):
        raise RuntimeError("no profiler")
    monkeypatch.setattr(jax.profiler, "start_trace", broken)
    cap = ProfilerCapture((0, 0), str(tmp_path / "prof"))
    with pytest.raises(RuntimeError, match="no profiler"):
        cap.round_started(0)
    assert not cap.active


HLO = """HloModule jit_sl_round, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(sl_round)/sl/server/exp"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

ENTRY %main.7 (x: f32[4]) -> f32[] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %copy.2 = f32[4]{0} copy(%fusion)
  %c = f32[] constant(0)
  ROOT %reduce.3 = f32[] reduce(%copy.2, %c), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(sl_round)/sl/client/transpose(jvp(sl/client))/reduce_sum"}
}
"""


def test_hlo_op_scopes_reads_each_op_of_its_own():
    from repro.obs.profiler import hlo_op_scopes
    module, ops = hlo_op_scopes(HLO)
    assert module == "jit_sl_round"
    # fusion bodies and reducers run as no op of their own; a fusion with
    # no metadata is its root's; an op with none is left out
    assert ops == {"x": "x", "fusion": "jit(sl_round)/sl/server/exp",
                   "reduce.3": "jit(sl_round)/sl/client/"
                               "transpose(jvp(sl/client))/reduce_sum"}


def test_profiler_validates_window():
    with pytest.raises(ValueError):
        ProfilerCapture((3, 1), "x")
    off = ProfilerCapture(None, "x")
    off.round_started(0)
    off.close()
    assert off.status == "off"


# ---------------------------------------------------------------------------
# plan integration: the acceptance criteria
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mission_run(tmp_path_factory):
    """One obs-enabled mission campaign, shared across assertions."""
    root = str(tmp_path_factory.mktemp("runs"))
    spec = ExperimentSpec(
        model=BASE.model, data=BASE.data, clients=BASE.clients,
        cut_policy=BASE.cut_policy, engine=BASE.engine,
        mission=MissionSpec(farm_acres=100.0),
        global_rounds=3, local_steps=2, batch_size=4)
    plan = compile_experiment(
        spec, obs=ObsConfig(run_root=root, run_id="trun"))
    state, records = plan.run()
    plan.obs.close()
    return plan, records, plan.obs.run_dir


def test_plan_run_writes_run_dir(mission_run):
    plan, records, run_dir = mission_run
    assert sorted(os.listdir(run_dir)) == ["events.jsonl", "manifest.json",
                                           "ops"]
    # the compiled round's op -> scope map, under the round's stable name
    assert os.listdir(os.path.join(run_dir, "ops")) == ["jit_sl_round.json"]
    ops = json.load(open(os.path.join(run_dir, "ops", "jit_sl_round.json")))
    assert ops["module"] == "jit_sl_round"
    for scope in ("sl/client", "sl/server"):
        assert any(scope in v for v in ops["ops"].values())
    man = json.load(open(os.path.join(run_dir, "manifest.json")))
    assert man["backend"] == jax.default_backend()
    assert len(man["plans"]) == 1
    p = man["plans"][0]
    assert p["engine"] == "sl/vmap" and p["num_clients"] == 4
    evs = _load_events(run_dir)
    kinds = {e["ev"] for e in evs}
    assert {"span", "gauge", "record", "mission_span"} <= kinds
    recs = [e for e in evs if e["ev"] == "record"]
    assert [r["round"] for r in recs] == [0, 1, 2]
    # the record stream round-trips the RoundRecord values verbatim
    assert abs(recs[-1]["loss"] - records[-1].loss) < 1e-9
    gauges = [e for e in evs if e["ev"] == "gauge"]
    assert len(gauges) == 3
    assert all(g["state_bytes"] > 0 and g["rss_bytes"] > 0 for g in gauges)
    assert all(g["cohort"] == 0 and g["dropped"] == 0 for g in gauges)


def test_phase_breakdown_covers_95pct(mission_run):
    import obs_report
    _, _, run_dir = mission_run
    manifest, events = obs_report.load_run(run_dir)
    spans = [e for e in events if e["ev"] == "span"]
    # EVERY root phase (compile, run) must be >=95% accounted for by its
    # direct children — the "no unexplained time" acceptance bar
    for root in (e for e in spans if e["depth"] == 0):
        prefix = root["path"] + "/"
        child_s = sum(e["dur_s"] for e in spans
                      if e["depth"] == 1 and e["path"].startswith(prefix))
        assert child_s >= 0.95 * root["dur_s"], root["path"]
    cov, root = obs_report.root_coverage(events)
    assert root is not None and cov >= 0.95
    # and the report renders without touching jax
    lines = obs_report.render(run_dir, manifest, events)
    text = "\n".join(lines)
    assert "coverage" in text and "round/execute" in text
    assert "mission dwell" in text


def test_mission_span_decomposition(mission_run):
    plan, records, run_dir = mission_run
    evs = [e for e in _load_events(run_dir) if e["ev"] == "mission_span"]
    assert {e["name"] for e in evs} == \
        {"mission/travel", "mission/hover", "mission/comm"}
    assert all(e["clock"] == "mission" for e in evs)
    per_round = [e for e in evs if e["round"] == 0]
    travel = [e for e in per_round if e["name"] == "mission/travel"][0]
    hover = [e for e in per_round if e["name"] == "mission/hover"][0]
    comm = [e for e in per_round if e["name"] == "mission/comm"][0]
    n = plan.spec.clients.num_clients
    mission = plan.spec.mission
    assert travel["dur_s"] == pytest.approx(
        plan.tour.tour_length / mission.uav.V, abs=1e-2)
    assert hover["dur_s"] == pytest.approx(n * mission.hover_s_per_stop)
    assert comm["dur_s"] == pytest.approx(n * mission.comm_s_per_stop)
    # legs are laid end-to-end on the simulated clock
    assert hover["t_mission_s"] == pytest.approx(
        travel["t_mission_s"] + travel["dur_s"], abs=1e-2)
    # one (travel, hover, comm) triple per executed round
    assert len(evs) == 3 * len(records)


def test_profile_rounds_capture_via_plan(tmp_path):
    plan = compile_experiment(
        BASE, obs=ObsConfig(run_root=str(tmp_path), run_id="prof",
                            profile_rounds=(0, 0)))
    plan.run(rounds=2, with_eval=False)
    plan.obs.close()
    man = json.load(open(os.path.join(plan.obs.run_dir, "manifest.json")))
    assert man["profiler"].startswith("captured")


def _recording_annotations(monkeypatch, entered):
    """Stand-ins for ``jax.profiler``'s annotations that record each
    enter as ``(kind, name, kwargs)`` and each exit as ``("exit", name)``."""
    def fake(kind):
        class Annotation:
            def __init__(self, name, **kwargs):
                self.name, self.kwargs = name, kwargs

            def __enter__(self):
                entered.append((kind, self.name, self.kwargs))
                return self

            def __exit__(self, *exc):
                entered.append(("exit", self.name))
                return False
        return Annotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake("trace"))
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", fake("step"))


def test_enabled_timeline_annotates_each_span(monkeypatch):
    """An enabled span is one profiler annotation named by the span, with
    its round (the ``round`` span a step annotation); a disabled timeline
    enters none."""
    entered = []
    _recording_annotations(monkeypatch, entered)
    tl = Timeline(ListSink(), enabled=True)
    with tl.span("round", round=3):
        with tl.span("round/execute", round=3):
            pass
    with tl.span("compile/flops"):
        pass
    assert entered == [
        ("step", "round", {"step_num": 3, "round": 3}),
        ("trace", "round/execute", {"round": 3}),
        ("exit", "round/execute"), ("exit", "round"),
        ("trace", "compile/flops", {}), ("exit", "compile/flops")]
    assert len(tl._sink.events) == 3
    entered.clear()
    off = Timeline(ListSink(), enabled=False)
    with off.span("round", round=0):
        with off.span("round/execute", round=0):
            pass
    assert entered == [] and off._sink.events == []


def test_profile_shows_program_spans_on_its_clock(tmp_path):
    """``profile_rounds`` traces the program's own spans: each traced
    round's ``round`` step and its ``round/execute`` inside it, named by
    the span and carrying the round."""
    import glob
    plan = compile_experiment(
        BASE, obs=ObsConfig(run_root=str(tmp_path), run_id="ann",
                            profile_rounds=(1, 2)))
    plan.run(rounds=3, with_eval=False)
    plan.obs.close()
    path, = glob.glob(os.path.join(plan.obs.run_dir, "profile", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    events = [e for p in jax.profiler.ProfileData.from_file(path).planes
              if p.name.startswith("/host") for ln in p.lines
              for e in ln.events]
    rounds = [e for e in events if e.name == "round"]
    execs = [e for e in events if e.name == "round/execute"]
    assert sorted(dict(e.stats)["step_num"] for e in rounds) == [1, 2]
    assert sorted(dict(e.stats)["round"] for e in execs) == [1, 2]
    for r in rounds:
        ex, = [e for e in execs
               if dict(e.stats)["round"] == dict(r.stats)["step_num"]]
        assert r.start_ns <= ex.start_ns and ex.end_ns <= r.end_ns


def test_obs_overhead_under_2pct(monkeypatch):
    """The disabled-telemetry hot path does no telemetry work: over a
    20-round run of a plan compiled with ``obs=None`` (the shared
    NULL_OBS) and of one with a disabled ``ObsConfig``, every seam is
    handed NULL_SPAN, and no Span is constructed, no profiler annotation
    entered, no event emitted and no op map written. What that path costs
    in time is measured on the chip, where the benchmark runs with
    telemetry off."""
    from repro.obs.timeline import Span
    spec = ExperimentSpec(
        model=BASE.model, data=BASE.data, clients=BASE.clients,
        cut_policy=BASE.cut_policy, engine=BASE.engine,
        global_rounds=20, local_steps=2, batch_size=4)
    plan_none = compile_experiment(spec)                 # obs=None -> NULL_OBS
    plan_off = compile_experiment(spec, obs=ObsConfig(enabled=False))
    assert plan_none.obs is NULL_OBS and not plan_off.obs
    batches = plan_none.round_batches(plan_none.init())

    work = {"span": 0, "emit": 0, "op_map": 0}
    entered, handed = [], []

    def count(key, fn):
        def wrapped(*a, **k):
            work[key] += 1
            return fn(*a, **k)
        return wrapped
    _recording_annotations(monkeypatch, entered)
    monkeypatch.setattr(Span, "__init__", count("span", Span.__init__))
    for sink in (NullSink, JsonlSink):
        monkeypatch.setattr(sink, "emit", count("emit", sink.emit))
    monkeypatch.setattr(Obs, "op_scopes", count("op_map", Obs.op_scopes))
    span = Timeline.span
    monkeypatch.setattr(Timeline, "span", lambda self, *a, **k: (
        handed.append(span(self, *a, **k)) or handed[-1]))

    for plan in (plan_none, plan_off):
        st = plan.init()
        for _ in range(20):
            st, rec = plan.run_round(st, batches, with_eval=False)
        assert st.round == 20 and np.isfinite(rec.loss)
    assert work == {"span": 0, "emit": 0, "op_map": 0} and entered == []
    # round, round/sample, round/execute, round/account: each round, each
    # plan
    assert len(handed) == 2 * 20 * 4
    assert all(sp is NULL_SPAN for sp in handed)


# ---------------------------------------------------------------------------
# monte-carlo sweeps
# ---------------------------------------------------------------------------

def test_monte_carlo_emits_sweep_telemetry(tmp_path):
    plan = compile_experiment(
        BASE, obs=ObsConfig(run_root=str(tmp_path), run_id="mc"))
    from repro.sim import run_monte_carlo
    mc = run_monte_carlo(plan, 2, rounds=2)      # inherits plan.obs
    plan.obs.close()
    evs = _load_events(plan.obs.run_dir)
    paths = {e["path"] for e in evs if e["ev"] == "span"}
    assert {"mc/setup", "mc/compile", "mc/execute",
            "mc/summarize"} <= paths
    note = [e for e in evs if e["ev"] == "note"
            and e.get("kind") == "monte_carlo"][0]
    assert note["num_seeds"] == 2 and note["mode"] == "vmap"
    assert note["wall_s"] == pytest.approx(mc.wall_s, abs=1e-5)
    man = json.load(open(os.path.join(plan.obs.run_dir, "manifest.json")))
    sweep = man["sweeps"][0]
    assert sweep["seeds"] == [0, 1] and sweep["rounds"] == 2


def test_monte_carlo_without_obs_writes_nothing(tmp_path):
    plan = compile_experiment(BASE)
    from repro.sim import run_monte_carlo
    mc = run_monte_carlo(plan, 2, rounds=2)
    assert mc.rounds == 2 and plan.obs is NULL_OBS
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# reports: trend-gate edges (satellite 3) + obs_report + --runs cross-link
# ---------------------------------------------------------------------------

def _perf_row(commit, variant, sps, case="c4s4b16"):
    return {"commit": commit, "bench": "engine_perf", "model": "tinycnn",
            "case": case, "variant": variant, "steps_per_s": sps}


def test_trend_gate_warns_on_missing_variant(tmp_path, capsys):
    from benchmarks.report import check_perf, missing_variants, perf_trend
    rows = [_perf_row("aaa", "sl_fleet", 100.0),
            _perf_row("aaa", "mc_vmap", 500.0, case="c4s2b8x16"),
            _perf_row("bbb", "sl_fleet", 99.0)]     # mc_vmap gone (shrunk)
    # no KeyError; the shared key still compares
    comps, regs = perf_trend(rows, threshold=0.10)
    assert len(comps) == 1 and regs == []
    assert missing_variants(rows) == ["tinycnn/c4s2b8x16/mc_vmap"]
    path = tmp_path / "engine_perf.json"
    path.write_text(json.dumps(rows))
    assert check_perf(str(path), threshold=0.10) == 0   # warn, don't fail
    out = capsys.readouterr().out
    assert "warning" in out and "mc_vmap" in out


def test_trend_gate_single_commit_vacuous(tmp_path, capsys):
    from benchmarks.report import check_perf, missing_variants
    rows = [_perf_row("aaa", "sl_fleet", 100.0),
            _perf_row("aaa", "fl_vmap", 200.0)]
    assert missing_variants(rows) == []
    path = tmp_path / "engine_perf.json"
    path.write_text(json.dumps(rows))
    assert check_perf(str(path)) == 0                   # passes vacuously
    assert "nothing to compare" in capsys.readouterr().out
    path.write_text("[]")
    assert check_perf(str(path)) == 0


def test_runs_overview_cross_links_gate_commits(tmp_path):
    from benchmarks.report import runs_overview
    root = tmp_path / "runs"
    for rid, commit in [("r-aaa", "aaa"), ("r-bbb", "bbb"),
                        ("r-zzz", "zzz")]:
        d = root / rid
        d.mkdir(parents=True)
        (d / "manifest.json").write_text(json.dumps(
            {"run_id": rid, "git_commit": commit, "created_utc": "t",
             "plans": [{"model": "tinycnn"}]}))
        (d / "events.jsonl").write_text('{"ev": "span"}\n')
    perf = tmp_path / "engine_perf.json"
    perf.write_text(json.dumps([_perf_row("aaa", "sl_fleet", 100.0),
                                _perf_row("bbb", "sl_fleet", 99.0)]))
    rows = runs_overview(str(root), perf_log=str(perf))
    by_id = {r["run_id"]: r for r in rows}
    assert by_id["r-aaa"]["gate_side"] == "prev"
    assert by_id["r-bbb"]["gate_side"] == "cur"
    assert by_id["r-zzz"]["gate_side"] is None
    assert not by_id["r-zzz"]["in_perf_log"]
    assert all(r["events"] == 1 and r["plans"] == 1 for r in rows)


def test_obs_report_spark_and_cli(tmp_path, capsys):
    import obs_report
    assert obs_report.spark([1.0, 2.0, 3.0]) == "▁▄█"
    assert obs_report.spark([float("nan"), 1.0]) == " ▁"
    assert obs_report.spark([]) == ""
    # latest_run_dir picks the newest (ids sort chronologically)
    (tmp_path / "20250101-000000-1").mkdir()
    (tmp_path / "20250102-000000-1").mkdir()
    assert obs_report.latest_run_dir(str(tmp_path)).endswith("0102-000000-1")
