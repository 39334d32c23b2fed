"""The program's spans, counters and device scopes as the benchmark reads
them: the op-to-scope map, the reduction to seconds per device scope and
idle time per innermost span (on hand-made lists), program spans in a
recorded trace, and the span metrics' readers through the CPU rehearsal,
with spans on, off, and on a program that has none."""
from types import SimpleNamespace

import pytest

from chip import harness, scopes, trace
from chip.spans import READERS, measure

SEED = 2**31 + 977
CELLS = [w["name"] for w in harness.manifest()["workloads"]]
DEVICE = ("ga_device_ms", "vote_device_ms")
ns = 1e-9


@pytest.mark.parametrize("path,scope", [
    ("jit(select_ensembles_from_stats)/ga/while/body/closed_call/eval/"
     "vmap()/dot_general", "ga/eval"),
    ("jit(select_ensembles_from_stats)/ga/while/body/closed_call/rank/"
     "vmap()/sort", "ga/rank"),
    ("jit(select_ensembles_from_stats)/ga/while", "ga"),
    ("jit(select_ensembles_from_stats)/vote/vmap()/gather", "vote"),
    ("jit(select_ensembles_from_stats)/ga/init/vmap(jit(_uniform))/vmap()/"
     "add", "ga/init"),
    ("jit(_flush)/flush/gram/dot_general", "flush/gram"),
    ("jit(_flush)/flush/scatter/scatter", "flush/scatter"),
    ("jit(_gather)/gather/gather", "gather"),
    ("jit(select_ensembles_from_stats)/vmap()/gather", None),
    ("jit(_where)/select_n", None),
    ("", None),
])
def test_an_op_name_maps_to_its_scope(path, scope):
    assert scopes.scope_of(path) == scope


def test_device_scopes_count_a_container_only_where_nothing_inside_runs():
    host = [("window", 0, 100)]
    ops = [("while.1", "ga", 10, 60),
           ("fusion.2", "ga/eval", 20, 30),
           ("while.3", "ga/rank", 32, 48),       # a while inside a while
           ("sort.4", "ga/rank", 35, 45),
           ("fusion.5", "vote", 70, 80),
           ("copy.6", None, 85, 90),
           ("fusion.7", "vote", 95, 120)]        # ends outside the window
    got = dict(scopes.device_scopes({"host": host,
                                     "devices": {"/device:TPU:0": ops}}))
    assert got["ga"] == pytest.approx(50 * ns)
    assert got["ga/eval"] == pytest.approx(10 * ns)
    assert got["ga/rank"] == pytest.approx(16 * ns)
    assert got["vote"] == pytest.approx(15 * ns)
    assert got[scopes.UNSCOPED] == pytest.approx(5 * ns)
    # the top level and `unscoped` add up to the busy time, once
    top = sum(v for k, v in got.items() if "/" not in k)
    assert top == pytest.approx(70 * ns)


def test_program_gaps_go_to_the_innermost_span():
    host = [("window", 0, 110),
            ("flush", 0, 50), ("store.flush", 5, 40),
            ("store.flush.assemble", 5, 30),
            ("ga", 50, 100), ("engine.select", 50, 95),
            ("engine.select.fetch", 60, 95)]
    ops = [("fusion.1", "flush/scatter", 35, 45),
           ("while.2", "ga", 62, 90)]
    got = dict(scopes.program_gaps({"host": host,
                                    "devices": {"/device:TPU:0": ops}}))
    assert got["store.flush.assemble"] == pytest.approx(25 * ns)
    assert got["store.flush"] == pytest.approx(5 * ns)
    assert got["flush"] == pytest.approx(10 * ns)     # 0-5 and 45-50
    assert got["engine.select"] == pytest.approx(10 * ns)
    assert got["engine.select.fetch"] == pytest.approx(7 * ns)
    assert got["ga"] == pytest.approx(5 * ns)
    assert got[scopes.NO_SPAN] == pytest.approx(10 * ns)
    assert sum(got.values()) == pytest.approx((110 - 38) * ns)


HLO = (
    'HloModule jit__flush, entry_computation_layout={()->()}\n\n'
    'ENTRY %main {\n'
    '  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fc, '
    'metadata={op_name="jit(_flush)/flush/gram/dot_general" '
    'source_file="x.py" source_line=1}\n'
    '  %copy.4 = f32[2]{0} copy(%fusion.3)\n'
    '  ROOT %while.9 = (s32[]) while(%t), body=%b, '
    'metadata={op_name="jit(_flush)/flush/while"}\n}\n')


def test_hlo_text_gives_each_named_instruction_its_op_name():
    assert scopes.hlo_op_names(HLO) == {
        ("jit__flush", "fusion.3"): "jit(_flush)/flush/gram/dot_general",
        # no op name: that of the scoped instruction it reads
        ("jit__flush", "copy.4"): "jit(_flush)/flush/gram/dot_general",
        ("jit__flush", "while.9"): "jit(_flush)/flush/while"}


SELECT_HLO = (
    'HloModule jit_select, is_scheduled=true\n\n'
    'ENTRY %main (p: f32[4,8]) -> f32[4] {\n'
    '  %p = f32[4,8]{1,0} parameter(0), metadata={op_name="probs_val"}\n'
    '  %copy.1 = f32[4,8]{0,1:T(8,128)} copy(%p), '
    'metadata={op_name="probs_val"}, backend_config={"a":[1]}\n'
    '  %bitcast.2 = f32[32]{0} bitcast(%copy.1)\n'
    '  %fusion.3 = f32[4]{0} fusion(f32[32]{0} %bitcast.2, f32[4,8]{1,0} %p),'
    ' kind=kLoop, calls=%fc, '
    'metadata={op_name="jit(select)/vote/vmap()/dot_general"}\n'
    '  %copy.4 = f32[4,8]{1,0} copy(%p)\n'
    '  %fusion.5 = f32[4]{0} fusion(%copy.4), kind=kLoop, calls=%fd, '
    'metadata={op_name="jit(select)/ga/eval/dot_general"}\n'
    '  %fusion.6 = f32[4]{0} fusion(%copy.4, %fusion.5), kind=kLoop, '
    'calls=%fe, metadata={op_name="jit(select)/vote/add"}\n'
    '  %copy.7 = f32[4]{0} copy(%fusion.6)\n'
    '  ROOT %tuple.8 = (f32[4]{0}) tuple(%copy.7)\n}\n')


def test_an_instruction_outside_every_scope_takes_its_neighbours_scope():
    """A layout copy named after its parameter (or not named) takes the
    scope of the instructions that read it, through a chain of bitcasts;
    where its readers lie under two scopes, that of what it reads."""
    got = {k[1]: scopes.scope_of(v)
           for k, v in scopes.hlo_op_names(SELECT_HLO).items()}
    assert got["copy.1"] == "vote" and got["bitcast.2"] == "vote"
    assert got["copy.7"] == "vote"          # read by nothing scoped
    # read by `ga` and by `vote`, and reads no scoped instruction
    assert "copy.4" not in got


def test_trace_ops_take_their_scope_by_module_and_instruction(monkeypatch):
    """A TPU trace names ops by instruction only: the module comes from
    the `XLA Modules` line; an instruction the op-name map does not know
    takes its module's one top-level scope."""
    import jax

    def ev(name, start, dur):
        return SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                               stats=[])

    def line(name, events):
        return SimpleNamespace(name=name, events=events)

    ops = [ev("%fusion.3 = f32[2]{0} fusion(%p)", 10, 10),
           ev("%copy.4 = f32[2]{0} copy(%fusion.3)", 20, 5),
           ev("%copy.5 = f32[2]{0} copy(%copy.4)", 25, 5),   # not in the HLO
           ev("%fusion.3 = f32[8]{0} fusion(%q)", 40, 10),   # other module
           ev("%copy.1 = f32[8]{0} copy(%x)", 70, 5)]         # no module
    planes = [SimpleNamespace(name="/device:TPU:0", lines=[
                  line("XLA Modules", [ev("jit__flush(123)", 5, 30),
                                       ev("jit_select(456)", 35, 30)]),
                  line("XLA Ops", ops)]),
              SimpleNamespace(name="/host:CPU", lines=[line("python", [
                  ev("window", 0, 100), ev("store.flush.upload", 1, 3),
                  ev("PjitFunction(_flush)", 4, 1)])])]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: SimpleNamespace(
                            planes=planes)))
    names = dict(scopes.hlo_op_names(HLO))
    names[("jit_select", "fusion.3")] = "jit(select)/ga/init/add"
    names[("jit_select", "fusion.8")] = "jit(select)/vote/add"
    got = scopes.read_xplane("unused", names)
    assert got["devices"]["/device:TPU:0"] == [
        ("fusion.3", "flush/gram", 10, 20), ("copy.4", "flush/gram", 20, 25),
        ("copy.5", "flush", 25, 30), ("fusion.3", "ga/init", 40, 50),
        ("copy.1", None, 70, 75)]
    assert sorted(n for n, _, _ in got["host"]) == ["store.flush.upload",
                                                    "window"]
    assert got["mapped"] == pytest.approx(25 / 35)


def test_program_spans_are_read_from_a_recorded_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.obs.metrics import Metrics
    mx = Metrics()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with mx.span("engine.select", select_id=1):
            with mx.span("engine.select.fetch"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = scopes.read_xplane(trace.find_xplane(str(tmp_path)), {})
    names = sorted(n for n, _, _ in got["host"])
    assert names == ["engine.select", "engine.select.fetch", "window"]
    (s0, e0), = [(s, e) for n, s, e in got["host"] if n == "engine.select"]
    (s1, e1), = [(s, e) for n, s, e in got["host"]
                 if n == "engine.select.fetch"]
    assert s0 <= s1 <= e1 <= e0


def test_the_cells_programs_compile_with_their_scopes(tiny):
    """The op-name map of the programs a cell's window runs, compiled for
    the device in use (the CPU here)."""
    from chip.run import run_cell
    from chip.spans import compiled_op_names
    got = {}

    def grab(wl):
        cell, _ = harness.find_cell(harness.manifest(), CELLS[0])
        got.update(compiled_op_names(wl, harness.load_traffic(
            cell["traffic"])))
    run_cell(CELLS[0], SEED, 0.1, False, t_start=harness.now(),
             overrides=tiny, before_window=grab)
    found = {(m, scopes.scope_of(p)) for (m, _), p in got.items()}
    for want in [("jit__flush", "flush/scatter"), ("jit__flush", "flush/gram"),
                 ("jit__gather", "gather"),
                 ("jit_select_ensembles_from_stats", "ga/eval"),
                 ("jit_select_ensembles_from_stats", "vote")]:
        assert want in found, want


def test_readers_return_none_on_a_run_without_spans():
    # what run.py hands a reader: no spans, counters or device scopes
    ctx = SimpleNamespace(records=[{"seconds": 1.0}], window_s=1.0,
                          setup_s=1.0, unit="batch", cfg={}, mix={},
                          peaks={}, trace=None)
    for name in READERS:
        for m in (name, "round_" + name):
            assert harness.load_metric(m).read(ctx) is None, m


@pytest.mark.parametrize("workload", CELLS)
def test_spans_on_give_the_host_metrics_and_leave_results_correct(
        workload, tiny):
    out = measure(workload, SEED, 0.3, t_start=harness.now(), trace_s=0.3,
                  overrides=tiny)
    assert out["correct"], out["checks"]
    assert out["spans_on"] and out["compiles_in_window"] == {}
    got = {k.removeprefix("round_"): v
           for k, v in out["span_metrics"].items()}
    assert sorted(got) == sorted(READERS)
    # the CPU's trace has no device plane, so no device scope
    assert all(got[k] is None for k in DEVICE)
    for k in sorted(set(READERS) - set(DEVICE)):
        assert got[k] is not None and got[k] > 0, (k, got)
    assert 0 < got["flush_useful_pct"] <= 100
    assert out["span_totals"]["store.flush"]["count"] >= 1


def test_spans_off_and_a_program_without_spans_read_null(tiny, monkeypatch):
    # the engine keeps its null registry: no span is recorded
    from chip import spans
    with monkeypatch.context() as m:
        m.setattr(spans, "enable_spans", lambda engine: None)
        off = measure(CELLS[0], SEED, 0.3, t_start=harness.now(),
                      trace_s=0.3, overrides=tiny)
    assert off["correct"] and not off["spans_on"]
    assert set(off["span_metrics"].values()) == {None}
    # an older program: its registry has no `span`, so spans stay off
    import repro.obs.metrics as obs_metrics
    monkeypatch.setattr(obs_metrics, "Metrics", type("Metrics", (), {}))
    old = measure(CELLS[0], SEED, 0.3, t_start=harness.now(), trace_s=0.3,
                  overrides=tiny)
    assert old["correct"] and not old["spans_on"]
    assert set(old["span_metrics"].values()) == {None}
