"""The reading of the program's own tracing (program_trace.py): op scopes
decoded from a small trace recorded on a TPU v5e (tests/data/small_trace),
the layer rule and the idle-by-program-span labels on hand-made planes
(every number checked by hand), and one traced call of a small cell on the
CPU (spans and the h2d_bytes counter; the CPU has no device planes).

    JAX_PLATFORMS=cpu PYTHONPATH=perfbench:src python -m pytest -q perfbench/tests
"""
import dataclasses
import pathlib

import jax
import pytest

import harness
import program_trace as PT
import trace_reduce as T
from program_trace import ScopedEvent as E
from trace_reduce import Event, Plane

DATA = pathlib.Path(__file__).parent / "data" / "small_trace"
recorded = pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")

GRADS = "jit(sweep_run)/while/body/closed_call/sweep_grads/vmap(jvp())/dot"


@recorded
def test_recorded_ops_carry_their_scope_and_module():
    planes = PT.load_dir(str(DATA))
    ops = [p for p in planes if p.name == "/device:TPU:0"][0].lines["XLA Ops"]
    by_name = {}
    for ev in ops:
        by_name.setdefault((ev.module, ev.name), set()).add(ev.scope)
    # the matmul's fusion (its convolution inside) and the sort
    assert by_name[("jit_matmul", "fusion")] == {"jit(matmul)/dot_general"}
    assert by_name[("jit_sort", "sort")] == {"jit(sort)/sort"}
    assert by_name[("jit_broadcast_in_dim", "broadcast_in_dim")] == {
        "jit(broadcast_in_dim)/broadcast_in_dim"}
    # XLA's own copies carry no op path, only their program
    assert by_name[("jit_matmul", "copy-done")] == {""}
    assert {PT.layer(ev) for ev in ops} == {PT.OUTSIDE}


def _fields(s):
    out = dataclasses.asdict(s)
    out["op_events"] = {k: [(e.name, e.start_ns, e.end_ns, e.text) for e in v]
                        for k, v in s.op_events.items()}
    return out


@recorded
def test_recorded_summary_reads_the_same():
    """The scoped events change no number reduce_planes computes."""
    before = T.reduce_planes(T.load_dir(str(DATA)))
    after = T.reduce_planes(PT.load_dir(str(DATA)))
    assert _fields(after) == _fields(before)


def test_layer_rule():
    assert PT.layer(E("fusion", 0, 1, scope=GRADS,
                      module="jit_sweep_run")) == "sweep_grads"
    nested = "jit(sweep_run)/sweep_combine/vmap()/sweep_screen/sort"
    assert PT.layer(E("sort", 0, 1, scope=nested)) == "sweep_combine"
    assert PT.layer(E("copy", 0, 1, module="jit_sweep_chunk")) == PT.UNSCOPED
    assert PT.layer(E("copy", 0, 1,
                      scope="jit(sweep_run)/concatenate")) == PT.UNSCOPED
    assert PT.layer(E("fusion", 0, 1, scope="jit(matmul)/dot_general",
                      module="jit_matmul")) == PT.OUTSIDE
    assert PT.layer(Event("fusion", 0, 1)) == PT.OUTSIDE


def _planes():
    host = Plane("/host:CPU", {
        "main": [Event("bench.window", 1000, 2000),
                 Event("bench.call", 1000, 1900),
                 Event("sweep.run", 1000, 1900),
                 Event("sweep.stage", 1000, 1200),
                 Event("sweep.prepare", 1200, 1300),
                 Event("sweep.dispatch", 1300, 1350),
                 Event("sweep.fetch", 1350, 1900),
                 Event("shard_args", 1050, 1150)],   # JAX's own, ignored
    })
    run = "jit_sweep_run"
    ops0 = [E("fusion.1", 900, 1100, scope=GRADS, module=run),  # 100 in
            E("copy.2", 1400, 1500, module=run),
            E("sort_columns", 1500, 1600,
              scope="jit(sweep_run)/sweep_screen/sort_columns", module=run),
            E("while", 1400, 1700, module=run),     # a container: skipped
            E("fusion.3", 1600, 1700,
              scope="jit(sweep_run)/sweep_loss/mul", module=run),
            E("reshape", 1950, 1980, scope="jit(reshape)/reshape",
              module="jit_reshape")]
    ops1 = [E("fusion.1", 1000, 1400, scope=GRADS, module=run)]
    return [host, Plane("/device:TPU:0", {"XLA Ops": ops0}),
            Plane("/device:TPU:1", {"XLA Ops": ops1})]


def test_reduce_layers_hand_checked():
    ly = PT.reduce_layers(_planes())
    ns = 1e-9
    # summed over both chips, clipped to the window
    assert ly.device_s == pytest.approx({
        "sweep_grads": (100 + 400) * ns, PT.UNSCOPED: 100 * ns,
        "sweep_screen": 100 * ns, "sweep_loss": 100 * ns,
        PT.OUTSIDE: 30 * ns})
    assert ly.program_s == pytest.approx(800 * ns)
    assert ly.span_s == pytest.approx({
        "sweep.run": 900 * ns, "sweep.stage": 200 * ns,
        "sweep.prepare": 100 * ns, "sweep.dispatch": 50 * ns,
        "sweep.fetch": 550 * ns})
    assert ly.calls == 1 and ly.chips == 2
    assert ly.op_layer_s["sort_columns"] == pytest.approx(
        {"sweep_screen": 100 * ns})
    assert ly.op_layer_s["fusion.1"] == pytest.approx(
        {"sweep_grads": 500 * ns})
    # idle inside the call (1000-1900): chip 0 1100-1400 (mid 1250:
    # sweep.prepare) and 1700-1900 (sweep.fetch); chip 1 1400-1900 (mid
    # 1650: sweep.fetch).  The innermost sweep.* span labels, JAX's own
    # spans do not, and 1900-2000 lies outside the call.
    assert ly.idle_s == pytest.approx({"sweep.prepare": 300 * ns,
                                       "sweep.fetch": 700 * ns})
    assert ly.covered_share() == pytest.approx(1.0)


def test_idle_outside_every_sweep_span():
    planes = _planes()
    planes[0].lines["main"] = [ev for ev in planes[0].lines["main"]
                               if ev.name != "sweep.prepare"]
    planes[0].lines["main"][2] = Event("sweep.run", 1300, 1900)
    ly = PT.reduce_layers(planes, devices=1)
    # chip 0 only: the gap 1100-1400 has its midpoint 1250 in no sweep.*
    # span (sweep.stage ends at 1200, sweep.run now starts at 1300);
    # 1700-1900 lies in sweep.fetch
    assert ly.idle_s == pytest.approx({PT.NO_SPAN: 300e-9,
                                       "sweep.fetch": 200e-9})
    assert ly.covered_share() == pytest.approx(0.4)


def test_scoped_planes_reduce_as_plain_ones():
    scoped = _planes()
    plain = [Plane(p.name, {k: [Event(e.name, e.start_ns, e.end_ns, e.text)
                                for e in v] for k, v in p.lines.items()})
             for p in scoped]
    assert _fields(T.reduce_planes(scoped)) == _fields(T.reduce_planes(plain))


def test_probe_reads_spans_and_the_counter_on_cpu():
    from test_check import SMALL
    cell = harness.Cell("showdown-seeds", overrides=SMALL["showdown-seeds"])
    system = harness.System(cell, 3, jax.devices()[:1])
    system.call(system.traffic.next_call())
    got = PT.probe(system, 1)
    # device bytes: the float64 digit stack lands as float32
    batches = jax.tree_util.tree_leaves(system.model["batches"])
    lanes = system.lanes_per_call
    want = (sum(jax.numpy.asarray(x).nbytes for x in batches)
            + lanes * 2 * 4)                               # + uint32 keys
    assert got.h2d_bytes == [want]
    assert got.h2d_mb_per_call() == pytest.approx(want / 1e6)
    assert got.layers.calls == 1
    assert got.layers.span_n["sweep.run"] == 1
    for span in ("sweep.stage", "sweep.prepare", "sweep.dispatch",
                 "sweep.fetch"):
        assert got.layers.span_n[span] >= 1, span
    assert 0 < got.per_call_ms("sweep.stage") < got.per_call_ms("sweep.run")
    # no device planes on the CPU: no device layer reads
    assert got.per_lane_round_us("sweep_grads") is None
    assert got.lane_rounds == lanes * system.rounds
