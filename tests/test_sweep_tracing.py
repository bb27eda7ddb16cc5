"""Tracing of the sweep program: the five layer scopes in the compiled HLO's
op_name metadata (and nothing but metadata), the host spans of one
`SweepEngine.run` in a profiler trace, and the per-run host-to-device byte
counter."""
import contextlib
import pathlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.power_control import Policy
from repro.core.scenario import DefenseSpec
from repro.fl import ExecutionPlan, ScenarioCase, SweepEngine, SweepSpec
from repro.fl import sweep as SW
from sweep_testlib import floa, tiny_problem

ROUNDS = 4


def _eval(p):
    return {"w1_mean": jnp.mean(p["w1"])}


def _grouped_cases(dim):
    """Analog, median and Krum lanes: the flat grouped path."""
    return [
        ScenarioCase("bev@N1", floa(dim, Policy.BEV, 1), 0.05, seed=1),
        ScenarioCase("median@N1", floa(dim, Policy.EF, 1, 0.0), 0.05, seed=2,
                     defense=DefenseSpec(name="median")),
        ScenarioCase("krum@N1", floa(dim, Policy.EF, 1, 0.0), 0.05, seed=3,
                     defense=DefenseSpec(name="krum", num_byzantine=1)),
    ]


def _floa_cases(dim):
    return [ScenarioCase(f"{pol.value}@N{n}", floa(dim, pol, n), 0.05,
                         seed=10 + n)
            for pol in (Policy.BEV, Policy.CI) for n in (0, 1)]


# name -> (cases, plan, the program's attribute, the scopes it must carry)
ALL = set(SW.LAYER_SCOPES)
NO_SCREEN = ALL - {SW.SCOPE_SCREEN}
PROGRAMS = {
    "flat_grouped": (_grouped_cases, ExecutionPlan(), "_run_jit", ALL),
    "flat_floa": (_floa_cases, ExecutionPlan(), "_run_jit", NO_SCREEN),
    "flat_switch": (_grouped_cases, ExecutionPlan(grouped_dispatch=False),
                    "_run_jit", ALL),
    "tree_grouped": (_grouped_cases, ExecutionPlan(flat_state=False),
                     "_run_jit", ALL),
    "tree_switch": (_grouped_cases,
                    ExecutionPlan(flat_state=False, grouped_dispatch=False),
                    "_run_jit", ALL),
    "chunk": (_grouped_cases, ExecutionPlan(chunk_rounds=2), "_chunk_jit",
              ALL),
}


def _engine(name):
    cases, plan, attr, scopes = PROGRAMS[name]
    loss, params, dim, batches = tiny_problem(rounds=ROUNDS)
    engine = SweepEngine(loss, SweepSpec.build(cases(dim)), eval_fn=_eval,
                         eval_every=2, plan=plan)
    return engine, params, batches, attr, scopes


def compiled_text(engine, params, batches, attr) -> str:
    """The compiled text of the engine's program `attr`, lowered with the
    arguments a run handed it."""
    engine.run(params, batches)          # builds the programs
    fn, seen = getattr(engine, attr), {}

    def spy(*args):
        seen["args"] = args
        return fn(*args)

    setattr(engine, attr, spy)
    try:
        engine.run(params, batches)
    finally:
        setattr(engine, attr, fn)
    return fn.lower(*seen["args"]).compile().as_text()


def op_name_components(text: str) -> set:
    return {c for path in re.findall(r'op_name="([^"]*)"', text)
            for c in path.split("/")}


METADATA = re.compile(r",? metadata=\{[^}]*\}")
STACK_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)$|^\d+ ")


def without_metadata(text: str) -> str:
    return "\n".join(l for l in METADATA.sub("", text).splitlines()
                     if not STACK_TABLES.match(l))


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_compiled_program_carries_its_layer_scopes(name):
    engine, params, batches, attr, scopes = _engine(name)
    text = compiled_text(engine, params, batches, attr)
    module = "jit_sweep_chunk" if attr == "_chunk_jit" else "jit_sweep_run"
    assert text.startswith(f"HloModule {module}")
    present = op_name_components(text) & ALL
    assert present == scopes, (name, sorted(present))


@pytest.mark.parametrize("name", ["flat_grouped", "flat_floa", "tree_switch",
                                  "chunk"])
def test_scopes_are_metadata_only(name, monkeypatch):
    engine, params, batches, attr, _ = _engine(name)
    scoped = compiled_text(engine, params, batches, attr)
    monkeypatch.setattr(SW, "_layer", lambda _name: contextlib.nullcontext())
    engine, params, batches, attr, _ = _engine(name)
    plain = compiled_text(engine, params, batches, attr)
    assert not op_name_components(plain) & ALL
    assert without_metadata(plain) == without_metadata(scoped)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    out = []
    with warnings.catch_warnings():
        # jaxlib warns while it builds the type of an event's stats; this
        # suite's filters would make that an error, which aborts the
        # process inside the binding.
        warnings.filterwarnings("ignore", "builtin type event_stats",
                                DeprecationWarning)
        for f in pathlib.Path(trace_dir).rglob("*.xplane.pb"):
            for plane in ProfileData.from_file(str(f)).planes:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("sweep."):
                            out.append((line.name, ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        dict(ev.stats)))
    return out


def test_run_spans_nest_in_sweep_run(tmp_path):
    engine, params, batches, _, _ = _engine("flat_grouped")
    engine.run(params, batches)          # run 0 builds; run 1 is traced
    with jax.profiler.trace(str(tmp_path)):
        engine.run(params, batches)
    events = _host_events(tmp_path)
    runs = [e for e in events if e[1] == "sweep.run"]
    assert len(runs) == 1
    line, _, lo, hi, stats = runs[0]
    assert stats["run"] == 1
    assert stats["lanes"] == 3 and stats["rounds"] == ROUNDS
    inside = {name for ln, name, s, e, _ in events
              if ln == line and lo <= s and e <= hi and name != "sweep.run"}
    assert {"sweep.prepare", "sweep.stage", "sweep.dispatch",
            "sweep.fetch"} <= inside


def test_spec_and_init_spans(tmp_path):
    loss, params, dim, batches = tiny_problem(rounds=ROUNDS)
    with jax.profiler.trace(str(tmp_path)):
        spec = SweepSpec.build(_floa_cases(dim))
        SweepEngine(loss, spec)
    names = [e[1] for e in _host_events(tmp_path)]
    assert names.count("sweep.spec") == 1
    assert names.count("sweep.init") == 1


@contextlib.contextmanager
def recorded_scalars():
    seen = []

    def listener(name, value, **kw):
        if name == SW.H2D_BYTES:
            seen.append(value)

    jax.monitoring.register_scalar_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_scalar_listener(listener)


@pytest.mark.parametrize("chunk", [None, 3])
def test_h2d_bytes_counts_the_staged_arrays(chunk):
    loss, params, dim, batches = tiny_problem(rounds=ROUNDS + 1)
    spec = SweepSpec.build(_grouped_cases(dim))
    engine = SweepEngine(loss, spec, plan=ExecutionPlan(chunk_rounds=chunk))
    keys = np.asarray(spec.keys())                  # host keys are staged
    host_params = jax.tree_util.tree_map(np.asarray, params)
    want_batches = sum(x.nbytes for x in batches.values())
    want = want_batches + keys.nbytes
    with recorded_scalars() as seen:
        engine.run(params, batches, keys=keys)      # device params: not staged
        engine.run(host_params, batches, keys=keys)
        engine.run(params, {k: jnp.asarray(v) for k, v in batches.items()})
    params_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    # a device batch stack is not staged again (monolithic); the chunked
    # path keeps the stack on the host and stages it block by block
    again = want_batches if chunk else 0
    assert seen == [want, want + params_bytes, again]
    assert want_batches == 4 * (ROUNDS + 1) * (4 * 8) * (6 + 1)
