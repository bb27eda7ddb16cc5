"""The sweep-bench perf-regression gate (`sweep_bench.check_regressions`)
is pure record-vs-record logic, so its contract is pinned here without
running the bench: rows regress only below baseline * (1 - tolerance),
shape-mismatched rows are skipped (reported), and missing rows never fail.
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

pytest.importorskip("benchmarks.sweep_bench")
from benchmarks.sweep_bench import check_regressions


def _rec(engines=None, defenses=None, scenarios=16, rounds=25,
         chunk_rounds=5):
    rec = {"scenarios": scenarios, "rounds": rounds,
           "chunk_rounds": chunk_rounds}
    if engines:
        rec["engines"] = {k: {"warm_rounds_per_sec": v}
                          for k, v in engines.items()}
    if defenses:
        rec["defenses"] = {k: {"warm_rounds_per_sec": v, "lanes": 6,
                               "rounds": 10} for k, v in defenses.items()}
    return rec


def test_gate_passes_within_tolerance():
    base = _rec(engines={"flat": 100.0}, defenses={"mixed": 40.0})
    fresh = _rec(engines={"flat": 51.0}, defenses={"mixed": 20.1})
    fails, notes = check_regressions(fresh, base, tolerance=0.5)
    assert fails == [] and notes == []


def test_gate_fails_below_floor():
    base = _rec(engines={"flat": 100.0}, defenses={"mixed": 40.0})
    fresh = _rec(engines={"flat": 49.0}, defenses={"mixed": 41.0})
    fails, _ = check_regressions(fresh, base, tolerance=0.5)
    assert len(fails) == 1 and "engines/flat" in fails[0]


def test_gate_skips_shape_mismatches():
    base = _rec(engines={"flat": 100.0}, defenses={"mixed": 40.0})
    # different headline grid shape: engine rows must be skipped, not failed
    fresh = _rec(engines={"flat": 1.0}, defenses={"mixed": 40.0}, scenarios=4)
    fails, notes = check_regressions(fresh, base, tolerance=0.5)
    assert fails == [] and any("engine rows skipped" in n for n in notes)
    # per-defense lane/round mismatch: that row is skipped
    fresh2 = _rec(engines={"flat": 100.0}, defenses={"mixed": 1.0})
    fresh2["defenses"]["mixed"]["lanes"] = 3
    fails2, notes2 = check_regressions(fresh2, base, tolerance=0.5)
    assert fails2 == [] and any("defenses/mixed" in n for n in notes2)


def test_gate_skips_chunk_rows_on_chunk_rounds_mismatch():
    """A different --chunk-rounds is a different program shape for the
    flat+chunk rows only: those skip (reported), the rest still gate."""
    base = _rec(engines={"flat": 100.0, "flat+chunk": 100.0,
                         "flat+chunk+async": 100.0})
    fresh = _rec(engines={"flat": 80.0, "flat+chunk": 1.0,
                          "flat+chunk+async": 1.0}, chunk_rounds=1)
    fails, notes = check_regressions(fresh, base, tolerance=0.5)
    assert fails == []
    assert sum("chunk_rounds differs" in n for n in notes) == 2
    # and a non-chunk row still fails on the same records
    fresh["engines"]["flat"]["warm_rounds_per_sec"] = 1.0
    fails2, _ = check_regressions(fresh, base, tolerance=0.5)
    assert len(fails2) == 1 and "engines/flat:" in fails2[0]


def _resume_row(chunked=100.0, ckpt=90.0, lanes=8, rounds=10,
                chunk_rounds=5, dim=50890):
    return {"lanes": lanes, "rounds": rounds, "chunk_rounds": chunk_rounds,
            "dim": dim,
            "chunked": {"warm_rounds_per_sec": chunked},
            "chunked_ckpt": {"warm_rounds_per_sec": ckpt}}


def test_gate_resume_rows():
    """The resume section gates its chunked/chunked_ckpt warm rows
    shape-aware (lanes/rounds/chunk_rounds/dim)."""
    base = _rec(engines={"flat": 100.0})
    base["resume"] = _resume_row()
    # within tolerance: passes
    fresh = _rec(engines={"flat": 100.0})
    fresh["resume"] = _resume_row(chunked=51.0, ckpt=46.0)
    fails, notes = check_regressions(fresh, base, tolerance=0.5)
    assert fails == [] and notes == []
    # a collapsed checkpointed row fails
    fresh["resume"]["chunked_ckpt"]["warm_rounds_per_sec"] = 1.0
    fails2, _ = check_regressions(fresh, base, tolerance=0.5)
    assert len(fails2) == 1 and "resume/chunked_ckpt" in fails2[0]
    # a different resume grid shape skips instead
    fresh["resume"]["lanes"] = 4
    fails3, notes3 = check_regressions(fresh, base, tolerance=0.5)
    assert fails3 == [] and any("resume" in n for n in notes3)
    # resume missing from the fresh run: skipped, reported
    del fresh["resume"]
    fails4, notes4 = check_regressions(fresh, base, tolerance=0.5)
    assert fails4 == [] and any("resume: not in fresh run" in n
                                for n in notes4)


def _lm_row(unsharded=100.0, sharded=80.0, d=50000, model_shards=8):
    row = {"d": d, "u": 8, "lanes": 2, "rounds": 3,
           "model_shards": model_shards,
           "unsharded": {"warm_rounds_per_sec": unsharded}}
    if sharded is not None:
        row["model_sharded"] = {"warm_rounds_per_sec": sharded}
    return row


def test_gate_lm_rows():
    """The --lm D-scaling section gates both its unsharded and
    model-sharded warm rows, shape-aware in (d, u, lanes, rounds,
    model_shards)."""
    base = _rec(engines={"flat": 100.0})
    base["lm"] = {"D50000": _lm_row()}
    fresh = _rec(engines={"flat": 100.0})
    fresh["lm"] = {"D50000": _lm_row(unsharded=51.0, sharded=41.0)}
    fails, notes = check_regressions(fresh, base, tolerance=0.5)
    assert fails == [] and notes == []
    # a collapsed model-sharded row fails
    fresh["lm"]["D50000"]["model_sharded"]["warm_rounds_per_sec"] = 1.0
    fails2, _ = check_regressions(fresh, base, tolerance=0.5)
    assert len(fails2) == 1 and "lm/D50000/model_sharded" in fails2[0]
    # a different device count is a different program shape: skipped
    fresh["lm"]["D50000"]["model_shards"] = 1
    fails3, notes3 = check_regressions(fresh, base, tolerance=0.5)
    assert fails3 == [] and any("lm/D50000" in n for n in notes3)
    # single-device fresh run without the sharded sub-row: skipped, noted
    fresh["lm"]["D50000"] = _lm_row(sharded=None)
    fails4, notes4 = check_regressions(fresh, base, tolerance=0.5)
    assert fails4 == [] and any("lm/D50000/model_sharded" in n
                                for n in notes4)
    # a D missing from the fresh series: skipped, noted
    del fresh["lm"]["D50000"]
    fails5, notes5 = check_regressions(fresh, base, tolerance=0.5)
    assert fails5 == [] and any("lm/D50000: not in fresh" in n
                                for n in notes5)


def test_gate_skips_missing_rows():
    base = _rec(engines={"flat": 100.0, "looped": 10.0},
                defenses={"mixed": 40.0, "krum": 70.0})
    fresh = _rec(engines={"flat": 100.0}, defenses={"mixed": 40.0})
    fails, notes = check_regressions(fresh, base, tolerance=0.5)
    assert fails == []
    assert any("engines/looped" in n for n in notes)
    assert any("defenses/krum" in n for n in notes)
