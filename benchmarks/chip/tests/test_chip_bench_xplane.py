"""The trace reduction, on hand-made events and on a recorded excerpt."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import xplane

OPS = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("a", 50, 55)]


def test_union_merges_overlaps_and_clips():
    assert xplane.union_ns(OPS) == 20 + 10 + 5
    assert xplane.union_ns(OPS, 8, 35) == 12 + 5
    assert xplane.union_ns([]) == 0


def test_idle_gaps_and_share():
    assert xplane.idle_gaps(OPS, 0, 60) == [(20, 30), (40, 50), (55, 60)]
    assert xplane.idle_gaps(OPS, 12, 45) == [(20, 30), (40, 45)]
    assert xplane.span_idle_share(OPS, 0, 60) == pytest.approx(25 / 60)


def test_programs_and_gaps():
    mods = [("jit_serve_step(7)", 100, 200), ("jit__lambda(3)", 0, 50),
            ("jit_serve_step(7)", 260, 360), ("jit_serve_stepper", 400, 410)]
    runs = xplane.programs(mods, "serve_step")
    assert [r[1] for r in runs] == [100, 260]
    assert xplane.programs(mods, "train_step") == []
    assert xplane.mean_gap_ns(runs[:2]) == 60
    assert xplane.mean_gap_ns(runs[:1]) is None


def test_top_ops_sums_self_time_by_name():
    flat = [("a", 0, 10), ("b", 10, 22), ("a", 30, 35), ("c", 40, 41)]
    assert xplane.top_ops(flat, 2) == [["a", 15e-9], ["b", 12e-9]]
    # a loop's event holds its body's: only what lies outside them counts
    nested = [("%while.1 = (s32[]) while(...)", 0, 100),
              ("%fusion.2 = bf16[8] fusion(...)", 10, 40),
              ("%fusion.3 = bf16[8] fusion(...)", 50, 95),
              ("%copy.4 = f32[8] copy(...)", 60, 70)]
    assert xplane.top_ops(nested) == [["%fusion.3", 35e-9],
                                      ["%fusion.2", 30e-9],
                                      ["%while.1", 25e-9],
                                      ["%copy.4", 10e-9]]


def test_gap_labels_take_the_innermost_python_function():
    host = [("$serve.py:10 run", 0, 100), ("$kv_cache.py:5 append", 20, 35),
            ("$builtins len", 25, 27), ("$serve.py:30 pick", 41, 49)]
    ops = [("x", 0, 22), ("y", 36, 42), ("z", 48, 70), ("w", 80, 100)]
    # gaps: (22, 36) mid 29 -> append (not the builtin inside it);
    # (42, 48) -> pick; (70, 80) -> run; (100, 120): nothing is open
    assert xplane.labelled_gaps(ops, host, 0, 120) == [
        ["unattributed", 20e-9], ["kv_cache.py:5 append", 14e-9],
        ["serve.py:10 run", 10e-9], ["serve.py:30 pick", 6e-9]]


def _recorded():
    path = Path(__file__).with_name("serve_two_steps.trace.json")
    d = next(iter(json.loads(path.read_text())["devices"].values()))
    return ([tuple(o) for o in d["ops"]], [tuple(m) for m in d["modules"]])


def test_recorded_decode_steps():
    """Two decode steps of the serving cell on a v5e: 6.69 ms of device
    work each, 1.77 ms apart; the cache's copies lead the self times."""
    ops, mods = _recorded()
    steps = xplane.programs(mods, "serve_step")
    assert [e - s for _, s, e in steps] == [6_690_680, 6_690_041]
    assert xplane.mean_gap_ns(steps) == 1_768_676
    assert xplane.union_ns(ops) == 13_380_135
    lo, hi = steps[0][1], steps[-1][2]
    assert xplane.span_idle_share(ops, lo, hi) == pytest.approx(0.1167876, 1e-6)
    top = xplane.top_ops(ops, 3)
    assert [name for name, _ in top] == [
        "%bitcast_dynamic-update-slice_fusion.5",
        "%bitcast_dynamic-update-slice_fusion.4",
        "%dynamic-slice_bitcast_fusion.5"]
    assert top[0][1] == pytest.approx(1.819253e-3)


def test_metric_readers_on_the_recorded_steps():
    from benchmarks.chip import harness, peaks

    ops, mods = _recorded()
    ctx = {"device0": {"ops": ops, "modules": mods},
           "config": harness.load_json(harness.HERE / "configs"
                                       / "qwen1.5-0.5b.json"),
           "args": {"batch": 8, "prompt-len": 1024, "gen": 512},
           "peaks": peaks.peaks_for("TPU v5 lite"), "work": {}}

    def read(name):
        mod = harness.load_module(harness.HERE / "metrics" / f"{name}.py",
                                  "t_" + name.replace(".", "_"))
        return mod.read(ctx)

    # 2 steps' needed bytes (1.75 GB each, HBM-bound) at 819 GB/s over
    # the 15.15 ms from the first step's start to the second's end
    assert read("decode.mfu_pct") == pytest.approx(27.998, abs=1e-3)
    assert read("decode.host_gap_us") == pytest.approx(1768.676)
    assert read("device.idle_pct.serve") == pytest.approx(11.67876, 1e-5)
    # nothing to read: no prefill time
    assert read("prefill.mfu_pct") is None


def test_load_finds_the_driving_thread(tmp_path):
    import jax

    def run_job():
        return sorted(str(i) for i in range(2000))

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    run_job()
    jax.profiler.stop_trace()
    tr = xplane.load(str(next(tmp_path.rglob("*.xplane.pb"))))
    assert any(name.endswith(" run_job") for name, _, _ in tr["host"])
    assert tr["devices"] == {}
