"""The span recorder (``repro.trace``) and the spans of a serving job."""

import gc
import threading
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import trace
from repro.launch import serve

B, GEN = 2, 6
SERVE_ARGV = ["--arch", "qwen1.5-0.5b", "--reduced", "--batch", str(B),
              "--prompt-len", "16", "--gen", str(GEN), "--spill"]
# every span of a --spill job, with how many times a job opens it
SERVE_SPANS = {
    "serve.job": 1, "serve.init": 1, "serve.compile": 1, "serve.prefill": 1,
    "serve.splice": 1, "box.open": 1, "serve.decode": 1,
    "serve.decode.step": GEN, "serve.decode.dispatch": GEN,
    "serve.decode.token_read": GEN, "kv.append": B * GEN,
    "serve.spill_check": 1, "kv.spill": B, "kv.fetch": B, "box.close": 1,
}


def test_spans_nest_and_carry_their_parent_and_job():
    rec = trace.Recorder()
    with rec.job("job") as root:
        with rec.span("outer") as outer:
            with rec.span("inner") as inner:
                pass
        with rec.span("after") as after:
            pass
    job = root.job
    assert rec.jobs() == [job]
    assert all(s.job is job for s in (root, outer, inner, after))
    spans = job.spans                                   # in order of start
    assert [r.name for r in spans] == ["job", "outer", "inner", "after"]
    assert [r.pos for r in spans] == [0, 1, 2, 3]
    assert [r.parent for r in spans] == [None, 0, 1, 0]
    assert job.root == spans[0]
    assert spans[0].start_ns <= spans[1].start_ns <= spans[2].start_ns \
        <= spans[2].end_ns <= spans[1].end_ns <= spans[3].start_ns \
        <= spans[3].end_ns <= spans[0].end_ns
    assert job.named("inner") == [spans[2]]
    assert trace.seconds(inner) == trace.seconds(spans[2]) >= 0


def test_spans_outside_a_job_are_not_kept():
    rec = trace.Recorder()
    with rec.span("loose") as loose:
        rec.count("n", 3)
    assert rec.jobs() == [] and loose.job is None and loose.counts is None
    assert loose.end_ns >= loose.start_ns


def test_a_span_belongs_to_the_job_open_on_its_thread():
    rec = trace.Recorder()
    seen = {}

    def other():
        with rec.span("elsewhere") as s:
            seen["span"] = s

    with rec.job("job") as root:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen["span"].job is None and seen["span"].parent is None
    assert [r.name for r in root.job.spans] == ["job"]


def test_counters_land_on_the_innermost_open_span():
    rec = trace.Recorder()
    with rec.job("job") as root:
        rec.count("calls")
        with rec.span("outer"):
            rec.count("rows", 2)
            with rec.span("inner"):
                rec.count("rows", 5)
                rec.count("rows", 1)
            rec.count("pages", 4)
    job_, outer, inner = root.job.spans
    assert job_.counts == {"calls": 1}
    assert outer.counts == {"rows": 2, "pages": 4}
    assert inner.counts == {"rows": 6}
    summary = trace.summary(root.job)
    assert list(summary) == ["job", "outer", "inner"]
    assert summary["inner"]["rows"] == 6 and summary["inner"]["count"] == 1
    assert summary["outer"]["total_s"] == trace.seconds(outer)


def test_memory_stays_bounded_and_the_first_job_is_kept():
    rec = trace.Recorder(keep=3)
    roots = []
    for _ in range(50):
        with rec.job("job") as root:
            for _ in range(10):
                with rec.span("step"):
                    pass
        roots.append(root)
    kept = rec.jobs()
    assert [j.id for j in kept] == [1, 48, 49, 50]
    assert kept[0] is roots[0].job and len(kept[0].spans) == 11
    assert len(rec.recent) == 3


def test_recording_keeps_nothing_the_garbage_collector_tracks():
    """Spans inside a timed loop add no work to the collector, so none of
    its collections lands in the loop on their account."""
    rec = trace.Recorder()
    with rec.job("job"):
        gc.disable()
        try:
            before = gc.get_count()[0]
            for i in range(1000):
                with rec.span("step") as s:
                    s.add("rows", 1)
                    rec.count("pages", i % 2)
            grown = gc.get_count()[0] - before
        finally:
            gc.enable()
    assert grown < 10
    assert trace.summary(rec.jobs()[0])["step"]["rows"] == 1000


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """A scratch persistent compile cache, on for the module: JAX counts
    compile requests only where the cache is on, and serve.run keeps the
    cache where JAX_COMPILATION_CACHE_DIR says."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    path = str(tmp_path_factory.mktemp("jax_cache"))
    before = jax.config.jax_compilation_cache_dir
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", path)
        jax.config.update("jax_compilation_cache_dir", path)
        cc.reset_cache()
        try:
            yield path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            cc.reset_cache()


def serve_job(argv):
    out = serve.run(argv)
    return out, trace.jobs()[-1]


@pytest.fixture(scope="module")
def served(compile_cache):
    return serve_job(SERVE_ARGV)


def test_serving_job_records_every_span(served):
    out, job = served
    summary = trace.summary(job)
    assert {name: row["count"] for name, row in summary.items()} == SERVE_SPANS
    assert job.root.name == "serve.job" and job.root.pos == 0
    # the returned windows are the spans' own
    for key, name in (("prefill_s", "serve.prefill"),
                      ("compile_s", "serve.compile")):
        assert out[key] == trace.seconds(job.named(name)[0])
    assert out["decode_tok_s"] == B * GEN / trace.seconds(
        job.named("serve.decode")[0])
    steps = [r.pos for r in job.named("serve.decode.step")]
    decode = job.named("serve.decode")[0]
    assert all(r.parent == decode.pos for r in job.named("serve.decode.step"))
    for name in ("serve.decode.dispatch", "serve.decode.token_read"):
        assert [r.parent for r in job.named(name)] == steps
    assert {r.parent for r in job.named("kv.append")} == set(steps)
    check = job.named("serve.spill_check")[0]
    assert all(r.parent == check.pos
               for r in job.named("kv.spill") + job.named("kv.fetch"))


def test_serving_job_counts_the_kv_tier_and_the_engine(served):
    _, job = served
    summary = trace.summary(job)
    assert summary["kv.append"]["rows"] == B * GEN
    assert summary["kv.append"]["pages"] >= B
    assert summary["kv.spill"]["pages"] == summary["kv.fetch"]["pages"] >= B
    assert summary["kv.spill"]["bytes"] == summary["kv.fetch"]["bytes"] > 0
    root = job.root.counts
    assert root["kv.rows_appended"] == B * GEN
    assert root["kv.pages_spilled"] == summary["kv.spill"]["pages"]
    assert root["kv.bytes_fetched"] == summary["kv.fetch"]["bytes"]
    assert root["rdma_ops"] > 0 and root["merge_drains"] > 0


def test_serving_job_counts_the_cache_bytes_its_step_aliases(served):
    """``serve.compile`` carries ``step_alias_bytes``: the compiled
    decode step hands the whole donated cache on to its output."""
    from repro.configs import get_reduced
    from repro.models import init_cache

    _, job = served
    cache = jax.eval_shape(lambda: init_cache(
        get_reduced("qwen1.5-0.5b"), B, max_len=16 + GEN))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert trace.summary(job)["serve.compile"]["step_alias_bytes"] == nbytes > 0


def test_no_compile_is_counted_inside_the_decode_loop(served):
    _, job = served
    spans = job.spans
    decode = job.named("serve.decode")[0].pos

    def under_decode(r):
        while r.parent is not None and r.pos != decode:
            r = spans[r.parent]
        return r.pos == decode

    compiles = set(trace.COMPILE_EVENTS.values())
    inside = [r.name for r in spans
              if under_decode(r) and compiles & set(r.counts or {})]
    assert inside == []
    # the counter sees compiles where they happen
    assert job.named("serve.compile")[0].counts["compile_requests"] >= 2


def test_spans_land_on_the_profilers_host_plane(compile_cache, tmp_path):
    """Under the profiler, each span is a host event of its name, as
    long as its in-memory record (within 5% above 1 ms), and the job's
    wall-clock offset puts the record where the event lies on the
    trace's timeline."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, job = serve_job(SERVE_ARGV)
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    # host events start at offsets from the trace's start, on the wall clock
    start = dict(data.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SERVE_SPANS:
                        events.setdefault(e.name, []).append(e)
    assert set(events) == set(SERVE_SPANS)
    for name, evs in events.items():
        spans = job.named(name)
        assert len(evs) == len(spans), name
        evs.sort(key=lambda e: e.start_ns)
        for e, s in zip(evs, spans):
            ns = s.end_ns - s.start_ns
            if ns > 1e6:
                assert abs(e.duration_ns - ns) <= 0.05 * ns, (name, e, ns)
            assert abs(start + e.start_ns
                       - (s.start_ns + job.wall_offset_ns)) < 1e6, name


def test_decode_dispatches_the_next_step_before_it_reads_a_token(
        compile_cache, monkeypatch):
    """The decode loop reads step i's token only after it has dispatched
    step i + 1, so the device has a step queued while the host waits on
    a token; every token is read once, in step order. The compiled step
    and numpy's ``asarray`` are wrapped in the serve module, as the chip
    benchmark's tap wraps numpy there, and record what they were given."""
    events, toks = [], []

    class Tapped:
        """The step program, as serve.run lowers, compiles and calls it."""

        def __init__(self, f):
            self.f = f

        def __getattr__(self, name):
            return getattr(self.f, name)

        def lower(self, *a):
            return Tapped(self.f.lower(*a))

        def compile(self):
            return Tapped(self.f.compile())

        def __call__(self, *a):
            out = self.f(*a)
            events.append(("dispatch", len(toks)))
            toks.append(out[2])
            return out

    def asarray(a, *args, **kw):
        step = next((i for i, t in enumerate(toks) if t is a), None)
        if step is not None:
            events.append(("read", step))
        return np.asarray(a, *args, **kw)

    programs = serve.programs

    def tapped_programs(cfg):
        prefill_jit, step_jit, pick = programs(cfg)
        return prefill_jit, Tapped(step_jit), pick

    tapped_np = types.ModuleType(np.__name__)
    tapped_np.__dict__.update(vars(np), asarray=asarray)
    monkeypatch.setattr(serve, "programs", tapped_programs)
    monkeypatch.setattr(serve, "np", tapped_np)
    _, job = serve_job(SERVE_ARGV)

    assert [i for kind, i in events if kind == "dispatch"] == list(range(GEN))
    assert [i for kind, i in events if kind == "read"] == list(range(GEN))
    at = {e: n for n, e in enumerate(events)}
    for i in range(GEN - 1):
        assert at[("dispatch", i + 1)] < at[("read", i)], events
    counts = job.named("serve.decode")[0].counts
    assert counts["decode.read_lag"] == serve.READ_LAG >= 1
    assert 0 <= counts["decode.reads_ready"] <= GEN
