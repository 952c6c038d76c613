"""Drive one benchmark cell: warm up, measure a window, trace, check.

Everything that belongs to one cell, configuration, entry or per-layer
metric sits in a file of its own under this directory and is found by the
name that ``BENCHMARK.json`` gives:

- ``workloads/<cell>.json``: the entry, its arguments and the limits of
  the correctness check;
- ``configs/<config>.json``: the sizes as run, with the name of the plain
  reference in ``reference/``;
- ``entries/<entry>.py``: how a job's returned dict turns into work and
  into what the check compares, and the tap that hands the program the
  run's seed;
- ``metrics/<metric>.py``: a reader of one per-layer metric.

A run is a closed loop: one batch job in flight, the next one started when
it ends. The warm-up is one whole job. The window then runs jobs back to
back until ``--seconds`` have passed; every end-to-end metric sums over
all jobs started in it. With ``--trace 1`` the window is two jobs traced
by the JAX profiler (see ``_window``), and the per-layer metrics are read
from the trace.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import io
import itertools
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def prepare() -> None:
    """Before JAX is imported: keep its compile cache at a fixed path
    inside the checkout, cache every program however fast it compiled,
    and import the program from the checkout's ``src/``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path.insert(0, str(ROOT / "src"))


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: Dict, cell: str, kind: str) -> List[Dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def job_argv(args: Dict) -> List[str]:
    """{"batch": 8, "spill": true} -> ["--batch", "8", "--spill"]."""
    argv: List[str] = []
    for key, value in args.items():
        if value is True:
            argv.append(f"--{key}")
        elif value is not False:
            argv += [f"--{key}", str(value)]
    return argv


def takes_seed(run) -> bool:
    """Whether an entry's ``--help`` lists ``--seed``."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), contextlib.suppress(SystemExit):
        run(["--help"])
    return "--seed" in buf.getvalue()


def device_summary(jax) -> Dict:
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class Cell:
    """One cell with its configuration, entry and reference loaded."""

    def __init__(self, spec: Dict, name: str, cell: Optional[Dict] = None,
                 config: Optional[Dict] = None) -> None:
        self.spec = spec
        self.name = name
        self.entry_spec = next(w for w in spec["workloads"]
                               if w["name"] == name)
        self.cell = cell or load_json(HERE / "workloads" / f"{name}.json")
        cfg_name = self.entry_spec["config"]
        self.config = config or load_json(HERE / "configs" / f"{cfg_name}.json")
        self.entry = importlib.import_module(
            f"benchmarks.chip.entries.{self.cell['entry']}")
        self.reference = importlib.import_module(
            f"benchmarks.chip.reference.{self.config['reference']}")
        self.args = {"arch": self.config["arch"], **self.cell["args"]}

    def program(self):
        return importlib.import_module(self.entry.MODULE).run


def run_job(run, argv: List[str]):
    """One job; the entry's printing is captured (and passed on to
    stderr) so that the result line stays the last line of stdout."""
    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        out = run(argv)
    wall = time.perf_counter() - t0
    sys.stderr.write(buf.getvalue())
    return out, wall


def measure(c: Cell, seed: int, seconds: float, trace: bool, *,
            warmup: bool = True, quant: Optional[str] = None,
            fault: Optional[str] = None) -> Dict:
    """Warm up, run the window, check; returns the result line's dict.

    ``quant`` puts the reference's precision-reduced twin in the
    program's place in the check (the control), and ``fault`` plants one
    of ``faults.FAULTS`` under every job: both only serve to show what
    the check catches, and the benchmark's own runs use neither."""
    from benchmarks.chip import faults

    run = c.program()
    seeded = takes_seed(run)
    base = job_argv(c.args) + (["--seed", str(seed)] if seeded else [])
    scratch = Path(tempfile.mkdtemp(prefix="chipbench-"))
    planted = (faults.FAULTS[c.cell["entry"]][fault]() if fault
               else contextlib.nullcontext())
    try:
        # without --seed, the entry's tap hands the seed to the program
        with planted, c.entry.tap(None if seeded else seed) as record:
            setup_s, works, device, kept = _window(c, run, base, record,
                                                   scratch, seconds, trace,
                                                   warmup)
        if not (seeded or record["swapped"]):
            print(f"note: seed {seed} reached no draw of {c.entry.MODULE}",
                  file=sys.stderr, flush=True)
        gc.collect()
        result: Dict = {"correct": None, "attempted": sum(
            w["requests"] for w in works), "failed": 0, "metrics": {},
            "device": device}
        on_chip = device["platform"] == "tpu"
        if trace:
            result.update(read_trace(c, scratch, works, device, on_chip))
        elif on_chip:
            e2e = dict(c.entry.end_to_end(works), setup_s=setup_s)
            for m in cell_metrics(c.spec, c.name, "end_to_end"):
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
        numbers = c.entry.check(kept, c.config, c.args, seed, c.reference,
                                quant)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    limits = c.cell["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    result["correct"] = all(v["value"] <= v["limit"] for v in checks.values())
    result["checks"] = checks
    return result


def _window(c: Cell, run, base: List[str], record: Dict, scratch: Path,
            seconds: float, trace: bool, warmup: bool):
    """The warm-up job, then the window's jobs; returns the set-up
    seconds, each window job's work, the device with its memory peak
    (read before the check touches the device) and what the last job
    served.

    Traced, the window is two jobs: the first with the device and host
    tracers alone, which the per-layer metrics read, and the second with
    JAX's Python tracer on as well, whose calls label the device's idle
    gaps. The Python tracer slows the host's Python several times over,
    so it stays out of the trace the metrics read."""
    import jax

    if warmup:
        run_job(run, base)
    setup_s = process_age_s()
    t_window = time.perf_counter()
    works: List[Dict] = []
    for i in itertools.count(1):
        record["stacked"].clear()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = int(i == 2)
            jax.profiler.start_trace(str(scratch / TRACES[i - 1]),
                                     profiler_options=opts)
        out, wall = run_job(run, base)
        if trace:
            jax.profiler.stop_trace()
        works.append(dict(c.entry.work(out, c.args), wall_s=wall))
        last = i == len(TRACES) if trace else \
            time.perf_counter() - t_window >= seconds
        if last:
            device = device_summary(jax)
            kept = c.entry.served(out, record, c.config, c.args)
        # the job's state goes before the next job starts
        del out
        if last:
            return setup_s, works, device, kept


# the traced window's jobs: device tracer only, then the Python tracer too
TRACES = ("trace", "trace_py")


def read_trace(c: Cell, scratch: Path, works: List[Dict], device: Dict,
               on_chip: bool) -> Dict:
    """Per-layer metrics, busy and window seconds, and the breakdown of
    the traced jobs. Off the chip there is no device plane, and nothing
    is reported under a device metric's name."""
    from benchmarks.chip import peaks, xplane

    tr, tr_py = (xplane.load(str(sorted((scratch / d).rglob("*.xplane.pb"))
                                 [-1])) for d in TRACES)
    if not on_chip or not tr["devices"]:
        return {}
    dev = sorted(tr["devices"])
    ops = [tr["devices"][d]["ops"] for d in dev]
    ctx = {"device0": tr["devices"][dev[0]], "work": works[0],
           "config": c.config, "args": c.args,
           "peaks": peaks.peaks_for(device["kind"])}
    metrics = {}
    for m in cell_metrics(c.spec, c.name, "per_layer"):
        reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # busy: the union of each chip's operations, averaged over the chips
    device.update(busy_s=sum(xplane.union_ns(o) for o in ops) / len(ops)
                  / 1e9, window_s=works[0]["wall_s"])
    breakdown = {"device_ops": xplane.top_ops(ops[0])}
    if tr_py["devices"]:
        py_ops = tr_py["devices"][sorted(tr_py["devices"])[0]]["ops"]
        events = py_ops + tr_py["host"]
        breakdown["idle_gaps"] = xplane.labelled_gaps(
            py_ops, tr_py["host"], min(e[1] for e in events),
            max(e[2] for e in events))
    return {"metrics": metrics, "breakdown": breakdown}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    c = Cell(load_json(ROOT / "BENCHMARK.json"), a.workload)
    import jax

    devices = jax.devices()
    need = c.entry_spec["chips"]
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"error: the cell needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = measure(c, a.seed, a.seconds, bool(a.trace))
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)       # "checks" is the last key
    return 0
