"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` by name; its configuration,
traffic mix and comparison limits are data files found by name:
``configs/<config>.json`` (through the entry's ``file``),
``traffic/<traffic>.json`` and ``checks/<workload>.json``.  The traffic
file names its runner, ``runners/<runner>.py``, which sets the program up,
runs the measured window and compares what the window produced with the
reference.  With ``--trace 1`` the window runs under the profiler and
each per-layer metric of the cell is read by ``metrics/<metric>.py``.

The last line on stdout is the result as one JSON object; the last lines
on stderr are the compared numbers beside their limits.  Without a TPU,
or with fewer chips than the cell asks for, the run exits 2 and prints
no result.
"""

import time

T0 = time.perf_counter()  # set-up counts from here

import argparse
import contextlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
# fixed and inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".jax_cache"
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import flops  # noqa: E402
import traffic  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(bench: dict, workload: str) -> dict:
    """Everything one cell needs, from ``BENCHMARK.json`` and the files it
    names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload] if m["moves"] in reported else [])]
    return {
        "name": workload,
        "chips": w["chips"],
        "model": load_json(ROOT / config["file"]),
        "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        "checks": load_json(HERE / "checks" / f"{workload}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


class Clock:
    def __init__(self):
        self.t0 = time.perf_counter()
        self.seconds = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


class Cell:
    """What a runner gets: the cell's data, the seed, the devices, host
    spans, the measured window and the peak-memory reading."""

    def __init__(self, spec: dict, seed: int, seconds: float, trace: bool,
                 devices, variants=()):
        import jax

        self.name, self.chips = spec["name"], spec["chips"]
        self.model, self.traffic, self.checks = spec["model"], spec["traffic"], spec["checks"]
        self.seed, self.seed32 = seed, traffic.seed32(seed)
        self.key = jax.random.PRNGKey(self.seed32)
        self.seconds, self.trace = seconds, trace
        self.devices = devices[: self.chips]
        self.variants = tuple(variants)
        self.setup_s = None
        self.trace_dir = None
        self.compiles_in_window = 0
        self._in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self._in_window and event == COMPILE_EVENT:
            self.compiles_in_window += 1

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax

        self.setup_s = time.perf_counter() - T0
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        try:
            with jax.profiler.TraceAnnotation("window"):
                clock = Clock()
                self._in_window = True
                yield clock
                clock.seconds = clock.elapsed()
                self._in_window = False
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices]
        return int(max(peaks))


class Reading:
    """What a per-layer reader gets (``metrics/<name>.py``: ``read(run)``
    returns a number, or None where it finds nothing to read)."""

    def __init__(self, cell: Cell, outcome, reduced, peak: dict):
        self.model, self.traffic, self.chips = cell.model, cell.traffic, cell.chips
        self.counts = outcome.counts
        self.trace = reduced
        self.peak = peak
        self.flops = flops
        self.notes: list[str] = []

    def note(self, text: str) -> None:
        self.notes.append(text)


def device_peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def prepare_env() -> None:
    """Before JAX starts: the program's compile cache is the benchmark's,
    and libtpu writes no log outside the checkout."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, variants=()):
    """Run one cell; returns the exit code, the result line (None where
    there is none) and the runner's outcome."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, decode's too, which compiles in under a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            print(f"no TPU: the first device is {devices[0].platform!r}", file=sys.stderr)
            return 2, None, None
        if len(devices) < spec["chips"]:
            print(f"the cell needs {spec['chips']} chips, JAX finds {len(devices)}",
                  file=sys.stderr)
            return 2, None, None
    kind = devices[0].device_kind
    if require_tpu:
        device_peaks(kind)  # a device missing from the table is an error

    cell = Cell(spec, seed, seconds, trace, devices, variants)
    runner = load_module(HERE / "runners" / f"{spec['traffic']['runner']}.py")
    outcome = runner.run(cell)
    correct, checks = compare.judge(outcome.numbers, spec["checks"]["limits"])
    correct = correct and outcome.failed == 0 and outcome.attempted > 0

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    line = {"correct": correct, "attempted": outcome.attempted, "failed": outcome.failed,
            "metrics": None, "device": device}
    notes = [f"setup_s={cell.setup_s} window_s={outcome.counts['window_s']} "
             f"compiles_in_window={cell.compiles_in_window}",
             "counts " + json.dumps(outcome.counts)]
    if trace:
        import devtrace

        reduced = devtrace.reduce_dir(cell.trace_dir)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        reading = Reading(cell, outcome, reduced, device_peaks(kind))
        metrics = {}
        for m in spec["per_layer"]:
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        notes += reading.notes
        device["busy_s"], device["window_s"] = reduced.busy_s, reduced.window_s
        line["metrics"] = metrics
        line["breakdown"] = reduced.breakdown()
    else:
        values = dict(outcome.metrics, setup_s=cell.setup_s)
        line["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                           for m in spec["end_to_end"]}
    if outcome.detail.get("program"):
        notes.append("detail " + json.dumps(finite(outcome.detail["program"])))
    if outcome.variants:
        notes.append("variants " + json.dumps(outcome.variants))
    line["checks"] = checks
    for text in notes:
        print(text, file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0, line, outcome


def finite(x):
    """JSON has no inf or nan: such a number is written as a string."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    prepare_env()
    spec = resolve(load_json(ROOT / "BENCHMARK.json"), args.workload)
    code, line, _ = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    if line is not None:
        print(json.dumps(finite(line)), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
