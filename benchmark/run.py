"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

It runs ONE cell of ``BENCHMARK.json`` on the machine it is started on
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``).  With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is in a file of its own that this program finds by the
name in ``BENCHMARK.json`` (see ``benchmark/README.md``); nothing here
names a cell, a configuration or a metric.

It fails, and prints no result, when JAX's platform is not ``tpu``, when
there are fewer chips than the cell asks for, when the ``device_kind`` is
not in ``peaks.json``, and when the program under test is not in the
checkout.  There is no CPU fallback and no option that makes one.
"""

import time

T_START = time.perf_counter()       # the process's first instant

import argparse                     # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)

#: a cell has to use a quarter of a chip's memory: the driver refuses a
#: smaller one as no deployment, and so does this program, with the number
MEMORY_FLOOR_SHARE = 0.25


class Refused(Exception):
    """The run cannot be made here; no result line is printed."""


def load_module(path):
    """A Python file of the benchmark, by path (file names are metric and
    driver names and may hold dots, so they are not importable by name)."""
    name = "benchmark_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class CompileMonitor:
    """Seconds JAX spent tracing, lowering and compiling, and its
    persistent-cache traffic, from JAX's own monitoring events (copied
    from ``chip_smoke.py``)."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.compile_seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, seconds, **_):
        if name in self._DURATIONS:
            self.compile_seconds += seconds
        if name == self._DURATIONS[2]:
            self.backend_compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Run:
    """One run of one cell: what the command line, ``BENCHMARK.json`` and
    the cell's files say, handed to the driver and to the readers."""

    backend = "tpu"     # a rehearsal test replaces it; no option does

    def __init__(self, manifest, workload, seed, seconds, trace):
        self.manifest = manifest
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise Refused("no workload %r in BENCHMARK.json (have: %s)"
                          % (workload, ", ".join(sorted(cells))))
        self.cell = cells[workload]
        self.chips = int(self.cell["chips"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        entry = next(c for c in manifest["configs"]
                     if c["name"] == self.cell["config"])
        self.config = load_json(REPO, entry["file"])
        self.config_dir = os.path.dirname(os.path.join(REPO, entry["file"]))
        self.mix = load_json(BENCH, "traffic", self.cell["traffic"] + ".json")
        self.generator = load_module(os.path.join(
            BENCH, "generators", self.mix["generator"] + ".py"))
        self.tracing = load_module(os.path.join(BENCH, "tracing.py"))
        # set-up is counted from here; check_device moves it to the
        # instant the chips are ready (a rehearsal has no check_device)
        self.t_start = time.perf_counter()
        # filled by check_device and by the driver
        self.devices = []
        self.peaks = None
        self.monitor = None
        self.counters = {}
        self.reduced = None

    def note(self, phase):
        """One line per phase with the seconds since the process started:
        where a run's time goes, set-up above all, for whoever shortens
        it next (earlier lines of standard output are free)."""
        print("benchmark: %7.2f s  %s"
              % (time.perf_counter() - T_START, phase), flush=True)

    def config_module(self, name):
        """``reference``, ``work`` or a builder of the configuration."""
        return load_module(os.path.join(self.config_dir, name + ".py"))

    def memory_peak(self):
        """Peak bytes in use, so far, on the fullest device of the cell."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def metrics_of(self, group):
        """The manifest's metrics of ``group`` that this cell reports."""
        return [m for m in self.manifest[group]
                if self.cell["name"] in m.get("workloads",
                                              [self.cell["name"]])]


def check_device(run):
    """The chips the cell asks for, on a TPU whose peaks are known.  The
    set-up clock starts when this returns: before it lie the
    interpreter's and JAX's start and the TPU runtime's, 10 to 16 s on
    one chip and 20 to 22 s on four for the same code (PERF.md, PR 23),
    which no change to the program moves and which moved ``setup_s`` by
    19 % between two sets of the same code; after it lie the program's
    imports, data, weights, compile or cache load, and warm-up."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    peaks = load_json(BENCH, "peaks.json")["devices"]
    if devices[0].platform != "tpu":
        raise Refused("JAX's platform is %r, not 'tpu': the benchmark "
                      "measures the chip and nothing else"
                      % devices[0].platform)
    if len(devices) < run.chips:
        raise Refused("the cell asks for %d chip(s), JAX sees %d"
                      % (run.chips, len(devices)))
    if kind not in peaks:
        raise Refused("device_kind %r is not in benchmark/peaks.json; add "
                      "its peaks with their source, do not guess" % kind)
    run.devices = devices[:run.chips]
    run.peaks = peaks[kind]
    run.t_start = time.perf_counter()
    run.counters["runtime_start_s"] = run.t_start - T_START
    run.note("chips ready: set-up is counted from here")


def result_line(run, outcome):
    """The contract's result object from a driver's ``outcome``."""
    import jax
    all_devices = jax.devices()
    # a driver reads the peak at its window's close where its comparison
    # with the reference could pass it: the cell is sized by the system
    peak = outcome.get("memory_peak_bytes", run.memory_peak())
    device = {"platform": all_devices[0].platform,
              "kind": all_devices[0].device_kind,
              "count": len(all_devices), "memory_peak_bytes": peak}
    metrics = {}
    if run.trace:
        reduced = run.reduced
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.window_s
        for metric in run.metrics_of("per_layer"):
            reader = load_module(os.path.join(
                BENCH, "layer_metrics", metric["name"] + ".py"))
            value = reader.read(run)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
    else:
        for metric in run.metrics_of("end_to_end"):
            value = outcome["end_to_end"].get(metric["name"])
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
    checks = outcome["checks"]      # {name: (ok, detail)}: all decide
    line = {"correct": all(ok for ok, _ in checks.values()),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"]),
            "metrics": metrics, "device": device}
    if run.trace:
        line["breakdown"] = run.reduced.breakdown()
    # beyond the contract's keys, for whoever reads a run by hand
    line["checks"] = {name: {"ok": bool(ok), "detail": detail}
                      for name, (ok, detail) in checks.items()}
    line["counters"] = run.counters
    return line


def execute(run):
    """Drive the cell and return the result object (the part a rehearsal
    test calls, after replacing ``backend`` and ``check_device``)."""
    run.monitor = CompileMonitor()
    driver = load_module(os.path.join(
        BENCH, "drivers", run.config["driver"] + ".py"))
    outcome = driver.run(run)
    return result_line(run, outcome)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)
    try:
        if not os.path.isdir(os.path.join(REPO, "veles_tpu")):
            raise Refused("no veles_tpu/ beside benchmark/: the program "
                          "under test is not in this checkout")
        sys.path.insert(0, REPO)
        run = Run(load_json(REPO, "BENCHMARK.json"), args.workload,
                  args.seed, args.seconds, args.trace)
        check_device(run)
        line = execute(run)
    except Refused as refusal:
        print("benchmark: %s" % refusal, file=sys.stderr)
        return 2
    peak = line["device"]["memory_peak_bytes"]
    floor = MEMORY_FLOOR_SHARE * run.peaks["hbm_bytes"]
    print("benchmark: %s peak memory %.2f GiB on the fullest chip (floor "
          "%.2f GiB)" % (run.cell["name"], peak / 2 ** 30, floor / 2 ** 30),
          flush=True)
    if peak < floor:
        print("benchmark: cell too small: %d B is under the floor of %d B"
              % (peak, floor), file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
