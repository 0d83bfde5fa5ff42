"""What every cell shares: finding its files by name, the look for the
chip, the clock, spans, the profiler's stretch, and the result line."""
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(SystemExit):
    pass


def resolve(spec):
    """"pkg.mod:attr" -> the object."""
    mod, _, attr = spec.partition(":")
    obj = importlib.import_module(mod)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Files:
    """Looks a cell's pieces up by name in the benchmark's directories
    (`data_dirs`, first hit wins): configs/, traffic/, cells/, metrics/ hold
    data, generators/ and readers/ hold code."""

    def __init__(self, spec_path=None, data_dirs=None):
        self.spec_path = spec_path or os.path.join(ROOT, "BENCHMARK.json")
        self.data_dirs = [os.path.abspath(d) for d in (data_dirs or [HERE])]
        self.spec = load_json(self.spec_path)

    def find(self, kind, filename):
        for d in self.data_dirs:
            path = os.path.join(d, kind, filename)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"{kind}/{filename} is in none of {self.data_dirs}")

    def data(self, kind, name):
        return load_json(self.find(kind, name + ".json"))

    def code(self, kind, name):
        path = self.find(kind, name + ".py")
        if os.path.dirname(os.path.dirname(path)) == HERE:
            return importlib.import_module(f"benchmark.{kind}.{name}")
        mod_spec = importlib.util.spec_from_file_location(
            f"benchmark_extra_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod

    def cell(self, name):
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.spec_path}; it has "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name):
        for c in self.spec["configs"]:
            if c["name"] == name:
                path = c["file"]
                if not os.path.isabs(path):
                    path = os.path.join(
                        os.path.dirname(self.spec_path), path)
                return load_json(path)
        raise KeyError(f"no config {name!r}")

    def metrics_of(self, cell_name, group):
        """Names of the `group` ("end_to_end" | "per_layer") metrics the
        cell reports: those without a `workloads` key, or that list it."""
        return [m["name"] for m in self.spec[group]
                if "workloads" not in m or cell_name in m["workloads"]]


def require_chips(chips, need_tpu=True):
    """The devices the cell runs on, or no result: with no TPU, or fewer
    chips than the cell asks for, the run ends here with a non-zero code.
    `need_tpu=False` is for the tests' CPU rehearsals and is reachable only
    as an argument of `run.main`, never from the command line."""
    import jax
    devices = jax.devices()
    if need_tpu and devices[0].platform != "tpu":
        raise NoChip(f"benchmark needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} x {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"benchmark needs {chips} chip(s); JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peaks_for(device_kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(it has {sorted(table['devices'])}); add it with its source")
    return table["devices"][device_kind]


def place_cache(root=ROOT):
    """JAX's persistent cache at a fixed path inside the checkout, unless
    JAX_COMPILATION_CACHE_DIR places it (the program store then leaves it
    alone)."""
    from paddle_tpu import programs
    return programs.enable(os.path.join(root, ".jax_cache"))


class Run:
    """One run's record: what the generator fills and the readers read."""

    def __init__(self, files, cell, args, t_start, devices):
        self.files = files
        self.cell = cell
        self.args = args
        self.seed = args.seed
        self.seconds = float(args.seconds if args.seconds is not None
                             else files.spec["run_seconds"])
        self.trace = bool(args.trace)
        self.t_start = t_start
        self.devices = devices
        self.config = files.config(cell["config"])
        self.traffic = files.data("traffic", cell["traffic"])
        cell_file = files.data("cells", cell["name"])
        self.limits = cell_file["limits"]
        self.not_compared = cell_file.get("not_compared", {})
        self.peaks = None
        self.spans = []            # (name, t0, t1) on time.perf_counter
        self.counters = {}
        self.requests = []         # serving: per-request records
        self.engine_steps = []     # serving: per engine.step() records
        self.window = None         # (t0, t1) of the measured window
        self.traced = None         # {"t0", "t1", ...} of the profiled stretch
        self.trace_summary = None
        self.setup_s = None
        self.extra = {}
        self._annotate = False
        self._mark = None
        self.trace_dir = os.path.join(
            os.path.dirname(files.spec_path), ".bench_trace",
            cell["name"])

    def lap(self, name):
        """Set-up's parts, in seconds since the process started."""
        self.extra.setdefault("setup_laps", {})[name] = round(
            time.perf_counter() - self.t_start, 3)

    @contextlib.contextmanager
    def span(self, name):
        """A span of the benchmark's own around a call into the program; in
        the profiled stretch it is written into the profiler's trace too."""
        ann = None
        if self._annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((name, t0, t1))

    def start_profile(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        # the Python tracer would write every function call of the host
        # loop (34 MB for 20 steps); annotations and device events stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._annotate = True
        # the traced window, marked in the trace itself: the reduction
        # clips everything to it
        self._mark = jax.profiler.TraceAnnotation("bench_traced_window")
        self._mark.__enter__()
        self.traced = {"t0": time.perf_counter()}

    def end_mark(self):
        """Closes the traced window.  Cheap, so it is called inside the
        run; `stop_profile`, which writes the trace out and can take many
        seconds, is called once nothing is waiting on the host."""
        t1 = time.perf_counter()
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None
            self._annotate = False
            self.traced["t1"] = t1
        return t1

    def stop_profile(self):
        import jax
        self.end_mark()
        jax.profiler.stop_trace()


def memory_peak_bytes(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_block(devices, run):
    d0 = devices[0]
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices),
           "memory_peak_bytes": run.extra.get("memory_peak_bytes", 0)}
    if run.trace_summary is not None:
        out["busy_s"] = run.trace_summary["busy_s"]
        out["window_s"] = run.trace_summary["window_s"]
    return out


def emit(result):
    """The result: the last line of standard output."""
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
