"""One run of one cell: set-up, a measured window, the check, one line.

``run.py`` is the command; this module is what it runs. Everything that
belongs to one cell is found by name from ``BENCHMARK.json``:

* the workload entry names a configuration and a traffic mix;
* ``configs/<config>.json`` holds the configuration's sizes, its
  pipeline settings, its bound and the limits of its check;
* ``traffic/<mix>.json`` holds the mix's parameters and names the
  generic driver (``drivers/<driver>.py``) that runs it;
* each per-layer metric is read by ``layers/<metric>.py``.

A driver module has three functions. ``setup(ctx)`` does the cell's
set-up and returns its state; ``window(ctx, state)`` runs the measured
window and returns a dict with ``attempted``, ``failed`` and
``metrics`` (end-to-end values by name); ``check(ctx, state, result)``
compares what the window produced with the reference and returns the
numbers compared, each with its limit (``reference.make_check``).

A layer module has ``read(ctx)``, which returns the metric's value or
None when the run holds nothing to read it from.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class Refused(Exception):
    """The run cannot be made here; nothing was measured."""


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration and mix."""

    def __init__(self, spec: Path, name: str, bench_dir: Path = BENCH):
        self.dir = Path(bench_dir)
        self.spec = load_json(spec)
        found = [w for w in self.spec["workloads"] if w["name"] == name]
        if not found:
            raise Refused(f"no workload {name!r} in BENCHMARK.json")
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        self.config = load_json(self.dir / "configs"
                                / f"{self.workload['config']}.json")
        self.traffic = load_json(self.dir / "traffic"
                                 / f"{self.workload['traffic']}.json")
        self.driver_path = (self.dir / "drivers"
                            / f"{self.traffic['driver']}.py")

    def _applies(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self) -> list[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"] if self._applies(m)]

    def per_layer(self) -> list[dict]:
        """The per-layer metrics read in this cell's traced run: those
        that list it, and those without a list whose moved metric the
        cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.spec["per_layer"]:
            cells = m.get("workloads")
            if (cells is not None and self.name in cells) or (
                    cells is None and m["moves"] in e2e):
                out.append(m)
        return out

    def layer_path(self, metric: str) -> Path:
        return self.dir / "layers" / f"{metric}.py"


class Ctx:
    """What a driver and the layer readers see of one run."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        from bench import counts

        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.shapes = counts.shapes_from_config(cell.config)
        self.field = None  # the original (S, T, H, W) field
        self.spans: dict[str, list[float]] = {}
        self.counters: dict[str, float] = {}
        self.units = 0  # jobs, decodes or queries done in the window
        self.window_s = 0.0
        self.trace = None  # tracefile.Trace of a traced run
        self.peak: dict | None = None  # peaks.json entry of the device
        # the control in the program's place: answers checked in bfloat16
        self.control = False
        # False: set-up warms nothing up (readings that time nothing)
        self.warm = True

    @property
    def pipeline_seed(self) -> int:
        """The seed of the model's initialisation and batch draws."""
        return self.seed % (2**31 - 1)

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark host span: timed on the host clock and written
        into the profiler's trace as ``bench.<name>``."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def note(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def pipeline_config(self):
        """The program's ``PipelineConfig`` for this configuration."""
        from repro.core.blocking import BlockGeometry
        from repro.core.pipeline import PipelineConfig

        p = self.config["pipeline"]
        return PipelineConfig(
            geometry=BlockGeometry(*p["geometry"]),
            latent=int(p["latent"]),
            conv_channels=tuple(p["conv_channels"]),
            use_correction=bool(p["use_correction"]),
            ae_steps=int(p["ae_steps"]),
            corr_steps=int(p["corr_steps"]),
            batch_size=int(p["batch_size"]),
            lr=float(p["lr"]),
            seed=self.pipeline_seed,
            param_dtype_bytes=int(p["param_dtype_bytes"]),
            family=p["family"],
        )


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads (JAX's own
    monitoring events). JAX times a program loaded from the persistent
    cache under the same event as a compilation, so a load is counted
    once as a load and the rest as compilations."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.events = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == self.COMPILE:
            self.events += 1

    def _event(self, event, **kw):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.events - self.cache_hits


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, require_tpu: bool = True,
        bench_dir: Path = BENCH, spec: Path | None = None,
        control: bool = False, warm: bool = True,
        persist: bool = True) -> dict:
    """Make one run; returns the result line's object.

    ``require_tpu=False`` skips the look for a chip (for tests on the
    CPU); the run is otherwise the same. ``spec`` and ``bench_dir`` name
    another ``BENCHMARK.json`` and benchmark directory (tests add cells
    there). ``control=True`` checks the window's answers carried in
    bfloat16 in place of the program's, and ``warm=False`` skips warming
    up in set-up (both for ``control.py``'s readings, which time
    nothing). ``persist=False`` leaves JAX's persistent compilation cache
    as it is (tests).
    """
    root = Path(root)
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"no program under {root / 'src'}: nothing to run")
    cell = Cell(spec or root / "BENCHMARK.json", workload, bench_dir)
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))

    import jax

    if require_tpu:
        if jax.default_backend() != "tpu":
            raise Refused(f"JAX found no TPU (backend "
                          f"{jax.default_backend()!r}); nothing was run")
    devices = jax.devices()[: cell.chips]
    if len(devices) < cell.chips:
        raise Refused(f"{cell.chips} chips wanted, {len(devices)} found")

    from repro import compile_cache

    from bench import roofline, surrogate, tracefile

    cache_dir = None
    if persist:
        cache_dir = compile_cache.configure(root)
        # every program goes to the persistent cache, however quick its
        # compile, so that only a cell's first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()

    ctx = Ctx(cell, seed, seconds)
    ctx.control = bool(control)
    ctx.warm = bool(warm)
    dev = device_info(devices)
    ctx.peak = (roofline.peaks(dev["kind"]) if require_tpu
                else {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    ctx.note(f"{workload}: {dev['platform']} {dev['kind']} x "
             f"{dev['count']}; seed {seed}; compile cache {cache_dir}")

    ctx.field = surrogate.field_for(cell.config["data"], seed,
                                    ctx.shapes.block)
    driver = load_module(cell.driver_path)
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    ctx.note(f"set-up {setup_s:.3f} s: {counter.compiles} compiles, "
             f"{counter.cache_hits} persistent-cache loads")

    compiles0, hits0 = counter.compiles, counter.cache_hits
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    try:
        t0 = time.perf_counter()
        with ctx.span("window"):
            result = driver.window(ctx, state)
        ctx.window_s = time.perf_counter() - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    ctx.note(f"window {ctx.window_s:.3f} s, {ctx.units} done: "
             f"{counter.compiles - compiles0} compiles and "
             f"{counter.cache_hits - hits0} persistent-cache loads inside")
    dev["memory_peak_bytes"] = memory_peak(devices)

    out = {"correct": None, "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    if trace:
        import shutil

        ctx.trace = tracefile.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = tracefile.busy_s(ctx.trace)
        dev["window_s"] = tracefile.window_s(ctx.trace)
        metrics = {}
        for m in cell.per_layer():
            value = load_module(cell.layer_path(m["name"])).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        metrics = {}
        for m in cell.end_to_end():
            value = (setup_s if m["name"] == "setup_s"
                     else result["metrics"].get(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

    checks = driver.check(ctx, state, result)
    out["correct"] = bool(checks) and all(c["ok"] for c in checks.values())
    out["metrics"] = metrics
    out["device"] = dev
    if trace:
        out["breakdown"] = tracefile.breakdown(ctx.trace)
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    from bench import reference

    print(reference.checks_line(checks), file=sys.stderr, flush=True)
    return out
