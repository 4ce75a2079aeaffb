"""Run one benchmark cell once, on the TPU the process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (`workloads` entry of BENCHMARK.json at the checkout's root)
names a configuration (`bench/configs/<name>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`); the mix's `kind` picks a request client
(`clients.py`).  Per-layer metrics are read by `bench/metrics/<name>.py`,
each found by its name in BENCHMARK.json.

A run: set up (inputs from the seed, warm-up requests that bring the
program to steady state, compilation served from the compile cache at
`.bench_jax_cache/` inside the checkout after the first run), then
closed-loop requests until `--seconds` have passed, then every answer
of the window is compared with the plain reference (`reference.py`).
The last stdout line is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (end-to-end with `--trace 0`, per-layer with
`--trace 1`), `device`, with `--trace 1` `breakdown`, and last `checks`,
each compared number beside its limit; the same numbers end stderr.
Off a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: End-to-end metrics from the window's host-clock readings (None when a
#: window completed no request).
E2E = {
    "setup_s": lambda w: w["setup_s"],
    "cases_per_s": lambda w: (w["units"] / w["elapsed_s"]
                              if w["units"] else None),
    "replan_s": lambda w: (w["elapsed_s"] / w["requests"]
                           if w["requests"] else None),
}

#: Program functions wrapped in host spans during a traced run:
#: (module, attribute, span).  Each is looked up by name at call time by
#: its callers, so replacing the module attribute is enough.
SPANS = [
    ("repro.core.engine_jax", "compile_plan", "plan"),
    ("repro.core.engine_jax", "execute_interval", "execute"),
    ("repro.core.engine_jax", "_chunk_inputs", "chunk_inputs"),
    ("repro.core.engine_jax", "_run_chunk", "chunk"),
    ("repro.core.engine_jax", "summarize_plan", "summarize"),
    ("repro.core.optimize", "_cem_search", "cem_search"),
    ("repro.core.optimize", "_grad_search", "grad_search"),
    ("repro.core.optimize", "trace_sweep", "result_sweep"),
]

#: jax.monitoring duration events that make up tracing, lowering and
#: compiling (the last includes loading from the compile cache).
JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Resolving a cell from the manifest
# ---------------------------------------------------------------------------
def resolve(root: str, workload: str, manifest: Optional[dict] = None
            ) -> dict:
    """Everything one cell needs, found by name from BENCHMARK.json (or
    from `manifest`, a parsed one)."""
    if manifest is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        spec = json.load(f)
    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in manifest["per_layer"]
                 if workload in m["workloads"]]
    readers = {m["name"]: os.path.join(root, "bench", "metrics",
                                       m["name"] + ".py")
               for m in per_layer}
    return {"cell": cell, "cfg": cfg, "spec": spec, "e2e": e2e,
            "per_layer": per_layer, "readers": readers, "root": root}


def load_reader(path: str):
    """The `read(run)` function of one per-layer metric file."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Host spans and compile clock
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def host_spans():
    """Wrap the SPANS functions in `jax.profiler.TraceAnnotation`s."""
    import functools

    import jax

    from xplane import SPAN_PREFIX
    saved = []
    try:
        for modname, attr, span in SPANS:
            mod = importlib.import_module(modname)
            fn = getattr(mod, attr)

            def wrapped(*a, _fn=fn, _name=SPAN_PREFIX + span, **kw):
                with jax.profiler.TraceAnnotation(_name):
                    return _fn(*a, **kw)

            functools.update_wrapper(wrapped, fn)
            saved.append((mod, attr, fn))
            setattr(mod, attr, wrapped)
        yield
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while on, and
    the number of programs compiled or loaded from the cache."""

    def __init__(self):
        import jax
        self.on = False
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **_) -> None:
        if self.on and event in JIT_EVENTS:
            self.seconds += duration
            self.compiles += event == JIT_EVENTS[-1]


def configure_process(root: str) -> None:
    """Process settings of a benchmark run, made before JAX loads: JAX's
    persistent compile cache at a fixed path in the checkout, where
    every program is cached, however small or quick to compile; no
    on-disk plan cache; the TPU runtime's logs under the temporary
    directory; the program's sources on the path."""
    path = os.path.join(root, ".bench_jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.pop("CARINA_PLAN_CACHE", None)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def run_cell(c: dict, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True) -> tuple:
    """Set up, measure, check.  Returns the result object (not printed)
    and the run's setup phases and request end times."""
    import jax

    cell, cfg, spec = c["cell"], c["cfg"], c["spec"]
    chips = int(cell["chips"])
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell {cell['name']!r} needs {chips} TPU chip(s); "
                     f"JAX sees {len(devs)} {devs[0].platform} device(s)")
    phases = {"devices": time.perf_counter() - _T0}
    import repro.carina as carina
    from clients import CLIENTS, compile_cache_off
    clock = CompileClock()
    client = CLIENTS[spec["kind"]](carina, cfg, spec, seed, chips)
    client.build()
    phases["inputs"] = time.perf_counter() - _T0
    client.warm_up()
    setup_s = time.perf_counter() - _T0
    phases["warm_up"] = setup_s

    readers = {n: load_reader(p) for n, p in c["readers"].items()} \
        if trace else {}
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    carina.reset_scan_stats()
    clock.on = True
    units = requests = lost = 0
    ends: List[float] = []
    with contextlib.ExitStack() as stack:
        if not spec.get("compile_cache_in_window", True):
            stack.enter_context(compile_cache_off())
        if trace:
            stack.enter_context(host_spans())
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tmp, profiler_options=opts)
            stack.callback(jax.profiler.stop_trace)
        w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:window"):
            k = 1
            while True:
                try:
                    with jax.profiler.TraceAnnotation("bench:request"):
                        units += client.request(k)
                except Exception:
                    # an answer that never comes: count it, end the window
                    traceback.print_exc()
                    lost = client.size()
                    break
                requests += 1
                k += 1
                ends.append(time.perf_counter() - w0)
                if ends[-1] >= seconds:
                    break
        elapsed = time.perf_counter() - w0
    clock.on = False
    stats = carina.scan_stats()
    used = devs[:chips]
    peak_mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)

    window = {"setup_s": setup_s, "elapsed_s": elapsed, "units": units,
              "requests": requests, "jit_s": clock.seconds}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": peak_mem}
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": device}
    if trace:
        from xplane import Trace
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        tr = Trace.from_file(max(paths, key=os.path.getmtime))
        shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_by_span(10)}
        # what a per-layer metric reader may read
        run = types.SimpleNamespace(trace=tr, stats=stats, window=window,
                                    client=client, cfg=cfg, device=device,
                                    chips=chips)
        for m in c["per_layer"]:
            v = readers[m["name"]](run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
    else:
        for m in c["e2e"]:
            v = E2E[m["name"]](window)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}

    checks = client.check()
    failed = int(checks.get("unscored", (0, 0))[0]) + lost
    result["attempted"] = units + lost
    result["failed"] = failed
    result["correct"] = bool(failed == 0 and all(
        v <= lim for v, lim in checks.values()))
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, (v, lim) in checks.items()}
    return result, {"setup phases (s from start)": phases,
                    "requests end at (s into the window)": ends,
                    "programs compiled or loaded in the window":
                        clock.compiles,
                    "compile s in the window": clock.seconds}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    c = resolve(root, args.workload)
    configure_process(root)
    try:
        result, info = run_cell(c, args.seed, args.seconds,
                                bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for what, v in info.items():
        print(f"{what}: {json.dumps(v)}", file=sys.stderr)
    for name, chk in result["checks"].items():
        print(f"check {name} = {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
