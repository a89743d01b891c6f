"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, device start, inputs, one warm call) is timed from the
start of the process.  The window then runs the cell's loop in a closed
loop, one call after another, until ``--seconds`` have passed; the call
under way when they pass is finished and counted, and the window's time
runs to its end.  With ``--trace 1`` the window holds the traffic file's
``trace_calls`` calls at most, under the profiler, and the run reports the
per-layer metrics instead of the end-to-end ones.

After the window the device's peak memory is read, the program's state is
dropped, and a sample of the window's calls is compared with the plain
reference; each number compared is printed beside its limit as the last
lines of standard error and under ``"checked"``, the last key of the
result line.  The result is the last line of standard output.

Exits with 2, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> SimpleNamespace:
    """A cell of ``BENCHMARK.json`` with its configuration, traffic mix,
    limits and the metrics it reports."""
    bm = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = next(c for c in bm["configs"] if c["name"] == cell["config"])
    mine = lambda ms: [m for m in ms if name in m.get("workloads", [name])]
    return SimpleNamespace(
        name=name, chips=cell["chips"], end_to_end=mine(bm["end_to_end"]),
        per_layer=mine(bm["per_layer"]),
        cfg=_json(os.path.join(ROOT, conf["file"])),
        traffic=_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")),
        limits=_json(os.path.join(BENCH, "limits", name + ".json"))["limits"])


def metric_reader(name: str):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, t_start: float = T_START,
             control: bool = False) -> dict:
    """Set up, measure and check one run of ``cell``; the result object.
    With ``control`` the check runs a second time with the control in the
    program's place, and its numbers go under ``log["control"]``."""
    import jax

    from bench import compare, loops, roofline
    from bench import trace as tr

    devs = _devices(cell.chips, require_chip)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    drv = loops.load(cell.traffic["entry"])(cell.cfg, cell.traffic, seed)
    drv.setup()
    setup_s = time.perf_counter() - t_start

    current: dict = {}

    def on_duration(event, duration, **_):
        if event in COMPILE_EVENTS and "compile_s" in current:
            current["compile_s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    max_calls = cell.traffic["trace_calls"] if trace else None
    calls, failed, errors = [], 0, []
    t0 = time.perf_counter()
    try:
        i = 0
        while (time.perf_counter() - t0 < seconds
               and (max_calls is None or i < max_calls)):
            current.clear()
            current["compile_s"] = 0.0
            ts = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(drv.span):
                    rec = drv.call(i)
                rec["latency_s"] = time.perf_counter() - ts
                rec["compile_s"] = current["compile_s"]
                calls.append(rec)
            except Exception:                     # a failed call is counted
                failed += 1
                errors.append(traceback.format_exc(limit=4))
            i += 1
        elapsed = time.perf_counter() - t0
    finally:
        current.clear()
        jax.monitoring.unregister_event_duration_listener(on_duration)
        if trace:
            jax.profiler.stop_trace()
    attempted = i

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    reduced = None
    if trace:
        try:
            reduced = tr.reduce(tr.load(trace_dir, {drv.span}))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    drv.release()
    t_check = time.perf_counter()
    numbers = drv.check()
    check_s = time.perf_counter() - t_check
    correct, table = compare.verdict(numbers, cell.limits)
    control_numbers = drv.check(control=True) if control else None
    correct = correct and failed == 0 and attempted > 0 and drv.checked > 0

    run = SimpleNamespace(calls=calls, elapsed_s=elapsed, setup_s=setup_s,
                          trace=reduced, traffic=cell.traffic, cfg=cell.cfg,
                          peaks=roofline.peaks(devs[0].device_kind)
                          if require_chip else None, notes={})
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["log"] = {"window_s": elapsed, "check_s": check_s,
                     "checked": drv.checked, "notes": run.notes,
                     "latency_s": [c["latency_s"] for c in calls],
                     "compile_s": [c["compile_s"] for c in calls],
                     "errors": errors[:2]}
    if control:
        result["log"]["control"] = control_numbers
    result["checked"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    for err in result["log"]["errors"]:
        print(err, file=sys.stderr)
    for name, v in result["checked"].items():
        print(f"checked {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
