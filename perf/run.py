#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in `BENCHMARK.json`, its configuration in
`perf/configs/<config>.json`, its traffic in `perf/traffic/<traffic>.json`,
the driver the traffic file names in `perf/drivers/<driver>.py`, the family
and the plain reference the configuration names (`perf/families/<family>.py`,
`perf/reference/<reference>.py`) and, for each per-layer metric the
manifest lists for the cell, its reader in `perf/layer_metrics/<metric>.py` —
all by name, so a later PR adds a cell as one entry plus files of its own.

The last line of standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`). Everything else — each number compared beside its limit, the
itemised set-up, where the series went — is on earlier lines or in
`perf_out/<workload>/`. Exit 2 and no result where JAX finds no TPU, fewer
chips than the cell asks for, or a chip whose peaks `perf/lib/peaks.py` does
not hold; exit 3 where the program itself is missing.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # the process's first instant, for setup_s

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def say(*a) -> None:
    print(*a, flush=True)


def load_cell(name: str) -> tuple:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"perf/run.py: no workload {name!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "perf", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return manifest, cell, config, traffic


def metrics_for(manifest: dict, cell: dict, kind: str, reported) -> list:
    """The manifest's metrics of `kind` that this cell reports: those that
    list it under `workloads`, and those without the key whose `moves`
    (per-layer) this cell reports or (end-to-end) that hold everywhere."""
    out = []
    for m in manifest[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def open_cell(name: str):
    """The cell's files and the chips to run it on, or an exit code: 2
    where JAX finds no TPU, too few chips or a chip without known peaks, 3
    where the program is missing."""
    manifest, cell, config, traffic = load_cell(name)
    # one fixed cache directory inside the checkout, for the benchmark and
    # for the program (which takes this variable before its own default)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_compile_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"perf/run.py: {name} needs {cell['chips']} TPU chip(s); jax "
              f"reports {len(devices)} x {dev.platform!r} "
              f"({dev.device_kind}). Nothing was measured.", file=sys.stderr)
        return 2
    from perf.lib import peaks

    try:
        chip_peaks = peaks.lookup(dev.device_kind)
    except peaks.UnknownDevice as e:
        print(f"perf/run.py: {e.args[0]}. Nothing was measured.",
              file=sys.stderr)
        return 2
    try:
        import ddp_practice_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"perf/run.py: the program is not in this checkout ({e}). "
              "Nothing was measured.", file=sys.stderr)
        return 3
    return manifest, cell, config, traffic, devices[:cell["chips"]], \
        chip_peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    opened = open_cell(args.workload)
    if isinstance(opened, int):
        return opened
    manifest, cell, config, traffic, devices, chip_peaks = opened
    line = measure(manifest, cell, config, traffic, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   devices=devices, chip_peaks=chip_peaks)
    say(json.dumps(line))
    return 0


def make_ctx(cell, config, traffic, *, seed, seconds, trace, devices,
             chip_peaks, outroot=None):
    """What a driver is handed: the cell's files, the clocks, the tracer's
    switches and where to write."""
    import jax

    from perf.lib import setup_clock

    outdir = os.path.join(outroot or os.path.join(ROOT, "perf_out"),
                          cell["name"], f"seed{seed}_trace{int(trace)}")
    os.makedirs(outdir, exist_ok=True)
    clock = setup_clock.SetupClock(T_START)
    compiles = setup_clock.CompileWatch()
    clock.mark("imports, device found, manifest read")
    return types.SimpleNamespace(
        root=ROOT, workload=cell, config=config, traffic=traffic,
        seed=seed, seconds=seconds, trace=trace,
        outdir=outdir, data_dir=os.path.join(outdir, "data"),
        trace_dir=os.path.join(outdir, "xplane"), chips=cell["chips"],
        peaks=chip_peaks, t_start=T_START, clock=clock, compiles=compiles,
        start_trace=start_trace, stop_trace=jax.profiler.stop_trace,
        memory_peak=lambda: memory_peak(devices),
        program_spans=program_spans,
    )


def measure(manifest, cell, config, traffic, *, seed, seconds, trace,
            devices, chip_peaks, outroot=None) -> dict:
    """Everything after the look for a chip: drive the cell's driver on
    `devices` and build the result line. (The tests call this on the CPU
    with toy sizes to see `correct` decided; a number from such a call is
    never a device metric.)"""
    ctx = make_ctx(cell, config, traffic, seed=seed, seconds=seconds,
                   trace=trace, devices=devices, chip_peaks=chip_peaks,
                   outroot=outroot)
    driver = importlib.import_module(f"perf.drivers.{traffic['driver']}")
    result = driver.run(ctx)
    return report(manifest, cell, ctx, result, devices[0])


def start_trace(trace_dir: str) -> None:
    """Device ops and TraceAnnotations, no Python call stacks: the host
    tracer at its lightest that still records annotations."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def memory_peak(devices) -> int:
    """The peak on the fullest chip as JAX's allocator reports it: the most
    bytes ever held by arrays (`peak_bytes_in_use`) plus the most ever
    reserved for running programs' temporaries (`peak_bytes_reserved`; on
    this runtime a step's activations live there and never show in the
    first number)."""
    def one(d):
        stats = d.memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) \
            + stats.get("peak_bytes_reserved", 0)

    return int(max(one(d) for d in devices))


def program_spans(recorder) -> list:
    """[name, t0, t1] of the spans in a `utils/trace.py` TraceRecorder of
    the program (monotonic seconds), or [] without one."""
    if recorder is None:
        return []
    out, open_ = [], {}
    for ev in recorder.to_chrome_trace().get("traceEvents", []):
        ph, key = ev.get("ph"), (ev.get("pid"), ev.get("tid"),
                                 ev.get("name"))
        if ph == "X":
            out.append([ev["name"], ev["ts"] * 1e-6,
                        (ev["ts"] + ev.get("dur", 0)) * 1e-6])
        elif ph == "B":
            open_.setdefault(key, []).append(ev["ts"] * 1e-6)
        elif ph == "E" and open_.get(key):
            out.append([ev["name"], open_[key].pop(), ev["ts"] * 1e-6])
    return sorted(out, key=lambda s: s[1])


def dump_trace_head(trace, outdir: str, n: int = 400) -> None:
    """The start of every line of the loaded trace and a count of names,
    small enough to bring back from the chip and read by hand."""
    if trace is None:
        return
    head = []
    for plane in trace["planes"]:
        for ln in plane["lines"]:
            names = {}
            for e in ln["events"]:
                names[e[0][:80]] = names.get(e[0][:80], 0) + 1
            head.append({"plane": plane["name"], "line": ln["name"],
                         "events": len(ln["events"]),
                         "first": ln["events"][:n],
                         "names": sorted(names.items(),
                                         key=lambda kv: -kv[1])[:60]})
    with open(os.path.join(outdir, "trace_head.json"), "w") as f:
        json.dump(head, f)


def report(manifest, cell, ctx, result, dev) -> dict:
    checks = result["checks"]
    for name, secs in ctx.clock.items:
        say(f"setup {secs:8.3f} s  {name}")
    say(f"setup {result['metrics']['setup_s']:8.3f} s  TOTAL (process "
        "start to the window's first instant)")
    say(f"reference {result['series']['reference_s']:6.3f} s  after the "
        "window, not in setup_s")
    for line in checks.lines():
        say(line)
    e2e = metrics_for(manifest, cell, "end_to_end", None)
    reported = {m["name"] for m in e2e if m["name"] in result["metrics"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": checks.correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    units = {m["name"]: m["unit"]
             for k in ("end_to_end", "per_layer") for m in manifest[k]}
    if not ctx.trace:
        for m in e2e:
            if m["name"] in result["metrics"]:
                line["metrics"][m["name"]] = {
                    "value": result["metrics"][m["name"]],
                    "unit": units[m["name"]]}
    else:
        from perf.lib import readers

        obs = result["obs"]
        obs["config"], obs["peaks"] = ctx.config, ctx.peaks
        dump_trace_head(obs.get("trace"), ctx.outdir)
        device.update(readers.device_busy(obs))
        line["breakdown"] = readers.breakdown(obs)
        for m in metrics_for(manifest, cell, "per_layer", reported):
            reader = importlib.import_module(
                f"perf.layer_metrics.{m['name']}")
            value = reader.read(obs)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": units[m["name"]]}
    series = dict(result["series"], metrics=result["metrics"],
                  memory_stats=dev.memory_stats(),
                  setup_items=ctx.clock.items,
                  checks=checks.rows, seed=ctx.seed, seconds=ctx.seconds)
    with open(os.path.join(ctx.outdir, "series.json"), "w") as f:
        json.dump(series, f)
    say(f"series and itemised set-up: {ctx.outdir}/series.json")
    return line


if __name__ == "__main__":
    sys.exit(main())
