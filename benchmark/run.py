"""One run of one benchmark cell:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it checks the manifest, looks for the chips the
cell asks for (no chip, no run: there is no CPU fallback), loads, warms,
measures for ``--seconds`` and prints one JSON object as its last line.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a run with a profiler trace over part of the window.
Everything that belongs to one cell, configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``; see
``benchmark/README.md``.
"""

import time

T_START = time.perf_counter()

import argparse    # noqa: E402
import importlib   # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import shutil      # noqa: E402
import sys         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf   # noqa: E402
from benchmark.harness import peaks            # noqa: E402


def say(what, facts):
    print(json.dumps({"info": what, **facts}, default=str), flush=True)


def find_chips(want):
    """The device facts of this machine, or SystemExit(2) when jax finds no
    TPU, fewer chips than the cell asks for, or a kind without published
    peaks."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < want:
        sys.stderr.write(
            f"benchmark: the cell needs {want} TPU chip(s); jax found "
            f"{len(devs)} x {devs[0].platform}:{devs[0].device_kind}. "
            f"Nothing was run.\n")
        raise SystemExit(2)
    try:
        peaks.peaks_for(devs[0].device_kind)
    except ValueError as e:
        sys.stderr.write(f"benchmark: {e}\n")
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def open_cell(name):
    """What every entry point does first: check the manifest, load the
    cell's files, find the program and the chips, place the compile cache.
    Returns (manifest, its entry of the cell, cell file, configuration,
    traffic mix, runner module, device facts)."""
    manifest = mf.load()
    mf.check(manifest)
    entry, cell, cfg, mix = mf.load_cell(manifest, name)
    import paddle_tpu  # noqa: F401  (fails here where the program is absent)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    device = find_chips(entry["chips"])
    say("start", {"cell": entry["name"], "device": device,
                  "compile_cache": enable_compile_cache()})
    runner = importlib.import_module("benchmark.harness." + cell["runner"])
    return manifest, entry, cell, cfg, mix, runner, device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest, entry, cell, cfg, mix, runner, device = open_cell(
        args.workload)

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(mf.BENCH, "_out", "trace", entry["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = runner.run(cell, cfg, mix, seed=args.seed, seconds=args.seconds,
                     t_start=T_START, trace_dir=trace_dir, say=say)
    run["peaks"] = peaks.peaks_for(device["kind"])
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": {}, "device": device}

    if args.trace:
        from benchmark.harness import trace_reduce
        run["trace"] = trace_reduce.reduce_file(
            trace_reduce.find_xplane(trace_dir))
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
        for m in manifest["per_layer"]:
            if not mf.reports(m, entry["name"]):
                continue
            value = mf.load_metric(m["name"]).read(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        for m in manifest["end_to_end"]:
            if mf.reports(m, entry["name"]):
                result["metrics"][m["name"]] = {
                    "value": run["end_to_end"][m["name"]], "unit": m["unit"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
