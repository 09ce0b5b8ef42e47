"""The control of ``correct``, and the readings its limits are set from:

    python benchmark/control.py --workload <cell> --seeds 11 12 13 --seconds 5
    python benchmark/control.py --workload <cell> --seeds 11 12 13 --seconds 5 \
        --engine '{"kv_cache_dtype": "int8"}'

For each seed, in one process, it makes a short run of the cell at its own
load (the same runner, ramp and sizes as ``run.py``) and reads, over the
same sampled requests and positions, the numbers ``correct`` compares twice:
once for the tokens the engine served, once for the tokens the reference
with weights rounded to int8 (one scale per output channel: the nearest
precision below the configuration's bf16) puts first.  The last line gives,
for each number, the largest sound reading and the smallest control reading;
a limit belongs between them (``PERF.md`` section 2 has the readings the
limits in the cells' files were set from).  ``--engine`` switches on a
lower-precision path of the program's own (the int8 KV cache) for the same
runs: the "sound" readings are then that path's, through the timed engine,
and have to lie over the limits too.  Chip only, like ``run.py``.
"""

import argparse
import gc
import json
import sys
import time

from run import open_cell, say


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--bits", type=int, default=8,
                    help="0: leave the rounded-weights reference out")
    ap.add_argument("--engine", type=json.loads, default={},
                    help="engine arguments over the cell's, as JSON")
    args = ap.parse_args(argv)
    _, _, cell, cfg, mix, runner, _ = open_cell(args.workload)
    cell = dict(cell, engine={**cell["engine"], **args.engine})
    sound, control = {}, {}
    for seed in args.seeds:
        run = runner.run(cell, cfg, mix, seed=seed, seconds=args.seconds,
                         t_start=time.perf_counter(), say=say,
                         control_bits=args.bits or None)
        for row in run["checks"]:
            if row["name"].startswith("served_"):
                sound.setdefault(row["name"], []).append(row["value"])
        for row in run["control"] or []:
            control.setdefault(row["name"], []).append(row["value"])
        say("seed", {"seed": seed, "correct": run["correct"],
                     "control_correct": run["control"] and all(
                         r["ok"] for r in run["control"])})
        del run
        gc.collect()
    print(json.dumps({"engine": cell["engine"]} | {
        name: {"sound": sound[name], "control": control.get(name),
               "sound_max": max(sound[name]),
               "control_min": min(control.get(name) or [float("nan")]),
               "limit": cell["check"]["limits"][name]}
        for name in sound}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
