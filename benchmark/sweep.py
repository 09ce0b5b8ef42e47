"""Find the knee of an open-loop cell, once, when the cell is defined:

    python benchmark/sweep.py --workload <cell> --seed 5 --seconds 25 --rates 3 4 5 6

One process, one engine: for each rate it offers the cell's traffic mix at
that rate for ``--seconds`` (after the mix's own ramp), reads the window's
numbers, and drains the engine before the next.  The knee is the highest
rate at which the queue does not grow: time to first token stays flat from
the window's first half to its second and no backlog is left.  The cell's
file then gets four fifths of it as a number.  Chip only, like ``run.py``.
"""

import argparse
import json
import sys
import time

from run import open_cell, say

from benchmark.harness import serve, stats, traffic   # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, _, cell, cfg, mix, _, _ = open_cell(args.workload)
    eng, _, _, _, parts = serve.setup(cell, cfg, mix, args.seed,
                                      args.seconds, time.perf_counter())
    say("setup", parts)
    for i, rate in enumerate(args.rates):
        at = dict(mix, rate_per_s=rate)
        reqs = traffic.generate(at, cfg["vocab_size"], args.seed + i,
                                args.seconds)
        stamps = serve.drive(eng, reqs, at, args.seconds)
        m = serve.measure(stamps, at, args.seconds)
        w0, w1 = stamps["window"]
        mid = (w0 + w1) / 2 - stamps["t_zero"]
        halves = [[], []]
        for rec, ms in zip(m["judged"], m["ttft_ms"]):
            halves[rec.due > mid].append(ms)
        print(json.dumps({
            "rate_per_s": rate, "judged": len(m["judged"]),
            "failed": m["failed"], "queue_left": stamps["queue_left"],
            "output_tok_s": m["tokens"] / args.seconds,
            "ttft_ms": stats.summary(m["ttft_ms"]),
            "ttft_p50_first_half_ms": stats.median(halves[0]),
            "ttft_p50_second_half_ms": stats.median(halves[1]),
            "token_gap_ms": stats.summary(m["gaps_ms"]),
            "tick_p50_ms": stats.median(
                [(b - a) * 1e3 for a, b, _, _ in m["ticks"]]),
            "occupancy_mean": sum(t[2] for t in m["ticks"])
            / max(1, len(m["ticks"]))}), flush=True)
        eng.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
