"""One run of the SDAR benchmark cell that also says, request by request,
where the served tokens lie from the reference's.  A tool, for the chip:

    python tests/chip_sdar_gaps.py --workload \
        sdar-30b-a3b-ep8.block-decode-saturated --seed <n> --seconds 45 \
        --trace 0

(``benchmark/run.py``'s arguments, from a checkout's root.)  The cell's
``correct`` reads the program's ROUNDING (PR 44: a step program that kept
float32 where the parent rounded to bfloat16 read as an error): beside the
run's usual lines this prints ``diag.sample`` (the requests compared: the
harness's own sample, then the earliest requests that finished, which are
the same on a faster and a slower program) and ``diag.gaps`` (per request:
positions, how many lie over the cell's ``gap_tail``, the largest and the
mean gap).  Run it on two checkouts with one seed and compare the requests
both lists hold.  The harness is patched in this process, never edited; the
result line is the harness's own, judged on its own sample.
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import numpy as np                                        # noqa: E402

from benchmark import run                                 # noqa: E402
from benchmark.harness import check, serve_sdar           # noqa: E402

real_sample, real_judge = check.sample_requests, serve_sdar.judge
picked = []         # the requests compared, then how many are the harness's


def sample(finished, k, seed):
    own = real_sample(finished, k, seed)
    greedy = sorted((r for r in finished
                     if r["temperature"] == 0.0 and r["tokens"]),
                    key=lambda r: r["index"])
    out = own + [r for r in greedy[:k] if all(r is not o for o in own)]
    picked[:] = out + [len(own)]
    print(json.dumps({"info": "diag.sample", "pool": len(finished),
                      "sampled": [{"index": r["index"],
                                   "prompt": len(r["prompt"]),
                                   "tokens": len(r["tokens"])}
                                  for r in out]}), flush=True)
    return out


def judge(pairs, limits, gap_tail):
    if not picked or len(pairs) != len(picked) - 1:     # a control's pairs
        return real_judge(pairs, limits, gap_tail)
    own = picked.pop()
    print(json.dumps({"info": "diag.gaps", "gap_tail": gap_tail,
                      "per_request": [
        {"index": r["index"], "total": len(r["prompt"]) + len(r["tokens"]),
         "positions": int(t.size), "over": int((t > gap_tail).sum()),
         "max": float(t.max()) if t.size else None,
         "mean": float(t.mean()) if t.size else None}
        for r, (t, _) in zip(picked, pairs)]}), flush=True)
    return real_judge(pairs[:own], limits, gap_tail)


check.sample_requests = sample
serve_sdar.judge = judge
run.main()
