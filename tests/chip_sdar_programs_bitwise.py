"""A cursor engine's two step programs on the SAME operands of real
chunk-free ticks of the SDAR cell (published widths, a cut of the layers, a
small pool), on the chip: tokens, ``n_unmasked``, expert load, and every
pool block but the null one bit for bit, the differing elements by layer.
A tool:

    python tests/chip_sdar_programs_bitwise.py [layers]

from a checkout's root.  On the CPU the two agree to the last bit; on the
chip what XLA fuses decides what is rounded to bfloat16, and a program that
fuses more rounds less (PR 44: the rows part alone, with no second part
taking a cut of a projection's result, differed in a fifth of the first
layer's elements and read as an error to the cell's ``correct``)."""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402
import numpy as np                                        # noqa: E402

from benchmark.harness import serve_sdar                  # noqa: E402
from paddle_tpu.serving import ServingEngine              # noqa: E402

CELL, CONFIG = "sdar-30b-a3b-ep8.block-decode-saturated", "sdar-30b-a3b-ep8"


def cell_file(kind, name):
    return json.load(open(os.path.join("benchmark", kind, name + ".json")))


def main(layers=16, num_blocks=65, want=3):
    cfg = dict(cell_file("configs", CONFIG), num_hidden_layers=int(layers))
    kw = dict(cell_file("workloads", CELL)["engine"], num_blocks=num_blocks)
    model, made = serve_sdar.build_model(cfg, 4444050007, kw["max_length"])
    del made
    eng = ServingEngine(model, seed=7, **kw)
    ticks, upload = [], eng._upload

    def spy(table, own):        # a chunk-free tick's cache and operands
        args = upload(table, own)
        if (table is eng._step_table and not int(own.get("clen", 1))
                and len(ticks) < want and eng._ticks % 7 == 0
                and int(np.asarray(eng._active).sum()) >= 3):
            ticks.append((jax.tree_util.tree_map(jnp.copy, eng._cache),
                          [jnp.array(np.array(x, copy=True)) for x in args],
                          int(np.asarray(eng._active).sum())))
        return args
    eng._upload = spy
    rng = np.random.default_rng(5)
    for n in (300, 520, 411, 777, 260, 640):
        eng.submit(rng.integers(1, cfg["mask_token_id"], n).astype(np.int32),
                   max_new_tokens=96)
    for _ in range(400):
        eng.step()
        if len(ticks) == want or not (eng.queue_depth or eng.last_occupancy):
            break
    print("captured", len(ticks), "chunk-free ticks; traces",
          eng.step_traces, eng.rows_step_traces, flush=True)
    names = eng._step_outputs[:-1]
    for cache, args, occ in ticks:
        copy = jax.tree_util.tree_map(jnp.copy, cache)
        *new, ca = eng._rows_fn(eng._params, cache, *args)
        *old, cb = eng._step_fn(eng._params, copy, *args)
        line = {"live_rows": occ}
        for name, x, y in zip(names, new, old):
            if name != "chunk_token":
                line[name + "_equal"] = bool(
                    np.array_equal(np.asarray(x), np.asarray(y)))
        for la, lb in zip(jax.tree_util.tree_leaves(ca),
                          jax.tree_util.tree_leaves(cb)):
            la, lb = (np.asarray(v[:, :, 1:]).astype(np.float32)
                      for v in (la, lb))
            diff = np.abs(la - lb)
            line.update(
                pool_elements=int(diff.size),
                pool_differing=int((diff > 0).sum()),
                pool_max_abs_diff=float(diff.max()),
                differing_by_layer=[int(v) for v in (diff > 0).reshape(
                    diff.shape[0], -1).sum(1)])
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:2])
