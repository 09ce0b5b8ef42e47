"""A cell's step programs compiled at FULL depth and pool for a described
v5e, on the CPU (abstract weights and pool: nothing is allocated, nothing
runs, no time comes out).  A tool:

    JAX_PLATFORMS=cpu python tests/aot_full_depth.py <repo root> sdar|joyai \
        [layers]

prints, for the mixed step program and for the rows-alone one (a checkout
that has it), the compiler's count of arguments and temporaries, every
``copy`` of more than 4M elements by shape, and whether one of them is a
copy of a pool leaf.  The tier-1 compiles
(``tests/test_decode_kernel_tpu_compile.py``) cut the depth to 2-4 layers,
and what XLA:TPU does to a 4 GB pool under control flow shows only from 6-8
layers on (PR 44: a ``lax.cond`` around the pass copied the pool and ran the
chip out of memory at 48 layers; a ``while_loop`` of one turn kept it in
place and hoisted every layer's weight relayout ahead of itself): compile
the whole program here before trusting control flow around the cache.  Some
minutes and ~10 GB of host memory a program."""
import collections
import json
import math
import os
import re
import sys
import time

CELLS = {
    "sdar": ("sdar-30b-a3b-ep8.block-decode-saturated", "sdar-30b-a3b-ep8",
             "serve_sdar", "sdar", "SdarMoeForCausalLM"),
    "joyai": ("joyai-llm-flash-ep16.shared-doc-saturated",
              "joyai-llm-flash-ep16", "serve_mla", "latent_moe",
              "LatentMoeForCausalLM"),
}


def main(root, which, layers=None):
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    import importlib

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as pt
    import paddle_tpu.ops._dispatch as D
    from paddle_tpu import nn
    from paddle_tpu.serving import ServingEngine, engine as E

    D.default_backend = lambda: "tpu"      # the chip's dispatch, lowered here
    jax.config.update("jax_enable_compilation_cache", False)
    # the pool as shapes alone: a CPU need not hold 4 GB of it
    real_init = E.init_paged_kv_cache
    E.init_paged_kv_cache = lambda *a, **k: jax.eval_shape(
        lambda: real_init(*a, **k))
    E._place_on_mesh = lambda model, params, cache, *a, **k: (
        params, cache, None)

    cell, config, runner, module, cls = CELLS[which]

    def cell_file(kind, name):
        return json.load(open(os.path.join(
            root, "benchmark", kind, name + ".json")))
    kw = cell_file("workloads", cell)["engine"]
    cfg = cell_file("configs", config)
    if layers:
        cfg = dict(cfg, num_hidden_layers=int(layers))
    runner = importlib.import_module("benchmark.harness." + runner)
    Model = getattr(importlib.import_module("paddle_tpu.models." + module),
                    cls)
    with nn.abstract_parameters():
        model = Model(runner.program_config(cfg, kw["max_length"]))
    model.eval()
    pt.flags.set_flags({"perf_model": "off"})
    eng = ServingEngine(model, seed=0, **kw)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        eng._lint_args())
    pool = {tuple(x.shape) for x in jax.tree_util.tree_leaves(args[1])}
    print(cell, "layers", cfg["num_hidden_layers"], "cache leaves", pool)
    for fn in (eng._step_fn, getattr(eng, "_rows_fn", None)):
        if fn is None:
            continue
        name = fn.python_fn.__name__
        t0 = time.time()
        low = jax.jit(fn.python_fn, donate_argnums=(1,)).lower(*args)
        t1 = time.time()
        try:
            compiled = low.compile()
        except Exception as e:          # over the chip's memory: say what
            msg = str(e)
            print(name, "COMPILE FAILED", msg[:300])
            for m in re.finditer(
                    r"Size: ([\d.]+[GM])\n\s+Shape: (\S+)[^\n]*\n[^\n]*\n"
                    r"\s+XLA label: ([^\n]{0,160})", msg):
                print("  alloc", *m.groups())
            continue
        ma = compiled.memory_analysis()
        print(name, "trace+lower s", round(t1 - t0, 1), "compile s",
              round(time.time() - t1, 1), "(this host's, no device time)")
        print("  arguments", ma.argument_size_in_bytes, "aliased",
              ma.alias_size_in_bytes, "temporaries", ma.temp_size_in_bytes)
        copies = collections.Counter()
        for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* copy\(",
                             compiled.as_text()):
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            if math.prod(dims) > 4_000_000:
                copies[dims] += 1
        print("  copies over 4M elements", dict(copies))
        print("  copies of a pool leaf", {s: n for s, n in copies.items()
                                          if s in pool})


if __name__ == "__main__":
    main(*sys.argv[1:4])
