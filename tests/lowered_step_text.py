"""The lowered (StableHLO) form of the engine's step programs, lowered on the
CPU for the chip.  A tool, and the helpers of tests that read a lowered
program (``tests/test_sample_paths.py``):

    JAX_PLATFORMS=cpu python tests/lowered_step_text.py <repo root>

prints, under the chip's dispatch, the sha256 of the text of the step and
prefill programs of every benchmark configuration (published widths, the
engine settings of the cells' files; 2 layers, or 4 where the layers are of
four kinds: the layers are one code path repeated) and of the engine's eight
layouts (paged x chunked x spec) at tiny llama geometry.  Run it on two
checkouts and compare the lines: PR 27 used it to show that a second model in
the engine left the llama step programs as they were, PR 29 that one composed
step program lowers to the text of the eight hand-written ones, PR 41 that
a wave engine's step program and its prefill program at ``prefill_batch`` rows
stayed as they were beside the one-row program (the ``prefill rows=1``
lines, which an older checkout does not print), PR 44 that every step
program stayed as it was beside a cursor engine's rows-alone program (the
``rows step`` lines), PR 46 that a seventh model with two slot leaves left
the six cells' programs as they were."""
import base64
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp


def lowered(fn, args):
    """``fn`` (a step program's Python body; its second argument, the cache,
    donated as the engine donates it) lowered for the chip."""
    return jax.jit(fn, donate_argnums=(1,)).trace(*args).lower(
        lowering_platforms=("tpu",))


def _functions(low):
    module = low.compiler_ir("stablehlo")
    return {f.name.value: f for f in module.body.operations
            if f.operation.name == "func.func"}


def walk(low):
    """Every operation of a lowered program, once for each chain of calls
    that reaches it from ``main``, as (operation, inside a conditional's
    branch or not)."""
    funcs = _functions(low)

    def visit(op, in_branch):
        name = op.operation.name
        yield op, in_branch
        if name == "func.call":
            yield from visit(funcs[op.attributes["callee"].value], in_branch)
        inside = in_branch or name in ("stablehlo.case", "stablehlo.if")
        for region in op.operation.regions:
            for block in region.blocks:
                for child in block.operations:
                    yield from visit(child, inside)

    yield from visit(funcs["main"], False)


def sorts_over(low, width):
    """Every ``stablehlo.sort`` of a lowered program whose operand's last axis
    is ``width`` long, once for each chain of calls that reaches it from
    ``main``, as (operand shape, inside a conditional's branch or not)."""
    found = []
    for op, in_branch in walk(low):
        if op.operation.name == "stablehlo.sort":
            shape = tuple(op.operands[0].type.shape)
            if shape and shape[-1] == width:
                found.append((shape, in_branch))
    return found


def kernel_calls(low):
    """How often a lowered program calls each Pallas kernel, by the
    kernel's name (``ops._dispatch.kernel_name``)."""
    calls = {}
    for op, _ in walk(low):
        if (op.operation.name == "stablehlo.custom_call" and
                op.attributes["call_target_name"].value == "tpu_custom_call"):
            name = op.attributes["kernel_name"].value
            calls[name] = calls.get(name, 0) + 1
    return calls


# what hands a weight on as it is, on its way to the product that reads it
_PASSES_ON = ("stablehlo.transpose", "stablehlo.convert", "stablehlo.reshape")


def product_reads(low, args):
    """Per leaf of ``args[0]`` (the parameters) of two or more axes: how
    many matrix products of the lowered program read it — a
    ``dot_general``, or a Pallas kernel's call — followed through calls and
    through transposes, conversions and reshapes."""
    funcs = _functions(low)

    def reads(value):
        n = 0
        for use in value.uses:
            op = use.owner
            if op.name in ("stablehlo.dot_general", "stablehlo.custom_call"):
                n += 1
            elif op.name == "func.call":
                callee = funcs[op.attributes["callee"].value]
                n += reads(callee.arguments[use.operand_number])
            elif op.name in _PASSES_ON:
                n += sum(reads(r) for r in op.results)
        return n

    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    main = funcs["main"]
    assert len(leaves) == len(main.arguments)
    return {jax.tree_util.keystr(path[1:]): reads(arg)
            for (path, leaf), arg in zip(leaves, main.arguments)
            if path[0].idx == 0 and len(leaf.shape) >= 2}


def strip_locations(txt):
    """A Mosaic kernel's body is MLIR bytecode that carries the kernel
    source's file:line locations, so any edit above a kernel moves it.
    Each body is parsed and printed again without debug info."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def plain(m):
        ctx = jmlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return ('body\\22: \\22' + mod.operation.get_asm(
                enable_debug_info=False).replace("\n", " ") + '\\22')
    return re.sub(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', plain, txt)


def sha(low):
    txt = low.as_text()
    raw = hashlib.sha256(txt.encode()).hexdigest()[:8]
    txt = strip_locations(txt)
    print("   raw text", raw, "kernel bodies re-printed:",
          txt.count("body\\22: \\22module"))
    return hashlib.sha256(txt.encode()).hexdigest()[:16], len(txt)


def prefill_args(eng, bucket, rows=None):
    """The prefill program's operands at one bucket length, with ``rows``
    rows (None: ``prefill_batch``, the one row count a checkout from before
    PR 41 has): the engine's own table of them, or, on a checkout from
    before PR 29, the hand copy."""
    rows = rows or eng.prefill_batch
    try:
        return eng._lint_args(bucket, rows)
    except TypeError:                   # before PR 41: one row count
        if rows != eng.prefill_batch:
            raise
    try:
        return eng._lint_args(bucket)
    except TypeError:
        nb = eng.prefill_batch

        def z(*s, dt=jnp.int32):
            return jnp.zeros(s, dt)
        rows = ((z(nb), z(nb), z(nb, eng.max_blocks)) if eng.paged
                else (z(nb), z(nb)))
        return (eng._params, eng._cache, z(nb, bucket), *rows,
                z(nb, dt=jnp.float32), z(nb), jnp.ones((nb,), jnp.float32),
                jax.random.key(0))


def programs(label, eng, bucket, vocab=None):
    step = eng._step_fn.python_fn
    low = lowered(step, eng._lint_args())
    print(label, "step", step.__name__, *sha(low))
    if vocab:
        print("   sorts over the vocabulary (shape, in a branch):",
              sorts_over(low, vocab))
    # a cursor engine's program for its chunk-free ticks (PR 44)
    if getattr(eng, "_rows_fn", None) is not None:
        rows = eng._rows_fn.python_fn
        print(label, "rows step", rows.__name__,
              *sha(lowered(rows, eng._lint_args())))
    if eng._prefill_fn is not None:
        prefill = eng._prefill_fn.python_fn
        print(label, "prefill", prefill.__name__,
              *sha(lowered(prefill, prefill_args(eng, bucket))))
        # the one-row program (PR 41: a prompt whose bucket fills the chip
        # is prefilled alone)
        if 1 in getattr(eng, "_wave_tables", ()) and eng.prefill_batch > 1:
            print(label, "prefill rows=1", prefill.__name__,
                  *sha(lowered(prefill, prefill_args(eng, bucket, 1))))


def cell_engines(root):
    """(cell, its engine, the vocabulary a sort would span or None) for
    every benchmark cell of the checkout at ``root`` (already on
    ``sys.path``), one at a time: published widths, the engine settings of
    the cell's file, 2 layers, or 4 where the layers are of four kinds.
    The expert models are built to be loaded, since only shapes are lowered
    and a CPU need not hold 2 GB of experts (the cost model would sum the
    weights' bytes, and is no part of a program)."""
    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving import ServingEngine

    def cell_file(kind, name):
        return json.load(open(os.path.join(
            root, "benchmark", kind, name + ".json")))
    cfg = cell_file("configs", "mistral-7b")
    fields = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads",
        "max_position_embeddings", "rms_norm_eps", "rope_theta",
        "tie_word_embeddings")}
    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        dtype="bfloat16", num_hidden_layers=2, **fields))
    model.eval()
    for cell in ("mistral-7b.decode-saturated", "mistral-7b.chat-open"):
        yield cell, ServingEngine(
            model, seed=0, **cell_file("workloads", cell)["engine"]), \
            fields["vocab_size"]
    del model

    # the third cell: the published layer 0 (dense, window) and one global
    # expert layer, every held expert
    from benchmark.harness import serve_afmoe
    from paddle_tpu.models.afmoe import AfmoeForCausalLM
    cell = "trinity-large-ep8.longtail-saturated"
    kw = cell_file("workloads", cell)["engine"]
    cfg = dict(cell_file("configs", "trinity-large-ep8"), num_hidden_layers=2,
               layer_types=["sliding_attention", "full_attention"])
    with nn.abstract_parameters():
        model = AfmoeForCausalLM(
            serve_afmoe.program_config(cfg, kw["max_length"]))
    model.eval()
    pt.flags.set_flags({"perf_model": "off"})
    yield cell, ServingEngine(model, seed=0, **kw), None
    pt.flags.set_flags({"perf_model": "on"})
    del model

    # the fourth cell, on a checkout that has it: a dense convolution
    # layer, a dense one, then an attention layer and a convolution layer
    # with experts (the published layers 0-3), every held expert
    cell = "lfm2-8b-a1b-ep2.decode-wide-saturated"
    if not os.path.isfile(os.path.join(root, "benchmark", "workloads",
                                       cell + ".json")):
        return
    from benchmark.harness import serve_lfm2
    from paddle_tpu.models.lfm2 import Lfm2MoeForCausalLM
    kw = dict(cell_file("workloads", cell)["engine"], num_blocks=129)
    cfg = cell_file("configs", "lfm2-8b-a1b-ep2")
    cfg = dict(cfg, num_hidden_layers=4, layer_types=cfg["layer_types"][:4])
    with nn.abstract_parameters():
        model = Lfm2MoeForCausalLM(
            serve_lfm2.program_config(cfg, kw["max_length"]))
    model.eval()
    pt.flags.set_flags({"perf_model": "off"})
    yield cell, ServingEngine(model, seed=0, **kw), None
    pt.flags.set_flags({"perf_model": "on"})
    del model

    # the fifth cell, on a checkout that has it: two of its 48 layers (all
    # alike), every held expert
    cell = "sdar-30b-a3b-ep8.block-decode-saturated"
    if not os.path.isfile(os.path.join(root, "benchmark", "workloads",
                                       cell + ".json")):
        return
    from benchmark.harness import serve_sdar
    from paddle_tpu.models.sdar import SdarMoeForCausalLM
    kw = dict(cell_file("workloads", cell)["engine"], num_blocks=129)
    cfg = dict(cell_file("configs", "sdar-30b-a3b-ep8"), num_hidden_layers=2)
    with nn.abstract_parameters():
        model = SdarMoeForCausalLM(
            serve_sdar.program_config(cfg, kw["max_length"]))
    model.eval()
    pt.flags.set_flags({"perf_model": "off"})
    yield cell, ServingEngine(model, seed=0, **kw), None
    pt.flags.set_flags({"perf_model": "on"})
    del model

    # the sixth cell, on a checkout that has it: the dense layer 0 and one
    # of the 39 expert layers (all alike), every held expert, latent
    # attention over a pool of one entry a position
    cell = "joyai-llm-flash-ep16.shared-doc-saturated"
    if not os.path.isfile(os.path.join(root, "benchmark", "workloads",
                                       cell + ".json")):
        return
    from benchmark.harness import serve_mla
    from paddle_tpu.models.latent_moe import LatentMoeForCausalLM
    kw = dict(cell_file("workloads", cell)["engine"], num_blocks=129)
    cfg = dict(cell_file("configs", "joyai-llm-flash-ep16"),
               num_hidden_layers=2)
    with nn.abstract_parameters():
        model = LatentMoeForCausalLM(
            serve_mla.program_config(cfg, kw["max_length"]))
    model.eval()
    pt.flags.set_flags({"perf_model": "off"})
    yield cell, ServingEngine(model, seed=0, **kw), None
    pt.flags.set_flags({"perf_model": "on"})
    del model

    # the seventh cell, on a checkout that has it: a Gated DeltaNet layer
    # and an attention layer (the published period has three of the first)
    cell = "olmo-hybrid-7b.decode-state-saturated"
    if not os.path.isfile(os.path.join(root, "benchmark", "workloads",
                                       cell + ".json")):
        return
    from benchmark.harness import serve_olmo_hybrid
    from paddle_tpu.models.olmo_hybrid import OlmoHybridForCausalLM
    kw = dict(cell_file("workloads", cell)["engine"], num_blocks=129)
    cfg = cell_file("configs", "olmo-hybrid-7b")
    cfg = dict(cfg, num_hidden_layers=2, layer_types=cfg["layer_types"][2:4])
    with nn.abstract_parameters():
        model = OlmoHybridForCausalLM(
            serve_olmo_hybrid.program_config(cfg, kw["max_length"]))
    model.eval()
    pt.flags.set_flags({"perf_model": "off"})
    yield cell, ServingEngine(model, seed=0, **kw), None
    pt.flags.set_flags({"perf_model": "on"})


def main(root):
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    import paddle_tpu.ops._dispatch as D
    D.default_backend = lambda: "tpu"      # the chip's dispatch, lowered here
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
    from paddle_tpu.serving import ServingEngine

    for cell, eng, vocab in cell_engines(root):
        programs(cell, eng, 256, vocab=vocab)
        del eng

    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    for paged in (False, True):
        for chunked in (False, True):
            for spec in (False, True):
                eng = ServingEngine(
                    model, num_slots=3, max_length=64, block_len=8,
                    prefill_chunk=8, spec_k=2, seed=0, paged=paged,
                    chunked=chunked, spec_decode=spec)
                programs(f"tiny-llama paged={int(paged)} chunked="
                         f"{int(chunked)} spec={int(spec)}", eng, 16)


if __name__ == "__main__":
    main(sys.argv[1])
