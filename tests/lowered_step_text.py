"""sha256 of the lowered (StableHLO) text of the llama step programs at the
Mistral cells' geometry (published widths, the engine settings of the cells'
files; 2 of the 16 layers: the layers are one code path repeated), lowered
on the CPU for the chip's dispatch.  A tool, not a test: run it on two
checkouts and compare the lines (PR 27 used it to show that a second model
in the engine left the llama step programs as they were):

    JAX_PLATFORMS=cpu python tests/lowered_step_text.py <repo root>
"""
import hashlib, json, os, sys
root = os.path.abspath(sys.argv[1]); sys.path.insert(0, root)
import jax, jax.numpy as jnp
import paddle_tpu.ops._dispatch as D
D.default_backend = lambda: "tpu"          # the chip's dispatch, lowered here
import paddle_tpu as pt
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.serving import ServingEngine
cfg = json.load(open(os.path.join(root, "benchmark/configs/mistral-7b.json")))
fields = {k: cfg[k] for k in ("vocab_size", "hidden_size", "intermediate_size",
    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
    "rms_norm_eps", "rope_theta", "tie_word_embeddings")}
pt.seed(0)
model = LlamaForCausalLM(LlamaConfig(dtype="bfloat16", num_hidden_layers=2, **fields)); model.eval()
import base64, re
def strip_locations(txt):
    """A Mosaic kernel's body is MLIR bytecode that carries the kernel
    source's file:line locations, so any edit above a kernel moves it.
    Each body is parsed and printed again without debug info."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    def plain(m):
        ctx = jmlir.make_ir_context(); tpu.register_dialect(ctx); ctx.allow_unregistered_dialects = True
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return 'body\\22: \\22' + mod.operation.get_asm(enable_debug_info=False).replace("\n", " ") + '\\22'
    return re.sub(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', plain, txt)
def sha(fn, args):
    txt = jax.jit(fn, donate_argnums=(1,)).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    raw = hashlib.sha256(txt.encode()).hexdigest()[:8]
    txt = strip_locations(txt)
    print("   raw text", raw, "kernel bodies re-printed:", txt.count("body\\22: \\22module"))
    return hashlib.sha256(txt.encode()).hexdigest()[:16], len(txt)
for cell in ("mistral-7b.decode-saturated", "mistral-7b.chat-open"):
    eng_kw = json.load(open(os.path.join(root, "benchmark/workloads", cell + ".json")))["engine"]
    eng = ServingEngine(model, seed=0, **eng_kw)
    print(cell, "step", eng._step_fn.python_fn.__name__, *sha(eng._step_fn.python_fn, eng._lint_args()))
    if eng._prefill_fn is not None:
        nb, L = eng.prefill_batch, 256
        z = lambda *s, dt=jnp.int32: jnp.zeros(s, dt)
        args = (eng._params, eng._cache, z(nb, L), z(nb), z(nb), z(nb, eng.max_blocks),
                z(nb, dt=jnp.float32), z(nb), jnp.ones((nb,), jnp.float32), jax.random.key(0))
        print(cell, "prefill", eng._prefill_fn.python_fn.__name__, *sha(eng._prefill_fn.python_fn, args))
    del eng
