"""The flash-decode kernel compiled FOR the chip WITHOUT one, at the
benchmark cells' widths: Mosaic and XLA:TPU run against a described v5e
(``jax.experimental.topologies``), so what interpret mode cannot see — a
slice off the tiling, a DMA shape, a loop Mosaic will not lower, more VMEM
than a kernel may take — fails here and not on the chip.  Nothing runs: no
result and no time comes out of this file.

Also the chunked cells' whole MIXED step programs (published widths, the
cells' engine settings, ``lowered_step_text``'s cut depth), under the chip's
dispatch: that one pass of the weights reads each weight in one product and
calls the grouped product three times an expert layer, that Mosaic takes it
at the flat pair count, and that the program holds no temporary of a
weight's or the pool's size and no copy of the pool; and the same of the
rows-alone programs their chunk-free ticks run (the chunk part a stub).

The topology is described inside a fixture, never at import: one process at
a time may load libtpu, and every xdist worker imports every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding
from lowered_step_text import (cell_engines, kernel_calls, lowered,
                               prefill_args, product_reads, sha)

from paddle_tpu.ops import _dispatch
from paddle_tpu.ops.pallas.decode_attention import (
    LatentLayout, SharedWalk, decode_attention_pallas,
    latent_decode_attention_pallas, paged_decode_attention_pallas)

HKV, D, BLOCK = 8, 128, 128

# name: (rows, q length, q heads, layers, pool blocks, table columns, window,
# cache dtype[, head size]) — the decode rows, the prompt chunk and a wave's
# rows of the Mistral cells, the Trinity cell's window layers, an int8 pool,
# and the LFM2 cell's six K/V layers at head size 64: half a lane tile, so
# the body slices a group's (keys, Hkv·D) buffer at 64-lane offsets (the
# SDAR cell's block-masked calls have a test of their own below)
PAGED = {
    "mistral-rows": (96, 1, 32, 16, 769, 32, None, jnp.bfloat16),
    "mistral-chunk": (1, 256, 32, 16, 385, 32, None, jnp.bfloat16),
    "mistral-wave-1024": (4, 1024, 32, 16, 769, 32, None, jnp.bfloat16),
    # a prompt of 256 positions or more is prefilled alone, one row a
    # program call (ISSUE 41): the longest such bucket and the shortest
    "mistral-wave-one-row-1024": (1, 1024, 32, 16, 769, 32, None,
                                  jnp.bfloat16),
    "mistral-wave-one-row-256": (1, 256, 32, 16, 769, 32, None,
                                 jnp.bfloat16),
    "trinity-rows-window": (192, 1, 48, 5, 1921, 64, 4096, jnp.bfloat16),
    "trinity-chunk-window": (1, 256, 48, 5, 1921, 64, 4096, jnp.bfloat16),
    "int8-rows": (8, 1, 32, 4, 257, 32, None, jnp.int8),
    "lfm2-rows-d64": (320, 1, 32, 6, 2049, 32, None, jnp.bfloat16, 64),
    "lfm2-chunk-d64": (1, 256, 32, 6, 2049, 32, None, jnp.bfloat16, 64),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep these compiles out of the persistent cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cached)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("name", list(PAGED))
def test_paged_kernel_compiles_for_v5e(one_chip, name):
    b, s, hq, layers, blocks, cols, window, dtype, *d = PAGED[name]
    d = d[0] if d else D
    args = [_spec(one_chip, (b, s, hq, d), jnp.bfloat16),
            _spec(one_chip, (layers, 2, blocks, BLOCK, HKV * d), dtype),
            _spec(one_chip, (b,), jnp.int32),
            _spec(one_chip, (b, cols), jnp.int32)]
    if dtype == jnp.int8:
        args.append(_spec(one_chip, (layers, 2, blocks, HKV), jnp.float32))

    def call(q, pool, pos, tables, scale=None):
        return paged_decode_attention_pallas(
            q, pool, layers - 1, pos, tables, pool_scale=scale,
            window=window)

    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pool is read where it lies: no temporary of a layer's size
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("rows, s", [(96, 4), (1, 256)],
                         ids=["sdar-block-rows", "sdar-chunk"])
def test_block_masked_kernel_compiles_for_v5e(one_chip, rows, s):
    """The body under the block-causal mask at the SDAR cell's geometry:
    4 K/V heads of 128 (512 lanes a key), a GQA group of 8 (q tiles of 8
    positions), a row's block of 4 and a 256-token chunk, all 48 layers."""
    hkv, hq, layers, blocks, cols = 4, 32, 48, 321, 32
    args = [_spec(one_chip, (rows, s, hq, D), jnp.bfloat16),
            _spec(one_chip, (layers, 2, blocks, BLOCK, hkv * D),
                  jnp.bfloat16),
            _spec(one_chip, (rows,), jnp.int32),
            _spec(one_chip, (rows, cols), jnp.int32)]

    def call(q, pool, pos, tables):
        return paged_decode_attention_pallas(q, pool, layers - 1, pos,
                                             tables, block=4)

    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


@pytest.mark.parametrize("rows, s, tiles", [(96, 1, 0), (1, 256, 0),
                                            (96, 1, 32)],
                         ids=["latent-rows", "latent-chunk",
                              "latent-rows-shared"])
def test_latent_kernel_compiles_for_v5e(one_chip, rows, s, tiles):
    """The body with the latent layout as its static parameter at the
    JoyAI cell's geometry: ONE array a layer of 640 stored lanes a position
    (576 values), 32 heads one query group (a rows tile of 32 MXU rows, a
    chunk's of 256: 8 tokens), the value the entry's first 512 lanes, copy
    groups of 1,024 keys, tables of 96 columns, all 40 layers.  Shared: the
    two-part walk as the cell's engine builds it — 96 rows on four
    documents, tiles of 8 rows (256 MXU rows) for as many as the slots can
    fill — both parts one Mosaic body each under the one name."""
    heads, width, layers, blocks, cols = 32, 640, 40, 700, 96
    args = [_spec(one_chip, (rows, s, heads, width), jnp.bfloat16),
            _spec(one_chip, (layers, 1, blocks, BLOCK, width), jnp.bfloat16),
            _spec(one_chip, (rows,), jnp.int32),
            _spec(one_chip, (rows, cols), jnp.int32)]
    if tiles:
        args += [_spec(one_chip, shape, jnp.int32) for shape in (
            (rows,), (rows,), (tiles, 256 // heads), (tiles,))]

    def call(q, pool, pos, tables, *shared):
        return latent_decode_attention_pallas(
            q, pool, layers - 1, pos, tables, LatentLayout(value_width=512),
            192 ** -0.5, shared=SharedWalk(*shared) if shared else None)

    compiled = jax.jit(call).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (2 if tiles else 1)
    out, = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (rows, s, heads, 512)
    # the pool is read where it lies: no temporary of a layer's size
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_contiguous_kernel_compiles_for_v5e(one_chip):
    q = _spec(one_chip, (8, 1, 32, D), jnp.bfloat16)
    kv = _spec(one_chip, (8, 4096, HKV, D), jnp.bfloat16)
    compiled = jax.jit(decode_attention_pallas).lower(
        q, kv, kv, _spec(one_chip, (8,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the Olmo-Hybrid cell's kernels -------------------------------------------

@pytest.mark.parametrize("rows, s", [(80, 1), (1, 256), (1, 8)],
                         ids=["olmo-rows-g1", "olmo-chunk-g1", "olmo-stub-g1"])
def test_flash_decode_at_a_query_group_of_one_compiles_for_v5e(one_chip,
                                                               rows, s):
    """The flash-decode body at plain multi-head attention, 30 K/V heads of
    128 and a query group of ONE (every older cell has a group of 4 to 32):
    a key is 15 KiB over K and V, so the copy group is 256 keys and not 512
    (``group_blocks``), or its double buffers alone would fill the 16 MB a
    kernel may use."""
    hkv, layers, blocks, cols = 30, 4, 471, 32
    args = [_spec(one_chip, (rows, s, hkv, D), jnp.bfloat16),
            _spec(one_chip, (layers, 2, blocks, BLOCK, hkv * D),
                  jnp.bfloat16),
            _spec(one_chip, (rows,), jnp.int32),
            _spec(one_chip, (rows, cols), jnp.int32)]

    def call(q, pool, pos, tables):
        return paged_decode_attention_pallas(q, pool, layers - 1, pos,
                                             tables)
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 21)


@pytest.mark.parametrize("rows, s", [(80, 1), (1, 256), (1, 8)],
                         ids=["step-80-rows", "chunk-256", "stub-8"])
def test_gated_delta_kernels_compile_for_v5e(one_chip, rows, s):
    """The gated delta rule's step and chunk kernels at the Olmo-Hybrid
    cell's shapes: 12 linear layers x 81 state rows of 96 x (30 x 192)
    float32 (2.15 GB), aliased: the compiled call holds the leaf once."""
    from paddle_tpu.ops.pallas.gated_delta import (gated_delta_chunk_pallas,
                                                   gated_delta_step_pallas)
    layers, state_rows, h, dk, dv = 12, 81, 30, 96, 192
    f32 = jnp.float32
    leaf = _spec(one_chip, (layers, state_rows, dk, h * dv), f32)
    args = [leaf, _spec(one_chip, (), jnp.int32),
            *(_spec(one_chip, (rows, s, h, w), f32) for w in (dk, dk, dv)),
            _spec(one_chip, (rows, s, h), f32),
            _spec(one_chip, (rows, s, h), f32),
            _spec(one_chip, (rows,), jnp.bool_),
            _spec(one_chip, (rows,), jnp.bool_)]
    fn = gated_delta_step_pallas if s == 1 else gated_delta_chunk_pallas

    def call(leaf, first, q, k, v, g, beta, live, fresh):
        return fn(leaf, layers - 1, first, q, k, v, g, beta, live, fresh)
    compiled = jax.jit(call, donate_argnums=(0,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    nbytes = layers * state_rows * dk * h * dv * 4
    assert mem.alias_size_in_bytes >= nbytes          # updated in place
    assert mem.temp_size_in_bytes < (1 << 26)         # no second copy


# -- the mixed step programs, whole -------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# cell: expert layers at the cut depth
JOYAI = "joyai-llm-flash-ep16.shared-doc-saturated"
OLMO = "olmo-hybrid-7b.decode-state-saturated"
MIXED = {"mistral-7b.chat-open": 0,
         "trinity-large-ep8.longtail-saturated": 1,
         "lfm2-8b-a1b-ep2.decode-wide-saturated": 2,
         "sdar-30b-a3b-ep8.block-decode-saturated": 2,
         JOYAI: 1, OLMO: 0}
# the name of the rows part's attention kernel, where it is not the decode
# rows': a block-diffusion model's rows are blocks, a latent pool's walk has
# its own name
ROWS_KERNEL = {"sdar-30b-a3b-ep8.block-decode-saturated":
               "_step_impl_block_rows_flash_decode",
               JOYAI: "_step_impl_decode_rows_latent_flash_decode"}
CHUNK_KERNEL = {JOYAI: "_step_impl_prompt_chunk_latent_flash_decode"}
# the step and prefill programs of the five cells PR 42 found, as
# ``lowered_step_text.py`` hashes them (kernel bodies re-printed without
# locations): a latent pool's layout is a static parameter of the one
# flash-decode body, and every call site that was there keeps its statics
# and its lowered text.  A PR that means to change one of these programs
# re-pins its line from the tool's output and says so.
PINNED = {
    ("mistral-7b.decode-saturated", "step"): "9caac2d72281204d",
    ("mistral-7b.decode-saturated", "prefill"): "ecf0b26814cb2758",
    ("mistral-7b.decode-saturated", "prefill rows=1"): "d8d687e81c01f5bc",
    ("mistral-7b.chat-open", "step"): "20f63b0095305316",
    # the three with expert layers re-pinned by PR 44, which meant to change
    # them: the grouped product's call is jitted on its own
    # (``grouped_matmul._gmm_call``), so a ``func.call`` stands where the
    # kernel's call stood; what is called is the same
    ("trinity-large-ep8.longtail-saturated", "step"): "b5f36e18443ee703",
    ("lfm2-8b-a1b-ep2.decode-wide-saturated", "step"): "42a11c3aee010e1a",
    ("sdar-30b-a3b-ep8.block-decode-saturated", "step"): "2aa0ba5bde16ac1e",
    # a cursor engine's program for its chunk-free ticks, new in PR 44 and
    # pinned as it came: the mixed program's body with the chunk part cut
    # to a stub of 8 positions
    ("mistral-7b.chat-open", "rows step"): "978e465efa7a0746",
    ("trinity-large-ep8.longtail-saturated", "rows step"):
        "3cb43a8bca38e591",
    ("lfm2-8b-a1b-ep2.decode-wide-saturated", "rows step"):
        "ebf36c1acba7984a",
    ("sdar-30b-a3b-ep8.block-decode-saturated", "rows step"):
        "fd27b4cad493a221",
    (JOYAI, "rows step"): "bd1ef7642f732ae0",
}


@pytest.fixture(scope="module")
def mixed_engines():
    """cell -> its engine, built under the chip's dispatch (the code asks
    ``default_backend()`` which kernels to build; the test steers it, the
    program has no option for it).  The patch stays for the module's tests:
    they lower and compile, nothing runs."""
    patch = pytest.MonkeyPatch()
    patch.setattr(_dispatch, "default_backend", lambda: "tpu")
    yield {cell: eng for cell, eng, _ in cell_engines(ROOT)}
    patch.undo()


@pytest.mark.parametrize("cell, program", list(PINNED),
                         ids=[f"{c}-{p.replace(' ', '-')}" for c, p in PINNED])
def test_older_cells_programs_lower_to_the_text_they_had(mixed_engines, cell,
                                                         program):
    eng = mixed_engines[cell]
    if program.endswith("step"):
        fn = eng._rows_fn if program == "rows step" else eng._step_fn
        low = lowered(fn.python_fn, eng._lint_args())
    else:
        low = lowered(eng._prefill_fn.python_fn, prefill_args(
            eng, 256, 1 if program.endswith("rows=1") else None))
    assert sha(low)[0] == PINNED[cell, program]


@pytest.mark.parametrize("rows_alone", [False, True],
                         ids=["mixed", "rows_alone"])
@pytest.mark.parametrize("cell", list(MIXED))
def test_mixed_program_reads_each_weight_in_one_product(mixed_engines, cell,
                                                        rows_alone):
    eng = mixed_engines[cell]
    args = eng._lint_args()
    fn = eng._rows_fn if rows_alone else eng._step_fn
    low = lowered(fn.python_fn, args)
    reads = product_reads(low, args)
    # tables and filters no product reads: the embedding where the head is
    # not tied to it, RoPE's, a short convolution's taps
    # (and a latent layer's up-projection, which is sliced apart first: its
    # key half goes to the query in one product, its value half to the
    # result in another)
    assert {k for k, n in reads.items() if n == 0} <= {
        k for k in reads if k.endswith(("rope_cos']", "rope_sin']",
                                        "embed_tokens']", ".conv.conv']",
                                        ".mixer.conv']", ".kv_b_proj']"))}
    twice = {k: n for k, n in reads.items() if n > 1}
    assert not twice, twice
    assert sum(reads.values()) >= 5 * eng.config.num_hidden_layers
    calls = kernel_calls(low)
    assert calls.get("_step_impl_token_pass_moe_experts", 0) == 3 * MIXED[cell]
    assert not [k for k in calls if "moe_experts" in k
                and k != "_step_impl_token_pass_moe_experts"], calls
    # attention stays a part at a time, under the part's own name
    # (the rows-alone program calls the chunk's for its stub)
    assert {k for k in calls if "flash_decode" in k} == {
        ROWS_KERNEL.get(cell, "_step_impl_decode_rows_flash_decode"),
        CHUNK_KERNEL.get(cell, "_step_impl_prompt_chunk_flash_decode")}


@pytest.mark.parametrize("rows_alone", [False, True],
                         ids=["mixed", "rows_alone"])
def test_olmo_programs_call_the_two_state_kernels_a_linear_layer(
        mixed_engines, rows_alone):
    """One linear layer at the cut depth: the decode rows' step and the
    chunk part's walk (the rows-alone program's stub of it too) once each a
    layer, under their program part's names."""
    eng = mixed_engines[OLMO]
    fn = eng._rows_fn if rows_alone else eng._step_fn
    calls = kernel_calls(lowered(fn.python_fn, eng._lint_args()))
    assert {k: n for k, n in calls.items() if "gated_delta" in k} == {
        "_step_impl_decode_rows_gated_delta_step": 1,
        "_step_impl_prompt_chunk_gated_delta_chunk": 1}


@pytest.mark.parametrize("rows_alone", [False, True],
                         ids=["mixed", "rows_alone"])
@pytest.mark.parametrize("cell", list(MIXED))
def test_mixed_program_compiles_for_v5e(one_chip, mixed_engines, cell,
                                        rows_alone):
    """The mixed step program, and the rows-alone one a chunk-free tick
    runs, under the same bounds."""
    eng = mixed_engines[cell]
    params, cache, *operands = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype), eng._lint_args())
    fn = eng._rows_fn if rows_alone else eng._step_fn
    compiled = jax.jit(fn.python_fn, donate_argnums=(1,)).lower(
        params, cache, *operands).compile()
    text = compiled.as_text()
    assert text.count("_step_impl_token_pass_moe_experts") >= 3 * MIXED[cell]
    assert CHUNK_KERNEL.get(
        cell, "_step_impl_prompt_chunk_flash_decode") in text
    assert ROWS_KERNEL.get(
        cell, "_step_impl_decode_rows_flash_decode") in text
    print(cell, "temporaries", compiled.memory_analysis().temp_size_in_bytes)

    def largest(tree):
        return max(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))
    temp = compiled.memory_analysis().temp_size_in_bytes
    # no temporary of a weight's size (LFM2's 260 MB are the sampling
    # epilogue's sort branch over 320 x 65,536 logits, as at the parent),
    # nor of the pool's (the tool cuts LFM2's pool to 129 blocks: there the
    # weights' bound is the tighter one)
    assert temp < largest(params)
    assert temp < max(largest(cache), largest(params))
    # and the pool stays the one buffer it came in: no copy of its shape
    # (control flow around a pass of depth copies it: ``aot_full_depth.py``)
    pool = max(jax.tree_util.tree_leaves(cache),
               key=lambda x: x.size * x.dtype.itemsize)
    shape = ",".join(map(str, pool.shape))
    assert not re.search(rf"\[{shape}\]\S* copy\(", text), shape
