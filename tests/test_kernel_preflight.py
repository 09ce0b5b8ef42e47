"""Kernel pre-flight tests (ISSUE 14): offender + clean case per rule,
the VMEM hand-computation cross-check, dispatch agreement, the
engine-layout guard sweep, and the ``--kernels`` CLI contract."""

import json

import pytest

import numpy as np

from paddle_tpu.flags import flag
from paddle_tpu.ops.pallas import limits as _limits
from paddle_tpu.ops.pallas.decode_attention import (group_blocks,
                                                    live_block_range,
                                                    q_tiles, walk_counts)
from paddle_tpu.static_analysis import kernel_registry as kr
from paddle_tpu.static_analysis import kernel_rules as krl


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# registry sanity + clean baseline
# ---------------------------------------------------------------------------

def test_registered_kernels_preflight_clean():
    """Every Pallas kernel entry point ships a registered spec, and the
    whole registry lints clean under the default rule set."""
    specs = kr.registered_kernel_specs()
    assert len(specs) >= 9
    ops = {s.op for s in specs}
    assert {"decode_attention", "flash_attention", "int8_matmul",
            "rms_norm"} <= ops
    assert krl.analyze_kernels(specs) == []


def test_kernel_report_shape():
    spec = kr.registered_kernel_specs()[0]
    rep = krl.kernel_report(spec)
    assert set(rep) == {"op", "variant", "vmem_bytes", "streamed_bytes",
                        "findings"}
    assert rep["vmem_bytes"] > 0 and rep["streamed_bytes"] > 0
    assert rep["findings"] == []


# ---------------------------------------------------------------------------
# kernel-vmem: double-buffered footprint vs the per-core budget
# ---------------------------------------------------------------------------

def test_vmem_rule_offender_and_clean():
    # a 64K-token contiguous cache streamed as ONE chunk: the K/V
    # blocks alone dwarf any VMEM
    fat = kr.decode_attention_spec(8, 1, 32, 8, 128, kv_len=1 << 16,
                                   block_kv=1 << 16)
    findings = krl.KernelVmemRule().run(fat)
    assert _rules_of(findings) == ["kernel-vmem"]
    assert findings[0].bytes == kr.vmem_footprint(fat)
    assert findings[0].bytes > int(flag("kernel_lint_vmem_bytes"))
    # raising the budget clears it; the default-geometry spec is clean
    assert krl.KernelVmemRule(budget_bytes=1 << 40).run(fat) == []
    ok = kr.decode_attention_spec(8, 1, 32, 8, 128, kv_len=8192)
    assert krl.KernelVmemRule().run(ok) == []


def test_vmem_estimate_matches_hand_computed_tile_sum():
    """ISSUE 14 acceptance: the q-tiled paged decode estimate equals
    the hand-computed tile sum (Pallas's double-buffered q and out, the
    body's own K and V group buffers, the accumulators) within the lint
    tolerance."""
    b, s, hq, hkv, d = 1, 256, 32, 8, 128
    bl, mb = 128, 64
    spec = kr.decode_attention_spec(b, s, hq, hkv, d, block_len=bl,
                                    max_blocks=mb)
    g = hq // hkv                                   # 4 q heads per kv head
    bq = min(s, max(1, _limits.MAX_Q_ROWS // g))    # 16 q rows per tile
    tile_p = max(8, -(-bq * g // 8) * 8)            # 64 padded q rows
    q_tile = 1 * hkv * tile_p * d * 2               # bf16
    kv_tile = 1 * bl * (hkv * d) * 2                # bf16
    gb = group_blocks(bl)                           # 4 blocks a copy group
    scratch = (hkv * tile_p * d) * 4 \
        + 2 * (hkv * tile_p * _limits.LANES) * 4    # f32 acc + m/l rows
    # q and out are Pallas's (double-buffered); K and V stay in HBM and the
    # body copies groups of them into its OWN two buffers each
    manual = 2 * (2 * gb * kv_tile)
    hand = 2 * (2 * q_tile) + manual + scratch
    got = kr.vmem_footprint(spec)
    assert abs(got - hand) <= flag("graph_lint_hbm_tol") * hand
    assert got == hand                              # the model is exact here
    # the manual operands add nothing beside the buffers they are copied to
    assert {o.name for o in spec.operands if o.manual} == {"k", "v"}
    assert spec.scratch[:2] == (((2, gb * bl, hkv * d), "bfloat16"),) * 2
    assert spec.grid == (b, -(-s // bq))            # no table axis


# ---------------------------------------------------------------------------
# kernel-bounds: abstract interpretation + dead-tail clamp corners
# ---------------------------------------------------------------------------

def _mini_table_spec(mode):
    """4-chunk paged mini-kernel: a block-table dereference whose clamp
    is correct ('clamped'), missing ('unclamped'), or too aggressive
    ('overclamped')."""
    chunks, n_pool = 4, 10
    pos = kr.ScalarOperand("pos", (1,), 0, 5)       # last written position
    bt = kr.ScalarOperand("bt", (chunks,), 0, n_pool - 1)

    def expected(p, q):     # last live column dereferenced at (p, q)
        return min(q, p // 2)

    def idx(grid, env):
        (q_iv,) = grid
        last = env.lookup("pos", kr.iv(0)) // 2
        if mode == "clamped":
            col = kr.iv_min(q_iv, last)
        elif mode == "unclamped":
            col = q_iv                              # dead tail streams
        else:                                       # overclamped
            col = kr.iv_min(q_iv, last // 2)        # truncates live KV
        bid = env.lookup("bt", col)
        return (bid, kr.iv(0), kr.iv(0))

    op = kr.BlockOperand("k", (1, 2, 128), (n_pool, 2, 128), "bfloat16",
                         idx, clamp=kr.ClampCheck("bt", "pos", 0,
                                                  expected))
    return kr.KernelSpec(op="mini_paged", variant=mode, grid=(chunks,),
                         operands=(op,), scalars=(pos, bt))


def test_bounds_clamp_clean():
    assert krl.KernelBoundsRule().run(_mini_table_spec("clamped")) == []


def test_bounds_unclamped_dead_tail_offender():
    findings = krl.KernelBoundsRule().run(_mini_table_spec("unclamped"))
    assert findings and _rules_of(findings) == ["kernel-bounds"]
    assert any("unclamped table dereference" in f.message
               and "alias pad data" in f.message for f in findings)


def test_bounds_overclamped_offender():
    findings = krl.KernelBoundsRule().run(_mini_table_spec("overclamped"))
    assert findings and _rules_of(findings) == ["kernel-bounds"]
    assert any("over-clamped" in f.message
               and "silently truncated" in f.message for f in findings)


def test_bounds_grid_overrun_and_scalar_oob_offenders():
    sc = kr.ScalarOperand("tbl", (4,), 0, 3)

    def idx(grid, env):
        (i,) = grid
        env.lookup("tbl", i + 2)                    # reaches 5 on a (4,)
        return (i * 4, kr.iv(0))                    # reaches 12 of [0, 9]

    op = kr.BlockOperand("x", (1, 128), (10, 128), "bfloat16", idx)
    spec = kr.KernelSpec(op="mini_oob", variant="offender", grid=(4,),
                         operands=(op,), scalars=(sc,))
    findings = krl.KernelBoundsRule().run(spec)
    msgs = " | ".join(f.message for f in findings)
    assert "outside block range" in msgs
    assert "scalar-prefetch 'tbl'" in msgs and "outside shape" in msgs


def test_paged_decode_spec_bounds_clean_across_scalar_domain():
    """The real q-tiled paged decode index maps (spec mirrors the
    kernel verbatim) stay in-bounds and correctly clamped over the
    whole pos/block-table domain."""
    spec = kr.decode_attention_spec(4, 1, 32, 8, 128, block_len=128,
                                    max_blocks=8)
    assert krl.KernelBoundsRule().run(spec) == []
    chunked = kr.decode_attention_spec(1, 256, 32, 8, 128, block_len=128,
                                       max_blocks=64)
    assert krl.KernelBoundsRule().run(chunked) == []


def test_streamed_bytes_are_the_live_ranges():
    """The body copies the blocks of each q tile's walk and no other: a
    decode call at the deepest position reads every block once, a window
    layer's the window's blocks, a chunk's each tile's range again."""
    block = 128 * (8 * 128) * 2                      # one bf16 K or V block
    q_io = lambda sp: sum(o.fetches * o.block_bytes()      # noqa: E731
                          for o in sp.operands if not o.manual)
    rows = kr.decode_attention_spec(4, 1, 32, 8, 128, block_len=128,
                                    max_blocks=8)
    assert kr.streamed_bytes(rows) - q_io(rows) == 4 * 8 * 2 * block
    win = kr.decode_attention_spec(4, 1, 32, 8, 128, block_len=128,
                                   max_blocks=8, window=256)
    # positions 768..1023 of the deepest row: blocks 6 and 7
    assert kr.streamed_bytes(win) - q_io(win) == 4 * 2 * 2 * block
    chunk = kr.decode_attention_spec(1, 256, 32, 8, 128, block_len=128,
                                     max_blocks=8)
    bq, nq = q_tiles(256, 4)
    tiles = sum((1024 - 256 + min((q + 1) * bq, 256) - 1) // 128 + 1
                for q in range(nq))                  # 7 or 8 blocks a tile
    assert kr.streamed_bytes(chunk) - q_io(chunk) == tiles * 2 * block


def _walk_spec(mode):
    """One row, one q tile, a 4-column table and a window of 2 blocks of 2
    positions: the body's walk mirrored right ('walk'), started one column
    early ('early') or late ('late')."""
    chunks, n_pool, bk, window = 4, 10, 2, 4
    pos = kr.ScalarOperand("pos", (1,), 0, 7)
    bt = kr.ScalarOperand("bt", (1, chunks), 0, n_pool - 1)

    def bounds(p, q):
        first, last = live_block_range(np.int64(p), np.int64(q), s=1, bq=1,
                                       bk=bk, n_cols=chunks, window=window,
                                       xp=np)
        return int(first), int(last)

    def idx(grid, env):
        p = env.lookup("pos", kr.iv(0))
        last = kr.iv_min(p // bk, chunks - 1)
        first = kr.iv_min(kr.iv_max(p - (window - 1), 0) // bk, last)
        if mode == "early":
            first = kr.iv_max(first - 1, 0)
        elif mode == "late":
            first = kr.iv_min(first + 1, last)
        col = kr.iv_min(first + kr.Iv(0, chunks), last)
        return (env.lookup("bt", kr.iv(0), col), kr.iv(0), kr.iv(0))

    op = kr.BlockOperand(
        "k", (1, bk, 128), (n_pool, bk, 128), "bfloat16", idx, manual=True,
        clamp=kr.ClampCheck("bt", "pos", 1, lambda p, q: bounds(p, q)[1],
                            expected_first=lambda p, q: bounds(p, q)[0]))
    return kr.KernelSpec(op="mini_walk", variant=mode, grid=(1, 1),
                         operands=(op,), scalars=(pos, bt))


def test_bounds_hold_both_ends_of_the_walk():
    """At a pinned position the largest AND the smallest table column the
    body dereferences are the walk's last and first block."""
    assert krl.KernelBoundsRule().run(_walk_spec("walk")) == []
    early = krl.KernelBoundsRule().run(_walk_spec("early"))
    assert any("not at the walk's first block" in f.message
               and "no query sees" in f.message for f in early)
    late = krl.KernelBoundsRule().run(_walk_spec("late"))
    assert any("not at the walk's first block" in f.message
               and "silently truncated" in f.message for f in late)
    # the real spec declares both ends, from the kernel's own function
    spec = kr.decode_attention_spec(4, 1, 48, 8, 128, block_len=128,
                                    max_blocks=64, window=4096)
    clamp = next(o.clamp for o in spec.operands if o.name == "k")
    assert (clamp.expected_first(5000, 0), clamp.expected(5000, 0)) == (
        (5000 - 4095) // 128, 5000 // 128)
    assert krl.KernelBoundsRule().run(spec) == []


def test_host_walk_counts_agree_with_the_mask():
    """``walk_counts`` — what the engine's spans carry — and
    ``live_block_range`` — what the kernel's loop runs over — against the
    mask's own definition on random positions: the blocks of a q tile's
    walk are exactly those from the first to the last that hold a key some
    query of the tile may see."""
    rng = np.random.default_rng(0)
    for _ in range(200):
        bk = int(rng.choice([8, 16, 128]))
        s = int(rng.choice([1, 3, 5, 40]))
        n_cols = int(rng.integers(-(-s // bk), 12))   # the table holds s
        g = int(rng.choice([1, 4, 6]))
        window = [None, int(rng.integers(1, 4 * bk))][int(rng.integers(2))]
        b = int(rng.integers(1, 5))
        pos = rng.integers(0, n_cols * bk - s + 1, b)
        bq, nq = q_tiles(s, g)
        need = walk = 0
        for p in pos:
            for qi in range(nq):
                seen = set()
                for i in range(qi * bq, min((qi + 1) * bq, s)):
                    lo = 0 if window is None else max(p + i - window + 1, 0)
                    seen.update(range(lo // bk, (p + i) // bk + 1))
                first, last = live_block_range(
                    np.int64(p), np.int64(qi), s=s, bq=bq, bk=bk,
                    n_cols=n_cols, window=window, xp=np)
                assert (first, last) == (min(seen), max(seen))
                need += last - first + 1
                gb = group_blocks(bk)
                walk += -(-(last - first + 1) // gb) * gb
        assert walk_counts(pos, s, g, bk=bk, n_cols=n_cols,
                           window=window) == (need, walk)
    # a row parked past the cache (the contiguous cursor engine's idle
    # rows) stays inside the table
    assert live_block_range(np.int64(64), np.int64(0), s=1, bq=1, bk=8,
                            n_cols=8, window=16, xp=np) == (6, 7)
    assert live_block_range(np.int64(640), np.int64(0), s=1, bq=1, bk=8,
                            n_cols=8, window=16, xp=np) == (7, 7)


# ---------------------------------------------------------------------------
# kernel-align: tiling / lanes / sublanes
# ---------------------------------------------------------------------------

def test_align_misaligned_head_dim_offender():
    # d=64 with hkv=2 folded into the last dim: per-head slices
    # straddle 128-lane tiles
    spec = kr.decode_attention_spec(4, 1, 8, 2, 64, block_len=128,
                                    max_blocks=8)
    findings = krl.KernelAlignRule().run(spec)
    assert any("misaligned head_dim" in f.message for f in findings)


def test_align_tiling_and_sublane_offenders():
    def idx(grid, env):
        (i,) = grid
        return (i, kr.iv(0), kr.iv(0))

    bad = kr.BlockOperand("w", (1, 12, 192), (3, 24, 576), "bfloat16",
                          idx)
    spec = kr.KernelSpec(op="mini_align", variant="offender", grid=(3,),
                         operands=(bad,))
    msgs = " | ".join(f.message for f in krl.KernelAlignRule().run(spec))
    assert "not a multiple of 128 lanes" in msgs          # 192 % 128
    assert "sublane tile 16" in msgs                      # 12 % 16, bf16


def test_align_block_divisibility_offender():
    def idx(grid, env):
        return (kr.iv(0), kr.iv(0))

    bad = kr.BlockOperand("w", (3, 128), (10, 128), "bfloat16", idx)
    spec = kr.KernelSpec(op="mini_align", variant="offender2", grid=(1,),
                         operands=(bad,))
    msgs = " | ".join(f.message for f in krl.KernelAlignRule().run(spec))
    assert "block 3 does not tile array dim 10" in msgs


def test_align_scale_tables_ride_scalar_prefetch():
    """Mosaic refuses a (1, hkv) block of a (num_blocks, hkv) array —
    the int8 kernel's first form (PR 13) never lowered for a TPU.  The
    scale tables are scalar-prefetch operands now, and the sublane lint
    flags the 1-row block it used to exempt."""
    spec = next(s for s in kr.registered_kernel_specs()
                if s.dims.get("quantized") and s.dims.get("paged"))
    assert not [o for o in spec.operands if "scale" in o.name]
    assert {"k_scale", "v_scale"} <= {s.name for s in spec.scalars}
    assert krl.KernelAlignRule().run(spec) == []

    def idx(grid, env):
        (i,) = grid
        return (i, kr.iv(0))

    row = kr.BlockOperand("k_scale", (1, 8), (513, 8), "float32", idx)
    bad = kr.KernelSpec(op="mini_align", variant="scale-row", grid=(513,),
                        operands=(row,))
    msgs = " | ".join(f.message for f in krl.KernelAlignRule().run(bad))
    assert "second-minor block dim 1" in msgs


# ---------------------------------------------------------------------------
# kernel-scale-granule: int8 scale layout vs KV chunking
# ---------------------------------------------------------------------------

def test_scale_granule_offender_and_clean():
    bad = kr.decode_attention_spec(8, 1, 32, 8, 128, kv_len=8192,
                                   quantized=True, n_granules=48)
    findings = krl.KernelScaleGranuleRule().run(bad)
    msgs = " | ".join(f.message for f in findings)
    assert _rules_of(findings) == ["kernel-scale-granule"]
    assert "!= cache length 8192" in msgs         # 170 x 48 = 8160
    assert "not 128-aligned" in msgs              # 170 % 128
    # the align rule independently flags the lane-hostile granule
    assert any("scale_granule" in f.message
               for f in krl.KernelAlignRule().run(bad))
    ok = kr.decode_attention_spec(8, 1, 32, 8, 128, kv_len=8192,
                                  quantized=True, n_granules=64)
    assert krl.KernelScaleGranuleRule().run(ok) == []
    assert krl.KernelAlignRule().run(ok) == []


# ---------------------------------------------------------------------------
# kernel-stream: the committed int8_serving streamed-bytes bound
# ---------------------------------------------------------------------------

def test_stream_rule_bound_and_offender():
    spec = kr.decode_attention_spec(8, 1, 32, 8, 128, kv_len=8192,
                                    quantized=True, n_granules=64)
    kvb = int(spec.dims["kv_streamed_bytes"])
    bf16 = int(spec.dims["kv_streamed_bytes_bf16_equiv"])
    # the real int8 layout honours the committed claim...
    assert kvb <= krl.STREAM_RATIO_BOUND * bf16
    assert krl.KernelStreamRule().run(spec) == []
    # ...and a hypothetical fatter-scale layout is flagged (no real
    # geometry can offend, so the model numbers are patched directly)
    spec.dims["kv_streamed_bytes"] = int(0.60 * bf16)
    findings = krl.KernelStreamRule().run(spec)
    assert _rules_of(findings) == ["kernel-stream"]
    assert "int8_serving bound" in findings[0].message
    # a relaxed project-level bound clears the same spec
    assert krl.KernelStreamRule(max_ratio=0.7).run(spec) == []


def test_bf16_specs_are_exempt_from_stream_rule():
    spec = kr.decode_attention_spec(8, 1, 32, 8, 128, kv_len=8192)
    assert not spec.dims.get("quantized")
    assert krl.KernelStreamRule().run(spec) == []


# ---------------------------------------------------------------------------
# satellite 1: dispatch <-> kernel agreement
# ---------------------------------------------------------------------------

def test_dispatch_agreement_clean():
    assert krl.dispatch_agreement_findings() == []


def test_dispatch_agreement_offenders(monkeypatch):
    import paddle_tpu.ops.attention as att
    shape = [dict(b=4, s=1, hq=32, hkv=8, d=128, kv_len=4096)]
    # gate refuses a shape the kernel accepts (with a SHAPE reason)
    monkeypatch.setattr(att, "decode_shape_gate",
                        lambda *a, **k: ("xla", "GQA group unsupported"))
    findings = krl.dispatch_agreement_findings(shapes=shape)
    assert any("dispatch refuses a shape the kernel accepts"
               in f.message for f in findings)
    # gate routes to pallas a shape the kernel rejects
    monkeypatch.setattr(att, "decode_shape_gate",
                        lambda *a, **k: ("pallas_decode", ""))
    bad = [dict(b=4, s=1, hq=32, hkv=8, d=300, kv_len=4096)]
    findings = krl.dispatch_agreement_findings(shapes=bad)
    assert any("the kernel spec rejects it" in f.message
               for f in findings)
    # environment refusals are NOT disagreements
    monkeypatch.setattr(att, "decode_shape_gate",
                        lambda *a, **k: ("xla", "cache below "
                                         "decode_attention_min_len"))
    assert krl.dispatch_agreement_findings(shapes=shape) == []


# ---------------------------------------------------------------------------
# satellite 3 guard: every engine layout pre-flights clean, both dtypes
# ---------------------------------------------------------------------------

_LAYOUTS = [
    ("contiguous", {}),
    ("paged", dict(paged=True, block_len=16)),
    ("contiguous+chunked", dict(chunked=True, prefill_chunk=8)),
    ("paged+chunked", dict(paged=True, block_len=16, chunked=True,
                           prefill_chunk=8)),
    ("contiguous+spec", dict(spec_decode=True, spec_k=4)),
    ("paged+spec", dict(paged=True, block_len=16, spec_decode=True,
                        spec_k=4)),
    ("paged+chunked+spec", dict(paged=True, block_len=16, chunked=True,
                                prefill_chunk=8, spec_decode=True,
                                spec_k=4)),
    ("contiguous+chunked+spec", dict(chunked=True, prefill_chunk=8,
                                     spec_decode=True, spec_k=4)),
]


@pytest.fixture(scope="module")
def _tiny_model():
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config

    pt.seed(0)
    model = LlamaForCausalLM(tiny_llama_config())
    model.eval()
    return model


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("name,kw", _LAYOUTS, ids=[n for n, _ in _LAYOUTS])
def test_engine_layouts_preflight_clean(_tiny_model, name, kw, dtype):
    """ISSUE 14 guard: every serving layout the CLI smokes — bf16 AND
    int8 KV — pre-flights with zero kernel findings and a sane budget
    fraction."""
    from paddle_tpu.serving import ServingEngine

    kw = dict(kw)
    if dtype == "int8":
        kw["kv_cache_dtype"] = "int8"
    eng = ServingEngine(_tiny_model, num_slots=2, max_length=64, **kw)
    kp = eng.kernel_preflight()
    assert kp["findings"] == [], (name, dtype, kp["findings"])
    assert kp["kernels"], "preflight must analyze at least one kernel"
    assert 0 < kp["vmem_bytes"] <= kp["vmem_budget_bytes"]
    assert 0 < kp["vmem_budget_frac"] <= 1
    assert kp["streamed_bytes"] > 0
    # memoized under default rules: the lint_step merge reuses it
    assert eng.kernel_preflight() is kp


# ---------------------------------------------------------------------------
# satellite 5: --kernels CLI exits 0, deterministic v4 JSON
# ---------------------------------------------------------------------------

_CLI_ARGV = ["--kernels", "--slots", "2", "--max-length", "64",
             "--block-len", "16", "--prefill-chunk", "8",
             "--spec-k", "4"]


def test_cli_kernels_json_is_versioned_and_deterministic(capsys):
    from paddle_tpu.static_analysis.__main__ import SCHEMA_VERSION, main

    argv = _CLI_ARGV + ["--json"]
    assert main(argv) == 0
    first = capsys.readouterr().out
    blob = json.loads(first)
    assert blob["schema_version"] == SCHEMA_VERSION == 4
    assert blob["total_findings"] == 0
    layouts = blob["layouts"]
    # the registered-kernel sweep rides as its own entry
    reg = layouts["registered_kernels"]
    assert reg["findings"] == [] and len(reg["kernels"]) >= 9
    # every engine layout has an int8-kv twin and a kernel block
    names = set(layouts) - {"registered_kernels"}
    assert {n for n in names if n.endswith("+int8kv")} \
        == {f"{n}+int8kv" for n in names if not n.endswith("+int8kv")}
    for name in names:
        entry = layouts[name]
        assert entry["findings"] == [], name
        kp = entry["kernel_preflight"]
        assert kp["findings"] == [] and kp["vmem_bytes"] > 0, name
        assert 0 < kp["vmem_budget_frac"] <= 1, name
    assert main(argv) == 0
    assert capsys.readouterr().out == first   # byte-identical for CI
