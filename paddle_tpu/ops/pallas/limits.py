"""Shared Pallas kernel limits — ONE source of truth for the shape
bounds the kernels enforce, the dispatch rules gate on, and the static
kernel pre-flight (paddle_tpu/static_analysis/kernel_rules.py) checks.

Before ISSUE 14 these literals lived three times: as
``NotImplementedError`` gates inside each kernel, as hard-coded numbers
in ``ops.attention.decode_attention_path``'s dispatch decision, and as
folklore in docstrings.  A drift between any two of them is a silent
routing bug — dispatch sends a shape the kernel rejects (runtime
NotImplementedError on the serving hot path) or refuses a shape the
kernel handles (perf left on the floor).  Deriving all three sites from
this module makes the drift impossible, and the registry's
dispatch-agreement lint (``kernel_rules.dispatch_agreement_findings``)
sweeps a shape lattice to prove dispatch and kernel still agree.

The values themselves are TPU architecture facts, not tunables:

  * ``LANES`` — the VPU/MXU lane width; last-dim tiles and KV chunk
    lengths must be 128-aligned for a chunk to be one clean DMA;
  * ``SUBLANES`` — the second-minor register-tile height per dtype
    ((8, 128) f32, (16, 128) bf16, (32, 128) int8): blocks whose
    second-minor dim is not a multiple waste sublane occupancy unless
    the kernel pads explicitly;
  * ``MAX_Q_ROWS`` — the per-tile s·G row cap of the flash-decode
    kernel's q tiling (one MXU-rows-worth of grouped queries);
  * ``MAX_Q_LEN`` — beyond this a q is whole-prefill-shaped and belongs
    to the flash kernel, not the cached-decode path;
  * ``MAX_HEAD_DIM`` — two lane tiles; larger heads blow the per-head
    VMEM scratch budget of the decode kernels;
  * ``MAX_GEMM_ROWS`` — the int8 weight-only matmul is decode-shaped
    (batch·seq rows stay tiny); training-size GEMMs belong to XLA;
  * ``MAX_SCALE_TABLE`` — entries (rows · chunks · kv-heads) in ONE of
    the int8 decode kernel's two f32 scale tables.  They ride in SMEM
    as scalar prefetch beside the block table; Mosaic for a v5e took
    65,536-entry tables and refused 131,072 (AOT compile, PR 21);
  * ``GMM_TILE_VALUES`` / ``GMM_VMEM_LIMIT`` — the grouped matmul's
    (``grouped_matmul.py``) weight tile, in values, and the scoped VMEM
    it asks Mosaic for: the tile is the unit of the weights' HBM stream
    and Pallas holds two of them, so the limit is twice the tile in bf16
    plus the row tiles and the f32 accumulator, with room.
"""

from __future__ import annotations

LANES = 128          # VPU lane width / minimal last-dim tile
MAX_Q_ROWS = 64      # flash-decode per-tile s·G row cap
MAX_Q_LEN = 2048     # q longer than any prefill chunk => flash kernel
MAX_HEAD_DIM = 256   # decode-attention head_dim ceiling (2 lane tiles)
MAX_GEMM_ROWS = 256  # int8_matmul row ceiling (decode-shaped GEMMs)
MAX_SCALE_TABLE = 65536  # int8 decode: per-table SMEM scale entries
GMM_TILE_VALUES = 3 * 1024 * 1024   # grouped matmul: values of a weight tile
GMM_VMEM_LIMIT = 32 * 1024 * 1024   # grouped matmul: scoped VMEM, bytes

# second-minor register-tile height by dtype name (jnp dtype .name)
SUBLANES = {
    "float32": 8,
    "int32": 8,
    "bfloat16": 16,
    "float16": 16,
    "int8": 32,
}


def sublanes(dtype_name: str) -> int:
    """Sublane tile height for a dtype name; unknown dtypes get the f32
    tile (the most permissive check)."""
    return SUBLANES.get(str(dtype_name), 8)
