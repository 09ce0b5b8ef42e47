"""``kernel.decode_attn_roofline`` over the decode rows' kernel alone: the
engine names the flash-decode call of a step program's decode rows
``_step_impl_decode_rows_flash_decode`` and the prompt chunk's
``_step_impl_prompt_chunk_flash_decode`` (``ops._dispatch.program_part``),
so in the chunked cell the decode rows' needs are divided by the decode
rows' kernel time and not by both kernels'.  The same reader, given the
narrower pattern; memory-bound, as there."""

import re

from benchmark.harness import manifest as mf

KERNEL = re.compile(r"^pallas:_step_impl_decode_rows_flash_decode:")


def read(run):
    both = mf.load_metric("kernel.decode_attn_roofline")    # a fresh module
    both.KERNEL = KERNEL
    return both.read(run)
