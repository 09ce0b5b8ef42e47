"""The flash-decode kernel's share of its roofline over the BLOCK ROWS of
the traced ticks, for a block-diffusion model: the least time the chip could
take to read K and V of the rows' depths and of each row's block once a
layer (``flops_bytes_sdar.block_rows_attention``, from each tick's occupancy
and live depth as the harness stamps them: a block's four queries share one
read of their keys), q in and the output out, over the device time of the
block rows' kernel (``_step_impl_block_rows_flash_decode``; the prompt
chunk's calls have their own name and are left out on both sides).
Memory-bound (32 FLOPs a byte of K and V at four queries a row against the
chip's 240).  The harness's depth counts a row from its first delivery, so
a row inside its first block adds its kernel time and no bytes: the share
reads low by those rows, about one in a hundred.  None against a program
without the kernel."""

import re

from benchmark.harness import flops_bytes, flops_bytes_sdar

KERNEL = re.compile(r"^pallas:_step_impl_block_rows_flash_decode:")


def read(run):
    tr = run["trace"]
    seconds = sum(sec for key, (sec, _) in tr["ops"].items()
                  if KERNEL.search(key))
    if not seconds or not run.get("trace_slice"):
        return None
    lo, hi = run["trace_slice"]
    least = 0.0
    for _, t_after, occupancy, depth in run["ticks"]:
        if lo <= t_after <= hi and occupancy:
            flops, nbytes = flops_bytes_sdar.block_rows_attention(
                run["config"], occupancy, depth)
            least += flops_bytes.roofline_seconds(flops, nbytes,
                                                  run["peaks"])[0]
    return 100.0 * least / seconds if least else None
