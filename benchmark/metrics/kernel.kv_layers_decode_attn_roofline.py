"""The flash-decode kernel's share of its roofline over the DECODE ROWS of
the traced ticks, for a model in which only some layers hold K and V: the
least time the chip could take to read K and V of the rows' live depths
once a K/V LAYER (counted from the configuration's ``layer_types``:
``flops_bytes_lfm2.decode_rows_attention``, from each tick's occupancy and
live depth as the harness stamps them), q in and the output out, over the
device time of the decode rows' kernel
(``_step_impl_decode_rows_flash_decode``; the prompt chunk's calls have
their own name and are left out on both sides).  Memory-bound at every
depth (4 FLOPs a byte of K and V against the chip's 240).
``kernel.decode_attn_roofline`` multiplies by ``num_hidden_layers`` and
would read four times over where 6 layers of 24 hold K/V, so that cell has
this reader instead."""

import re

from benchmark.harness import flops_bytes, flops_bytes_lfm2

KERNEL = re.compile(r"^pallas:_step_impl_decode_rows_flash_decode:")


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
            flops, nbytes = flops_bytes_lfm2.decode_rows_attention(
                run["config"], occupancy, depth)
            least += flops_bytes.roofline_seconds(flops, nbytes,
                                                  run["peaks"])[0]
    return 100.0 * least / seconds if least else None
