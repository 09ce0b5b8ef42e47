"""The flash-decode kernel's share of its roofline over the DECODE ROWS of
the traced ticks, counted with the window: a window layer need read only
the last ``sliding_window`` positions of a row, a global layer all of them
(``flops_bytes_afmoe.decode_rows_attention``, from each tick's live depth
as the harness stamps it and the window-dead positions the program counts),
over the device time of the decode rows' kernel
(``_step_impl_decode_rows_flash_decode``; the prompt chunk's calls have
their own name and are left out on both sides).  Memory-bound, as
``kernel.decode_attn_roofline``; that metric's byte count knows no window
and its pattern would take in the grouped product, so this cell has this
reader instead."""

import re

from benchmark.harness import flops_bytes, flops_bytes_afmoe

KERNEL = re.compile(r"^pallas:_step_impl_decode_rows_flash_decode:")


def read(run):
    counted = (run.get("counters") or {}).get("trace")
    tr = run["trace"]
    seconds = sum(sec for key, (sec, _) in tr["ops"].items()
                  if KERNEL.search(key))
    if not seconds or not counted:
        return None
    least = 0.0
    for (_, _, occupancy, depth), dead in counted["dead_by_tick"]:
        if not occupancy or dead is None:
            continue
        flops, nbytes = flops_bytes_afmoe.decode_rows_attention(
            run["config"], occupancy, depth, dead)
        least += flops_bytes.roofline_seconds(flops, nbytes,
                                              run["peaks"])[0]
    return 100.0 * least / seconds
