"""The gated delta rule's STEP kernel's share of its roofline over the
decode rows of the traced ticks: the least time the chip could take to read
and write the OCCUPIED rows' matrix state ``S`` once a linear layer (d_k x
d_v float32 a head, unpadded) plus q, k, v, beta, g in and o out
(``flops_bytes_olmo_hybrid.gated_delta_step``, from each tick's occupancy
as the harness stamps it, over the configuration's linear layers), over the
device time of the decode rows' kernel
(``_step_impl_decode_rows_gated_delta_step``).  Memory-bound (7 FLOPs a
state value of 8 bytes moved, against the chip's 240 a byte).  The count
does not depend on how the kernel is written: rows no request holds, a
padded layout or a second pass over ``S`` would each show as a lower share.
None against a program without the kernel."""

import re

from benchmark.harness import flops_bytes, flops_bytes_olmo_hybrid

KERNEL = re.compile(r"^pallas:_step_impl_decode_rows_gated_delta_step:")


def read(run):
    seconds = sum(sec for key, (sec, _) in run["trace"]["ops"].items()
                  if KERNEL.search(key))
    if not seconds or not run.get("trace_slice"):
        return None
    lo, hi = run["trace_slice"]
    least = 0.0
    for _, t_after, occupancy, _ in run["ticks"]:
        if lo <= t_after <= hi and occupancy:
            flops, nbytes = flops_bytes_olmo_hybrid.gated_delta_step(
                run["config"], occupancy)
            least += flops_bytes.roofline_seconds(flops, nbytes,
                                                  run["peaks"])[0]
    return 100.0 * least / seconds if least else None
