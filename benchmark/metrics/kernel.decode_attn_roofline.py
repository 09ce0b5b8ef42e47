"""The flash-decode kernel's share of its roofline in the traced part of the
window: the least time the chip could take for the attention the traced
ticks needed (``flops_bytes.cached_attention`` at each tick's live depth,
every layer), over the device time of the kernel's events.  Memory-bound at
every depth here (4 FLOPs a byte of K and V against the chip's 240), so the
least time is bytes over 819 GB/s.

The kernel is a Mosaic custom call that carries no name of its own: the
trace names it after the jitted step program it sits in, and
``trace_reduce.op_key`` turns that into ``pallas:<step program>:<shape>``
(pattern below, read off a trace by hand, PR 23: the step programs hold no
other Pallas call).  In the chunked cell the same kernel also serves the
prompt chunk's queries; their pairs and positions are not stamped by the
harness, so there the share counts the decode rows' needs only against the
whole kernel time and reads low by the chunk's part.
"""

import re

from benchmark.harness import flops_bytes

KERNEL = re.compile(r"^pallas:_\w*step_impl\w*:")


def read(run):
    tr = run["trace"]
    seconds = sum(sec for key, (sec, _) in tr["ops"].items()
                  if KERNEL.search(key))
    if not seconds:
        return None
    lo, hi = run["trace_slice"]
    cfg = run["config"]
    least = 0.0
    for _, t_after, occupancy, depth in run["ticks"]:
        if lo <= t_after <= hi and occupancy:
            flops, nbytes = flops_bytes.cached_attention(
                cfg, depth, depth, occupancy)
            least += cfg["num_hidden_layers"] * flops_bytes.roofline_seconds(
                flops, nbytes, run["peaks"])[0]
    return 100.0 * least / seconds
