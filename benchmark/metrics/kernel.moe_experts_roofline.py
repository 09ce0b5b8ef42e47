"""The grouped matrix product's share of its roofline in the traced part of
the window: the least time the chip could take for the held experts' three
products over the traced ticks (``flops_bytes_afmoe.grouped_product`` of the
pairs the program's counters say were routed here and of the experts they
touched: an expert no pair chose need not be read), over the device time of
the kernel's events.  Memory-bound at this batch (2 to 4 pairs an expert:
about 3 FLOPs a byte of weights against the chip's 240).

The kernel is named by the program, ``<program part>_moe_experts``
(``ops._dispatch.kernel_name`` under the engine's ``program_part``), so the
pattern takes the decode rows' and the prompt chunk's calls and nothing
else of the step program.  Against a program without the counters (or the
kernel) there is nothing to read.
"""

import re

from benchmark.harness import flops_bytes, flops_bytes_afmoe

KERNEL = re.compile(r"^pallas:_\w*step_impl\w*moe_experts:")


def read(run):
    counted = (run.get("counters") or {}).get("trace")
    tr = run["trace"]
    seconds = sum(sec for key, (sec, _) in tr["ops"].items()
                  if KERNEL.search(key))
    if not seconds or not counted:
        return None
    flops, nbytes = flops_bytes_afmoe.grouped_product(
        run["config"], int(counted["pairs"].sum()),
        counted["experts_touched"])
    return 100.0 * flops_bytes.roofline_seconds(
        flops, nbytes, run["peaks"])[0] / seconds
