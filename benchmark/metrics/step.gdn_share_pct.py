"""Share of the traced window's BUSY device time that the gated delta
rule's two kernels took (the decode rows' step, the prompt chunk's walk and
the rows-alone program's stub of it: every Pallas kernel whose name ends in
``gated_delta_step`` or ``gated_delta_chunk``): how much of a tick the
matrix state costs beside the weights' stream and the attention layers'
K/V.  None against a program without the kernels."""

import re

KERNELS = re.compile(r"^pallas:\w*gated_delta_(step|chunk):")


def read(run):
    tr = run["trace"]
    seconds = sum(sec for key, (sec, _) in tr["ops"].items()
                  if KERNELS.search(key))
    if not seconds or not tr.get("busy_s"):
        return None
    return 100.0 * seconds / tr["busy_s"]
