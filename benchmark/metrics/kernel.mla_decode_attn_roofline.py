"""The latent flash-decode kernel's share of its roofline over the DECODE
ROWS of the traced ticks: the least time the chip could take for the work
(``flops_bytes_mla.decode_rows_attention``: every DISTINCT live position's
entry read once a layer — rows that share a document need it once — against
every (row, position, head) scored and summed; the larger of the two times)
over the device time of the rows' kernel
(``_step_impl_decode_rows_latent_flash_decode``; the prompt chunk's calls
have their own name and are left out on both sides).  The distinct
positions and the summed depths are the program's own, from the
``rows_positions`` and ``rows_depth`` args of the ``serving.decode`` spans
that lie in the traced slice.  A walk that reads a shared document once a
ROW reads well under 100: that is the finding, not a fault.  None against a
program without the kernel or the args."""

import re

from benchmark.harness import engine_spans, flops_bytes, flops_bytes_mla

KERNEL = re.compile(r"^pallas:_step_impl_decode_rows_latent_flash_decode:")


def read(run):
    tr = run["trace"]
    seconds = sum(sec for key, (sec, _) in tr["ops"].items()
                  if KERNEL.search(key))
    if not seconds or not run.get("trace_slice"):
        return None
    ticks = engine_spans.ring_spans(dict(run, window=run["trace_slice"]),
                                    "serving.decode")
    if not ticks or any("rows_positions" not in a for _, a in ticks):
        return None
    least = 0.0
    for _, a in ticks:
        flops, nbytes = flops_bytes_mla.decode_rows_attention(
            run["config"], a["rows_positions"], a["rows_depth"])
        least += flops_bytes.roofline_seconds(flops, nbytes,
                                              run["peaks"])[0]
    return 100.0 * least / seconds if least else None
