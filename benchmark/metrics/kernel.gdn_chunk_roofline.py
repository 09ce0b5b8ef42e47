"""The gated delta rule's CHUNK kernel's share of its roofline over the
prompt chunks of the traced ticks: the greater of the least compute time
and the least byte time of the chunked form's walk of the carried state
(``flops_bytes_olmo_hybrid.gated_delta_chunk``: the four products a
sub-chunk of 64 and head that read or write ``S``, counted once at the
chip's bfloat16 peak, and ``S`` read and written once a layer with the
walk's operands) for each traced tick's REAL chunk tokens (the program's
counter ``serving.prefill_chunk_tokens``, read a tick by the runner), over
the device time of the chunk part's kernel
(``_step_impl_prompt_chunk_gated_delta_chunk``) — the rows-alone program's
stub calls of it, which walk no token, are in the time and add nothing to
the count.  What runs before the walk without the state (the triangular
solve and the WY operands, XLA fusions) is in neither.  None against a
program without the kernel or a run whose traced ticks carried no chunk."""

import re

from benchmark.harness import flops_bytes, flops_bytes_olmo_hybrid

KERNEL = re.compile(r"^pallas:_step_impl_prompt_chunk_gated_delta_chunk:")


def read(run):
    seconds = sum(sec for key, (sec, _) in run["trace"]["ops"].items()
                  if KERNEL.search(key))
    tokens = (run.get("counters") or {}).get("chunk_tokens")
    if not seconds or not tokens:
        return None
    least = 0.0
    for n in tokens:
        if n:
            flops, nbytes = flops_bytes_olmo_hybrid.gated_delta_chunk(
                run["config"], n)
            least += flops_bytes.roofline_seconds(flops, nbytes,
                                                  run["peaks"])[0]
    return 100.0 * least / seconds if least else None
