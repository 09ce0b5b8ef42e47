"""Record the small trace that ``test_engine_spans.py`` reads (run on the
chip, by hand, when the profiler's format or the engine's spans change):

    python benchmark/tests/record_engine_trace.py <out_dir>

Two tiny paged engines at Llama shape (hidden 512, 2 layers, 4 q / 2 kv
heads of 128, bf16; max_length 4096 so that attention takes the flash-decode
kernel), one after the other inside one ``bench.window``: wave prefill, then
chunked (5 and 6 ticks).  Every tick is a ``bench.step`` span with a 5 ms
``bench.wait`` sleep after it, as the harness's loop has them, so the trace
holds the engine's ``serving.*`` spans on the host plane beside the device's
events.  Commit it gzipped (a trace carries its programs' HLO): ``gzip -9 -c
<out_dir>/plugins/profile/*/*.xplane.pb > engine_trace.xplane.pb.gz``.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                                       # noqa: E402
import numpy as np                               # noqa: E402
from jax.profiler import TraceAnnotation         # noqa: E402


def drive(eng, prompts, new_tokens, ticks):
    for p in prompts:
        eng.submit(p, max_new_tokens=new_tokens)
    for _ in range(ticks):
        with TraceAnnotation("bench.step"):
            eng.step()
        with TraceAnnotation("bench.wait"):
            time.sleep(0.005)


def main(out_dir):
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=1024, hidden_size=512, intermediate_size=1024,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=4096, dtype="bfloat16"))
    model.eval()
    rng = np.random.default_rng(0)

    def prompts():        # new tokens each time: no prefix hit, one bucket
        return [rng.integers(1, 1024, n).astype(np.int32)
                for n in (40, 300, 90)]

    pool = dict(num_slots=4, max_length=4096, paged=True, block_len=128,
                num_blocks=65)
    wave = ServingEngine(model, seed=0, **pool)
    chunked = ServingEngine(model, seed=0, chunked=True, prefill_chunk=256,
                            **pool)
    for eng in (wave, chunked):                  # compile outside the trace
        drive(eng, prompts(), 3, 1)
        eng.drain()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # spans only: keeps the file small
    jax.profiler.start_trace(out_dir, profiler_options=options)
    with TraceAnnotation("bench.window"):
        drive(wave, prompts(), 16, 5)
        drive(chunked, prompts(), 16, 6)
    jax.profiler.stop_trace()
    wave.drain()
    chunked.drain()


if __name__ == "__main__":
    main(sys.argv[1])
