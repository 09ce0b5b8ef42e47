"""The serve runner for the Olmo-Hybrid architecture: ``serve.run``'s
signature and flow, as ``serve_lfm2.py`` has it, with what binds that
runner to LFM2-MoE replaced.

It IMPORTS everything that is the harness's and not the architecture's —
``serve.drive``, ``serve.measure``, ``serve.kernel_paths``,
``serve.cache_positions``, ``serve.warm_prompt_lengths``,
``check.sample_requests``, ``check.judge``, ``serve_afmoe``'s
``counters_between`` and ``mid_prefill_at_end`` (this cell too is chunked
AND against a backlog), ``serve_lfm2``'s per-slot state rows (``Counted``,
``state_between``) — and brings only:

  * the model's construction (built to be loaded) from
    ``weights_olmo_hybrid.py``; the program's model is looked for FIRST, so
    a program without it fails at once, with an ImportError;
  * the cache's bytes: K and V a position over the FOUR layers of sixteen
    that hold them, and a slot's state: the float32 matrix ``S`` and the
    convolution's window of the twelve linear layers;
  * ``served_gaps`` over ``reference/olmo_hybrid_arch.py`` (the head taken
    of the served rows alone), with the three controls (weights rounded to
    int8; the reference without history: ``S = 0`` and an empty window at
    every token, which is what a program that lost its per-slot state
    computes; the reference with ``S`` kept in bfloat16, which is what a
    program with a narrower state computes);
  * the prompt chunk's real tokens, tick by tick (the program's counter
    ``serving.prefill_chunk_tokens``), for ``kernel.gdn_chunk_roofline``.

``run()`` repeats ``serve_lfm2.run``'s body where it could not be imported:
that function builds its model and calls its reference itself.
"""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import (check, flops_bytes_lfm2,
                               flops_bytes_olmo_hybrid, serve, serve_afmoe,
                               serve_lfm2, stats, traffic,
                               weights_olmo_hybrid)
from benchmark.harness.compile_log import CompileLog
from benchmark.reference import olmo_hybrid_arch

# the reference's sequence is padded to a multiple of PAD_TO (under a causal
# mask and a causal recurrence a tail changes nothing before it) and the
# rows read to a multiple of ROWS_TO, so a cell compiles a few shapes of
# reference program and not one a request
PAD_TO = 1024
ROWS_TO = 256


# -- the model --------------------------------------------------------------

def program_config(cfg, max_positions):
    """The program's config of one configuration file."""
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig
    fields = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "rms_norm_eps", "tie_word_embeddings", "attention_bias",
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "linear_allow_neg_eigval")}
    return OlmoHybridConfig(
        dtype=cfg["dtype"], layer_types=tuple(cfg["layer_types"]),
        initializer_range=float(cfg.get("initializer_range", 0.02)),
        max_position_embeddings=min(int(max_positions),
                                    cfg["max_position_embeddings"]),
        **fields)


def build_model(cfg, seed, max_positions):
    """The program's model holding weights the benchmark made from the
    seed; returns (model, weights under the reference's names).  The
    program's model is looked for FIRST, so that a program without it fails
    at once and not after eight gigabytes of weights."""
    from paddle_tpu.models.olmo_hybrid import OlmoHybridForCausalLM

    from paddle_tpu import nn
    with nn.abstract_parameters():
        model = OlmoHybridForCausalLM(program_config(cfg, max_positions))
    model.eval()
    made = weights_olmo_hybrid.make_weights(cfg, seed, cfg["dtype"])
    missing = model.set_state_dict(
        {weights_olmo_hybrid.program_name(n): w for n, w in made.items()},
        strict=True)
    buffers = {n for n, p in model.named_parameters(include_buffers=True)
               if p.is_buffer}
    if set(missing) - buffers:
        raise KeyError(f"weights not made: {sorted(set(missing) - buffers)}")
    return model, made


# -- the comparison that decides ``correct`` --------------------------------

def served_gaps(made, cfg, prompt, tokens, control_bits=None):
    """``check.served_gaps`` over this architecture's reference: per served
    position, reference-best logit minus the served token's logit.  With
    ``control_bits`` also the same for the token that each control puts
    first: the reference with int-rounded weights, the reference whose
    linear layers see no earlier token, and the reference whose ``S`` is
    bfloat16.  Returns (gaps, {control's prefix: its gaps} or None)."""
    p, t = len(prompt), len(tokens)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(tokens, np.int32)])
    t_read = -(-t // ROWS_TO) * ROWS_TO
    padded = -(-(p - 1 + t_read) // PAD_TO) * PAD_TO
    ids = np.zeros(padded, np.int32)
    ids[:len(full) - 1] = full[:-1]
    nxt = np.zeros(padded, np.int32)
    nxt[:len(full) - 1] = full[1:]
    read = slice(p - 1, p - 1 + t_read)
    ref = olmo_hybrid_arch.logits(made, cfg, ids, rows=read)
    gaps = np.asarray(check._gap_below_best(ref, jnp.asarray(nxt[read])))[:t]
    if control_bits is None:
        return gaps, None

    def first_of(**control):
        low = olmo_hybrid_arch.logits(made, cfg, ids, rows=read, **control)
        first = jnp.argmax(low, axis=-1).astype(jnp.int32)
        return np.asarray(check._gap_below_best(ref, first))[:t]

    return gaps, {"": first_of(weight_bits=control_bits),
                  "no_history.": first_of(history=False),
                  "bf16_state.": first_of(state_dtype="bfloat16")}


# -- the program's counters, tick by tick -----------------------------------

class Counted(serve_lfm2.Counted):
    """``serve_lfm2.Counted`` (the per-slot state's rows a tick) plus, a
    tick, the real prompt tokens its chunk part carried: what the
    program's counter ``serving.prefill_chunk_tokens`` gained."""

    def __init__(self, eng):
        super().__init__(eng)
        from paddle_tpu import observability as obs
        self._chunk_tokens = obs.default_registry().get(
            "serving.prefill_chunk_tokens")
        self._seen = self._read()
        self.chunk = []

    def _read(self):
        fam = self._chunk_tokens
        return sum(c.value() for c in fam.children()) if fam else 0

    def step(self):
        finished = super().step()
        now = self._read()
        self.chunk.append(int(now - self._seen))
        self._seen = now
        return finished


def chunk_tokens_between(chunk, ticks, lo, hi):
    """The chunk part's real tokens of each tick that ended in [lo, hi]."""
    return [c for tk, c in zip(ticks, chunk) if lo <= tk[1] <= hi]


# -- one run ----------------------------------------------------------------

def setup(cell, cfg, mix, seed, seconds, t_start):
    """``serve.setup`` with this architecture's model."""
    from paddle_tpu.serving import ServingEngine

    clock = time.perf_counter
    compiles = CompileLog()
    parts = {"import_s": clock() - t_start}
    t = clock()
    model, made = build_model(cfg, seed, cell["engine"]["max_length"])
    jax.block_until_ready(made)
    parts["model_and_weights_s"] = clock() - t
    parts["memory_peak_after_weights"] = serve_afmoe._memory_peak()
    t = clock()
    eng = ServingEngine(model, seed=int(seed) & 0x7FFFFFFF,
                        **cell["engine"])
    reqs = traffic.generate(mix, cfg["vocab_size"], seed, seconds)
    rng = np.random.default_rng([int(seed), 0x3A3A])
    for n in serve.warm_prompt_lengths(cell, reqs):
        eng.submit(rng.integers(1, cfg["vocab_size"], n).astype(np.int32),
                   max_new_tokens=2)
        eng.drain()
    parts["engine_and_warm_s"] = clock() - t
    parts["compile"] = compiles.drain()
    return eng, made, reqs, compiles, parts


def run(cell, cfg, mix, *, seed, seconds, t_start, say, trace_dir=None,
        control_bits=None):
    """One run of one serve cell of this architecture; the record
    ``serve_lfm2.run`` returns, with ``counters["chunk_tokens"]`` (the
    chunk part's real tokens of each traced tick) and, with
    ``control_bits``, the controls' rows under ``control`` (the no-history
    control's names start with ``no_history.``, the bfloat16-state
    control's with ``bf16_state.``)."""
    clock = time.perf_counter
    counted_before = serve.kernel_paths()
    eng, made, reqs, compiles, parts = setup(cell, cfg, mix, seed, seconds,
                                             t_start)
    eng = Counted(eng)
    stamps = serve.drive(eng, reqs, mix, seconds, trace_dir)
    in_window_compiles = compiles.drain(floor=0.0)
    w0, w1 = stamps["window"]
    parts["ramp_s"] = w0 - stamps["t_zero"]
    memory_peak = serve_afmoe._memory_peak()
    paths = {k: n - counted_before.get(k, 0)
             for k, n in serve.kernel_paths().items()
             if n > counted_before.get(k, 0)}
    step_traces = eng.step_traces
    pool_peak = serve.gauge("kv_cache.peak_blocks_in_use")
    counters = {
        "window": serve_afmoe.counters_between(eng.log, stamps["ticks"],
                                               w0, w1),
        "trace": (serve_afmoe.counters_between(eng.log, stamps["ticks"],
                                               *stamps["trace_slice"])
                  if stamps["trace_slice"] else None),
        "state": serve_lfm2.state_between(eng.state, stamps["ticks"], w0, w1),
        # the chunk part's real tokens of each traced tick
        "chunk_tokens": (chunk_tokens_between(eng.chunk, stamps["ticks"],
                                              *stamps["trace_slice"])
                         if stamps["trace_slice"] else None)}
    finished = [
        {"index": rec.req.index, "prompt": rec.req.prompt,
         "tokens": eng.result(rec.rid), "temperature": rec.req.temperature,
         "in_window": rec.times[-1] >= w0}
        for rec in stamps["order"] if rec.done]
    del eng                     # the pool goes; the reference needs room
    gc.collect()

    m = serve.measure(stamps, mix, seconds)
    failed_by_measure = m["failed"]
    m, mid_prefill = serve_afmoe.mid_prefill_at_end(m, stamps, cell)
    end_to_end = {
        "output_tok_s": m["tokens"] / seconds,
        "token_gap_p95_ms": stats.percentile(m["gaps_ms"], 95),
        "ttft_p95_ms": stats.percentile(m["ttft_ms"], 95),
        "setup_s": w0 - t_start,
    }
    say("setup", {"setup_s": w0 - t_start, "parts": parts})
    live = [tk[3] for tk in m["ticks"]] or [0]
    reserved = serve.cache_positions(cell["engine"])
    kv_pos = flops_bytes_lfm2.kv_bytes_per_position(cfg)
    per_slot = flops_bytes_olmo_hybrid.state_bytes_per_slot(cfg)
    cache = {"positions_reserved": reserved,
             "reserved_bytes": reserved * kv_pos,
             "live_tokens_mean": sum(live) / len(live),
             "live_tokens_max": max(live),
             "live_kv_bytes_mean": sum(live) / len(live) * kv_pos,
             "pool_peak_blocks_in_use": pool_peak,
             "state_bytes_per_slot": per_slot,
             # rows allocated, as the program counts them, times a row
             "state_bytes": (counters["state"][0][1] * per_slot
                             if counters["state"] else None)}
    win = counters["window"] or {}
    say("window", {
        "seconds": seconds, "ticks": len(m["ticks"]), "tokens": m["tokens"],
        "requests_judged": len(m["judged"]),
        "failed": m["failed"], "failed_by_serve_measure": failed_by_measure,
        "mid_prefill_at_end": mid_prefill,
        "requests_finished": sum(r["in_window"] for r in finished),
        "token_gap_ms": stats.summary(m["gaps_ms"]),
        "ttft_ms": stats.summary(m["ttft_ms"]),
        "tick_ms": stats.summary([(b - a) * 1e3
                                  for a, b, _, _ in m["ticks"]]),
        "generator_late_ms": stats.summary(m["late_ms"]),
        "occupancy_mean": (sum(tk[2] for tk in m["ticks"])
                           / max(1, len(m["ticks"]))),
        "queue_left": stamps["queue_left"], "backlog": len(reqs),
        "cache": cache,
        "counters": {k: (v.tolist() if isinstance(v, np.ndarray) else v)
                     for k, v in win.items() if k != "dead_by_tick"},
        "kernel_paths": paths, "memory_peak_bytes": memory_peak,
        "compiles_in_window": in_window_compiles})

    # -- what decides ``correct`` -----------------------------------------
    checks = []

    def hold(name, value, limit, ok):
        checks.append({"name": name, "value": value, "limit": limit,
                       "ok": bool(ok)})

    hold("compiles_in_window", in_window_compiles["programs"], 0,
         in_window_compiles["programs"] == 0)
    hold("step_traces", step_traces, 1, step_traces == 1)
    missing = [p for p in cell["expect_paths"] if not paths.get(p)]
    hold("expected_kernel_paths_missing", missing, [], not missing)
    allowed = cell["allow_fallbacks"]     # {op or op/path: most, or null}
    fell = sorted(
        k for k, n in paths.items()
        if k.split("/")[1] in serve.FALLBACK_PATHS
        and k.split("/")[0] not in allowed
        and n > (allowed.get("/".join(k.split("/")[:2]), 0)))
    hold("unexpected_xla_fallbacks", fell, [], not fell)
    hold("backlog_left_min", stamps["queue_left"], 1,
         stamps["queue_left"] >= 1)

    t = clock()
    pool = [r for r in finished if r["in_window"]] or finished
    sample = check.sample_requests(pool, int(cell["check"]["sample"]), seed)
    gaps, controls = [], {}
    for r in sample:
        g, low = served_gaps(made, cfg, r["prompt"], r["tokens"],
                             control_bits)
        gaps.append(g)
        for prefix, low_gaps in (low or {}).items():
            controls.setdefault(prefix, []).append(low_gaps)
    limits = cell["check"]["limits"]
    checks.extend(check.judge(gaps, limits))
    control = None
    if control_bits:
        control = [dict(row, name=prefix + row["name"])
                   for prefix, low_gaps in controls.items()
                   for row in check.judge(low_gaps, limits)]
    say("check", {"reference_s": clock() - t, "requests": len(sample),
                  "longest": max((len(r["prompt"]) + len(r["tokens"])
                                  for r in sample), default=0),
                  "positions": int(sum(len(g) for g in gaps)),
                  "reference_compile": compiles.drain(),
                  "compared": checks, "control": control})

    return {
        "cell": cell, "config": cfg, "seconds": seconds, **stamps, **m,
        "kernel_paths": paths, "end_to_end": end_to_end, "checks": checks,
        "cache": cache, "counters": counters,
        "control": control, "correct": all(c["ok"] for c in checks),
        "attempted": len(m["judged"]),
        "memory_peak_bytes": memory_peak,
    }
