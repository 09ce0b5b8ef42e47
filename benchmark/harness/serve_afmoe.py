"""The serve runner for the AFMoE architecture: ``serve.run``'s signature
and flow, with what binds that runner to llama replaced.

``serve.py`` builds ``LlamaForCausalLM``, ``weights.py`` knows llama's
names and ``check.py`` calls ``reference/llama_arch.py`` by import, so a
second architecture brings a runner of its own beside them (README, "Adding
things").  This one IMPORTS everything that is the harness's and not the
architecture's — ``serve.drive`` (the submit/step/stamp loop),
``serve.measure``, ``serve.kernel_paths``, ``serve.gauge``,
``serve.cache_positions``, ``serve.warm_prompt_lengths``,
``check.sample_requests``, ``check.judge`` — and brings only:

  * the model's construction (built to be loaded, with no weights of its
    own: two sets of 8.6 GB do not fit) from ``weights_afmoe.py``;
  * the cache's bytes a position (``head_dim`` is its own key here);
  * ``served_gaps`` over ``reference/afmoe_arch.py``, with the two controls
    (weights rounded to int8; the reference without its window);
  * the program's counters read after every tick (pairs a held expert and
    expert layer, pairs routed elsewhere, experts touched; window-dead
    positions), which the cell's per-layer metrics difference over the
    window and over the traced slice.  Against a program without these
    counters they are None and those metrics are left out;
  * ``mid_prefill_at_end``: this is the first cell that is chunked AND
    against a backlog, where the window's end nearly always finds the
    engine's one cursor mid-prompt.  ``serve.measure`` would count that
    request as failed; here it is not judged, and only on the stamps'
    evidence that it could have no token yet.  ``serve.measure``'s own
    count is reported beside (``failed_by_serve_measure``).

``run()`` repeats ``serve.run``'s body where it could not be imported: that
function builds its model and calls its reference itself.
"""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import check, serve, stats, traffic, weights_afmoe
from benchmark.harness.compile_log import CompileLog
from benchmark.reference import afmoe_arch


# -- the model --------------------------------------------------------------

def program_config(cfg, max_positions):
    """The program's config of one configuration file: the router keeps its
    published width, the held experts are this rank's; the rotary table is
    built for the positions the cell can reach."""
    from paddle_tpu.models.afmoe import AfmoeConfig
    fields = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "num_experts_per_tok", "num_shared_experts", "route_norm",
        "route_scale", "score_func", "n_group", "topk_group",
        "sliding_window", "rope_theta", "rms_norm_eps", "mup_enabled",
        "tie_word_embeddings")}
    routed = cfg.get("num_experts_routed", cfg["num_experts"])
    if routed != cfg["num_experts"] * cfg.get("ep_size", 1):
        raise ValueError(
            f"{cfg['num_experts']} held experts x ep_size "
            f"{cfg.get('ep_size', 1)} are not the router's {routed}")
    return AfmoeConfig(
        dtype=cfg["dtype"], num_experts=routed,
        ep_size=cfg.get("ep_size", 1), ep_rank=cfg.get("ep_rank", 0),
        layer_types=tuple(cfg["layer_types"]),
        max_position_embeddings=min(int(max_positions),
                                    cfg["max_position_embeddings"]),
        **fields)


def build_model(cfg, seed, max_positions):
    """The program's model holding weights the benchmark made from the
    seed; returns (model, weights under the reference's names).  The model
    is built to be loaded (``nn.abstract_parameters``: no initializer
    runs), because its own random weights do not fit beside the seeded
    ones."""
    from paddle_tpu import nn
    from paddle_tpu.models.afmoe import AfmoeForCausalLM

    with nn.abstract_parameters():
        model = AfmoeForCausalLM(program_config(cfg, max_positions))
    model.eval()
    made = weights_afmoe.make_weights(cfg, seed, cfg["dtype"])
    missing = model.set_state_dict(
        {weights_afmoe.program_name(n): w for n, w in made.items()},
        strict=True)
    buffers = {n for n, p in model.named_parameters(include_buffers=True)
               if p.is_buffer}
    if set(missing) - buffers:
        raise KeyError(f"weights not made: {sorted(set(missing) - buffers)}")
    return model, made


def kv_bytes_per_position(cfg):
    """K and V of one token over every layer, in the served type."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * jnp.dtype(cfg["dtype"]).itemsize)


# -- the comparison that decides ``correct`` --------------------------------

def served_gaps(made, cfg, prompt, tokens, control_bits=None):
    """``check.served_gaps`` over this architecture's reference: per served
    position, reference-best logit minus the served token's logit.  With
    ``control_bits`` also the same for the token that each control puts
    first: the reference with int-rounded weights, and the reference
    without its window (which is the reference itself for a sequence no
    longer than the window).  Returns (gaps, int8 control's gaps or None,
    no-window control's gaps or None).  Padded to a power of two (under a
    causal mask a tail changes nothing before it), so a cell compiles five
    lengths of reference program at most."""
    p, t = len(prompt), len(tokens)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(tokens, np.int32)])
    padded = max(check.PAD_LEAST, 1 << (len(full) - 2).bit_length())
    ids = np.zeros(padded, np.int32)
    ids[:len(full) - 1] = full[:-1]
    nxt = np.zeros(padded, np.int32)
    nxt[:len(full) - 1] = full[1:]
    served = slice(p - 1, p - 1 + t)
    ref = afmoe_arch.logits(made, cfg, ids)
    gaps = np.asarray(check._gap_below_best(ref, jnp.asarray(nxt)))[served]
    if control_bits is None:
        return gaps, None, None

    def first_of(**control):
        low = afmoe_arch.logits(made, cfg, ids, **control)
        first = jnp.argmax(low, axis=-1).astype(jnp.int32)
        return np.asarray(check._gap_below_best(ref, first))[served]

    no_window = (first_of(window=False)
                 if len(full) - 1 > cfg["sliding_window"]
                 else np.zeros_like(gaps))
    return gaps, first_of(weight_bits=control_bits), no_window


# -- the program's counters, tick by tick -----------------------------------

class Counted:
    """The engine as ``serve.drive`` drives it, with the program's own
    counters read after every tick: ``log`` rows are (pairs by expert layer
    and held expert or None, pairs elsewhere, experts touched, expert-layer
    calls, window-dead positions), one a tick, in the order of the ticks
    ``drive`` stamps."""

    def __init__(self, eng):
        self._eng = eng
        self.log = []

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def step(self):
        finished = self._eng.step()
        load = getattr(self._eng, "expert_load", None) or {}
        self.log.append((load.get("pairs"), load.get("pairs_elsewhere", 0),
                         load.get("experts_touched", 0),
                         load.get("layer_calls", 0),
                         getattr(self._eng, "window_dead_positions", None)))
        return finished


def counters_between(log, ticks, lo, hi):
    """What the counters gained over the ticks that ended in [lo, hi], and
    those ticks' rows (tick, window-dead positions); None where the program
    has no such counters or fewer than two ticks ended there."""
    rows = [(tk, row) for tk, row in zip(ticks, log) if lo <= tk[1] <= hi]
    if len(rows) < 2 or rows[-1][1][0] is None:
        return None
    a, b = rows[0][1], rows[-1][1]
    return {"ticks": len(rows) - 1,
            "pairs": b[0] - (a[0] if a[0] is not None else 0),
            "pairs_elsewhere": b[1] - a[1], "experts_touched": b[2] - a[2],
            "layer_calls": b[3] - a[3],
            # the first row is the state BEFORE the span counted
            "dead_by_tick": [(tk, row[4]) for tk, row in rows[1:]]}


# -- one run ----------------------------------------------------------------

def _memory_peak():
    return int((jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0))


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
    parts["memory_peak_after_weights"] = _memory_peak()
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


def mid_prefill_at_end(m, stamps, cell):
    """``serve.measure`` judges, against a backlog, every request that held
    a slot in the window, and counts one without a first token by the
    window's end as failed: right for a wave engine, whose admission IS the
    first token.  A chunked engine admits a request as a cursor and streams
    its prompt one chunk a tick, one prompt at a time, so against a backlog
    the window's end nearly always finds one request mid-prompt.  Such a
    request is left out of the judged ones only on the stamps' evidence that
    it COULD not have a token yet: it has held its slot for fewer ticks than
    its prompt has chunks (the first token comes with the last chunk).  One
    that had the ticks and still has no token stays failed.  Returns
    (``m`` without them, the evidence a request left out)."""
    if not cell["engine"].get("chunked"):
        return m, []
    _, w1 = stamps["window"]
    chunk = int(cell["engine"]["prefill_chunk"])
    ends = [tk[1] for tk in stamps["ticks"]]
    out, keep, ttft_ms, waits = [], [], [], []
    for rec, ttft in zip(m["judged"], m["ttft_ms"]):
        needs = -(-len(rec.req.prompt) // chunk)
        had = sum(1 for t in ends if rec.slot <= t <= w1)
        if not (rec.times and rec.times[0] <= w1) and had < needs:
            out.append({"index": rec.req.index,
                        "prompt_tokens": len(rec.req.prompt),
                        "chunks_needed": needs, "ticks_had": had,
                        "admitted_before_end_s": w1 - rec.slot})
            continue
        keep.append(rec)
        ttft_ms.append(ttft)
        waits.append((rec.slot - (rec.due + stamps["t_zero"])) * 1e3)
    return dict(m, judged=keep, ttft_ms=ttft_ms, queue_wait_ms=waits,
                failed=m["failed"] - len(out)), out


def run(cell, cfg, mix, *, seed, seconds, t_start, say, trace_dir=None,
        control_bits=None):
    """One run of one serve cell of this architecture; the record
    ``serve.run`` returns, plus ``counters`` (window and traced slice) and,
    with ``control_bits``, both controls' rows under ``control`` (the
    no-window control's names start with ``no_window.``)."""
    clock = time.perf_counter
    counted_before = serve.kernel_paths()
    eng, made, reqs, compiles, parts = setup(cell, cfg, mix, seed, seconds,
                                             t_start)
    eng = Counted(eng)
    stamps = serve.drive(eng, reqs, mix, seconds, trace_dir)
    in_window_compiles = compiles.drain(floor=0.0)
    w0, w1 = stamps["window"]
    parts["ramp_s"] = w0 - stamps["t_zero"]
    memory_peak = _memory_peak()
    paths = {k: n - counted_before.get(k, 0)
             for k, n in serve.kernel_paths().items()
             if n > counted_before.get(k, 0)}
    step_traces = eng.step_traces
    pool_peak = serve.gauge("kv_cache.peak_blocks_in_use")
    counters = {"window": counters_between(eng.log, stamps["ticks"], w0, w1),
                "trace": (counters_between(eng.log, stamps["ticks"],
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
    m, mid_prefill = mid_prefill_at_end(m, stamps, cell)
    end_to_end = {
        "output_tok_s": m["tokens"] / seconds,
        "token_gap_p95_ms": stats.percentile(m["gaps_ms"], 95),
        "ttft_p95_ms": stats.percentile(m["ttft_ms"], 95),
        "setup_s": w0 - t_start,
    }
    say("setup", {"setup_s": w0 - t_start, "parts": parts})
    live = [tk[3] for tk in m["ticks"]] or [0]
    reserved = serve.cache_positions(cell["engine"])
    cache = {"positions_reserved": reserved,
             "reserved_bytes": reserved * kv_bytes_per_position(cfg),
             "live_tokens_mean": sum(live) / len(live),
             "live_tokens_max": max(live),
             "live_kv_bytes_mean": sum(live) / len(live)
             * kv_bytes_per_position(cfg),
             "pool_peak_blocks_in_use": pool_peak}
    win = counters["window"] or {}
    say("window", {
        "seconds": seconds, "ticks": len(m["ticks"]), "tokens": m["tokens"],
        "requests_judged": len(m["judged"]),
        "failed": m["failed"], "failed_by_serve_measure": failed_by_measure,
        "mid_prefill_at_end": mid_prefill,
        "requests_finished": sum(r["in_window"] for r in finished),
        "token_gap_ms": stats.summary(m["gaps_ms"]),
        "ttft_ms": stats.summary(m["ttft_ms"]),
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
    if mix["loop"] == "backlog":
        hold("backlog_left_min", stamps["queue_left"], 1,
             stamps["queue_left"] >= 1)

    t = clock()
    pool = [r for r in finished if r["in_window"]] or finished
    sample = check.sample_requests(pool, int(cell["check"]["sample"]), seed)
    gaps, int8_gaps, window_gaps = [], [], []
    for r in sample:
        g, low, no_window = served_gaps(made, cfg, r["prompt"], r["tokens"],
                                        control_bits)
        gaps.append(g)
        if low is not None:
            int8_gaps.append(low)
            window_gaps.append(no_window)
    limits = cell["check"]["limits"]
    checks.extend(check.judge(gaps, limits))
    control = None
    if control_bits:
        control = check.judge(int8_gaps, limits) + [
            dict(row, name="no_window." + row["name"])
            for row in check.judge(window_gaps, limits)]
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
