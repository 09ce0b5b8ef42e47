"""The serve runner: one cell, one run, one process.

It drives ``paddle_tpu.serving.ServingEngine`` the way a user would —
``submit`` / ``step`` / ``result`` — from a single thread.  Every turn of
the loop submits each request whose due time has passed, calls ``step()``
(which ends in the token readback, so the host clock after it is a
device-finished time) and stamps every token that appeared in
``result(rid)``.  Time to first token runs from when the request was DUE.

Set-up is everything from process start to the window's opening: imports,
the model, weights from the seed, the engine, a warm-up of the shapes the
cell's traffic reaches, and the ramp the traffic file asks for.  Nothing
may compile inside the window.
"""

import gc
import time

import numpy as np

from benchmark.harness import check, stats, traffic, weights
from benchmark.harness.compile_log import CompileLog

FALLBACK_PATHS = ("xla_math", "xla_reference", "xla_dequant")
TRACE_DELAY_S = 1.0
TRACE_LEN_S = 4.0


def kernel_paths():
    """``ops.kernel_path`` as {"op/path[/cache]": count} (counted at trace
    time: compiled programs that took this path)."""
    from paddle_tpu import observability as obs
    fam = obs.snapshot().get("ops.kernel_path", {"series": []})
    out = {}
    for row in fam["series"]:
        lab = row["labels"]
        key = "/".join(lab[k] for k in ("op", "path", "cache") if k in lab)
        out[key] = out.get(key, 0) + int(row["value"])
    return out


def gauge(name):
    """One unlabelled series of the program's registry, or None."""
    from paddle_tpu import observability as obs
    series = obs.snapshot().get(name, {"series": []})["series"]
    return series[0]["value"] if series else None


def build_model(cfg, seed):
    """The program's model holding weights the benchmark made from the
    seed; returns (model, weights under the reference's names)."""
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig

    fields = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "max_position_embeddings", "rms_norm_eps", "rope_theta",
        "tie_word_embeddings")}
    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig(dtype=cfg["dtype"], **fields))
    model.eval()
    handles = dict(model.named_parameters(include_buffers=True))
    names = {n: weights.program_name(n) for n in weights.weight_shapes(cfg)}
    # the constructor filled the chip with its own weights; free them
    # before making ours, or the two sets do not fit side by side
    for prog in names.values():
        handles[prog].value.delete()
    made = weights.make_weights(cfg, seed, cfg["dtype"])
    model.set_state_dict({names[n]: w for n, w in made.items()},
                         strict=True)
    return model, made


def cache_positions(engine):
    """Token positions the cell's cache reserves: the paged pool's blocks
    (but the null block), or every slot's ``max_length``."""
    if engine.get("paged") and engine.get("num_blocks"):
        return (int(engine["num_blocks"]) - 1) * int(engine["block_len"])
    return int(engine["num_slots"]) * int(engine["max_length"])


def kv_bytes_per_position(cfg):
    """K and V of one token over every layer, in the served type."""
    import jax.numpy as jnp
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd
            * jnp.dtype(cfg["dtype"]).itemsize)


def warm_prompt_lengths(cell, reqs):
    """One prompt length for each program the traffic reaches: the wave
    engine compiles a prefill per power-of-two bucket of the prompt length;
    the chunked engine has its one mixed step."""
    if cell["engine"].get("chunked"):
        return [min(len(r.prompt) for r in reqs)]
    longest = {}
    for r in reqs:
        b = max(8, 1 << (len(r.prompt) - 1).bit_length())
        longest[b] = max(longest.get(b, 0), len(r.prompt))
    return [longest[b] for b in sorted(longest)]


class _Rec:
    __slots__ = ("req", "rid", "due", "submit", "slot", "times", "done")

    def __init__(self, req):
        self.req, self.due = req, req.due_s
        self.rid = self.submit = self.slot = None
        self.times = []
        self.done = False


def setup(cell, cfg, mix, seed, seconds, t_start):
    """Model, weights from the seed, engine, the run's requests, and a
    warm-up of every program they reach.  Returns (engine, weights,
    requests, compile log, the parts of the set-up time)."""
    import jax

    from paddle_tpu.serving import ServingEngine

    clock = time.perf_counter
    compiles = CompileLog()
    parts = {"import_s": clock() - t_start}
    t = clock()
    model, made = build_model(cfg, seed)
    jax.block_until_ready(made)
    parts["model_and_weights_s"] = clock() - t
    t = clock()
    eng = ServingEngine(model, seed=int(seed) & 0x7FFFFFFF,
                        **cell["engine"])
    reqs = traffic.generate(mix, cfg["vocab_size"], seed, seconds)
    rng = np.random.default_rng([int(seed), 0x3A3A])
    for n in warm_prompt_lengths(cell, reqs):
        # one at a time: a paged wave pads every row to its longest
        eng.submit(rng.integers(1, cfg["vocab_size"], n).astype(np.int32),
                   max_new_tokens=2)
        eng.drain()
    parts["engine_and_warm_s"] = clock() - t
    parts["compile"] = compiles.drain()
    return eng, made, reqs, compiles, parts


def drive(eng, reqs, mix, seconds, trace_dir=None):
    """Offer ``reqs`` to the engine on the wall clock until the window
    (opened as the mix's ``window_opens`` says, ``seconds`` long) closes.
    Returns the stamps: every submitted record in order, the ticks, the
    window, the time 0 of the due times and, with ``trace_dir``, the part
    of the window the profiler traced (about TRACE_LEN_S seconds)."""
    import jax
    from jax.profiler import TraceAnnotation

    from paddle_tpu.serving import SamplingParams

    clock = time.perf_counter
    recs = [_Rec(r) for r in reqs]
    by_rid = {}
    opens = mix.get("window_opens", {})
    need_retired = int(opens.get("after_retired", 0))
    ramp_s = float(opens.get("after_s", 0.0))
    ticks = []                  # (t_before, t_after, occupancy, live depth)
    live = []                   # admitted, unfinished records, FIFO
    order = []                  # submitted records, FIFO
    next_i = admitted = retired = 0
    w0 = w1 = None
    trace_state = "wanted" if trace_dir else "off"     # -> "on" -> "done"
    trace_span = TraceAnnotation("bench.window")
    trace_slice = None
    t_zero = clock()

    def stop_trace():
        trace_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return (trace_slice[0], clock())

    while True:
        now = clock()
        if w0 is None and retired >= need_retired and now - t_zero >= ramp_s:
            w0, w1 = now, now + seconds
        if w1 is not None and now >= w1:
            break
        if (trace_state == "wanted" and w0 is not None
                and now >= w0 + min(TRACE_DELAY_S, seconds / 4)):
            jax.profiler.start_trace(trace_dir)
            trace_span.__enter__()
            trace_state, trace_slice = "on", (clock(), None)
        if trace_state == "on" and (
                clock() - trace_slice[0] >= min(TRACE_LEN_S, seconds / 2)):
            trace_state, trace_slice = "done", stop_trace()
        with TraceAnnotation("bench.submit"):
            while next_i < len(recs) and recs[next_i].due <= now - t_zero:
                rec = recs[next_i]
                next_i += 1
                r = rec.req
                try:
                    rec.rid = eng.submit(
                        r.prompt, max_new_tokens=r.max_new_tokens,
                        sampling=SamplingParams(temperature=r.temperature))
                except ValueError:      # refused: never stamped, so failed
                    continue
                rec.submit = clock()
                by_rid[rec.rid] = rec
                order.append(rec)
        if not live and admitted == len(order):
            # nothing to serve: wait for the next arrival (or the end)
            if next_i >= len(recs) and w1 is None:
                raise RuntimeError("the traffic ran out before the window "
                                   "opened: raise ramp_allow_s")
            nxt = recs[next_i].due + t_zero if next_i < len(recs) else w1
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, w1 or nxt) - clock()))
            continue
        t_a = clock()
        with TraceAnnotation("bench.step"):
            finished = eng.step()
        t_b = clock()
        with TraceAnnotation("bench.stamp"):
            newly = len(order) - eng.queue_depth
            for rec in order[admitted:newly]:
                rec.slot = t_b
                live.append(rec)
            admitted = newly
            depth = 0
            for rec in live:
                n = len(eng.result(rec.rid))
                if n > len(rec.times):
                    rec.times.extend([t_b] * (n - len(rec.times)))
                if rec.times:
                    depth += len(rec.req.prompt) + len(rec.times)
            for rid in finished:
                if rid in by_rid:
                    by_rid[rid].done = True
                    retired += 1
            if finished:
                live = [rec for rec in live if not rec.done]
            ticks.append((t_a, t_b, eng.last_occupancy, depth))
    if trace_state == "on":
        trace_slice = stop_trace()
    return {"records": recs, "order": order, "ticks": ticks,
            "window": (w0, w1), "t_zero": t_zero,
            "queue_left": len(order) - admitted, "trace_slice": trace_slice}


def measure(stamps, mix, seconds):
    """The window's numbers from the stamps: tokens, gaps, first-token
    times from the due time (a request that has none by the window's end
    counts as the window's length, and as failed), queue waits, lateness
    of the generator, and which requests are judged — in an open loop those
    due in the window but for its last ``ttft_grace_s`` seconds, against a
    backlog those that held a slot in the window."""
    w0, w1 = stamps["window"]
    t_zero, order = stamps["t_zero"], stamps["order"]
    in_win = lambda x: w0 <= x <= w1                         # noqa: E731
    tokens = sum(1 for rec in order for x in rec.times if in_win(x))
    gaps_ms = [(b - a) * 1e3 for rec in order
               for a, b in zip(rec.times, rec.times[1:]) if in_win(b)]
    if mix["loop"] == "open":
        grace = float(mix.get("ttft_grace_s", 0.0))
        judged = [rec for rec in stamps["records"]
                  if w0 <= rec.due + t_zero <= w1 - grace]
    else:
        judged = [rec for rec in order if rec.slot is not None
                  and rec.slot <= w1
                  and (not rec.done or rec.times[-1] >= w0)]
    ttft_ms, failed = [], 0
    for rec in judged:
        if rec.times and rec.times[0] <= w1:
            ttft_ms.append((rec.times[0] - (rec.due + t_zero)) * 1e3)
        else:
            failed += 1
            ttft_ms.append(seconds * 1e3)
    return {
        "tokens": tokens, "gaps_ms": gaps_ms, "ttft_ms": ttft_ms,
        "judged": judged, "failed": failed,
        "late_ms": [(rec.submit - (rec.due + t_zero)) * 1e3
                    for rec in order if in_win(rec.submit)],
        "queue_wait_ms": [(rec.slot - (rec.due + t_zero)) * 1e3
                          for rec in judged if rec.slot is not None],
        "ticks": [tk for tk in stamps["ticks"] if in_win(tk[1])],
    }


def run(cell, cfg, mix, *, seed, seconds, t_start, say, trace_dir=None,
        control_bits=None):
    """One run of one serve cell, traced where ``trace_dir`` is given.
    Returns the run's record: what the per-layer readers read, the
    end-to-end numbers, ``correct`` with every number compared, and the
    device's memory peak."""
    import jax

    clock = time.perf_counter
    counted_before = kernel_paths()      # the counters are the process's
    eng, made, reqs, compiles, parts = setup(cell, cfg, mix, seed, seconds,
                                             t_start)
    stamps = drive(eng, reqs, mix, seconds, trace_dir)
    in_window_compiles = compiles.drain(floor=0.0)
    w0, w1 = stamps["window"]
    parts["ramp_s"] = w0 - stamps["t_zero"]
    mem = jax.devices()[0].memory_stats() or {}
    paths = {k: n - counted_before.get(k, 0)
             for k, n in kernel_paths().items()
             if n > counted_before.get(k, 0)}
    step_traces = eng.step_traces
    pool_peak = gauge("kv_cache.peak_blocks_in_use")
    finished = [
        {"index": rec.req.index, "prompt": rec.req.prompt,
         "tokens": eng.result(rec.rid), "temperature": rec.req.temperature,
         "in_window": rec.times[-1] >= w0}
        for rec in stamps["order"] if rec.done]
    del eng                     # the cache goes; the reference needs room
    gc.collect()

    m = measure(stamps, mix, seconds)
    end_to_end = {
        "output_tok_s": m["tokens"] / seconds,
        "token_gap_p95_ms": stats.percentile(m["gaps_ms"], 95),
        "ttft_p95_ms": stats.percentile(m["ttft_ms"], 95),
        "setup_s": w0 - t_start,
    }
    say("setup", {"setup_s": w0 - t_start, "parts": parts})
    live = [tk[3] for tk in m["ticks"]] or [0]
    reserved = cache_positions(cell["engine"])
    cache = {"positions_reserved": reserved,
             "reserved_bytes": reserved * kv_bytes_per_position(cfg),
             "live_tokens_mean": sum(live) / len(live),
             "live_tokens_max": max(live),
             "live_kv_bytes_mean": sum(live) / len(live)
             * kv_bytes_per_position(cfg),
             "pool_peak_blocks_in_use": pool_peak}
    say("window", {
        "seconds": seconds, "ticks": len(m["ticks"]), "tokens": m["tokens"],
        "requests_judged": len(m["judged"]),
        "requests_finished": sum(r["in_window"] for r in finished),
        "token_gap_ms": stats.summary(m["gaps_ms"]),
        "ttft_ms": stats.summary(m["ttft_ms"]),
        "generator_late_ms": stats.summary(m["late_ms"]),
        "queue_left": stamps["queue_left"], "cache": cache,
        "kernel_paths": paths,
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
        k for k, n in paths.items() if k.split("/")[1] in FALLBACK_PATHS
        and k.split("/")[0] not in allowed
        and n > (allowed.get("/".join(k.split("/")[:2]), 0)))
    hold("unexpected_xla_fallbacks", fell, [], not fell)
    if mix["loop"] == "backlog":
        hold("backlog_left_min", stamps["queue_left"], 1,
             stamps["queue_left"] >= 1)

    t = clock()
    pool = [r for r in finished if r["in_window"]] or finished
    sample = check.sample_requests(pool, int(cell["check"]["sample"]), seed)
    gaps, low_gaps = [], []
    for r in sample:
        g, low = check.served_gaps(made, cfg, r["prompt"], r["tokens"],
                                   control_bits)
        gaps.append(g)
        if low is not None:
            low_gaps.append(low)
    checks.extend(check.judge(gaps, cell["check"]["limits"]))
    control = (check.judge(low_gaps, cell["check"]["limits"])
               if control_bits else None)
    say("check", {"reference_s": clock() - t, "requests": len(sample),
                  "positions": int(sum(len(g) for g in gaps)),
                  "reference_compile": compiles.drain(),
                  "compared": checks, "control": control})

    return {
        "cell": cell, "config": cfg, "seconds": seconds, **stamps, **m,
        "kernel_paths": paths, "end_to_end": end_to_end, "checks": checks,
        "cache": cache,
        "control": control, "correct": all(c["ok"] for c in checks),
        "attempted": len(m["judged"]),
        "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
    }
