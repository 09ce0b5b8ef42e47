"""The serve runner for the SDAR-MoE architecture, a block-diffusion
decoder: ``serve.run``'s signature and flow, as ``serve_lfm2.py`` has it,
with what binds that runner to its architecture replaced.

It IMPORTS everything that is the harness's and not the architecture's —
``serve.drive`` (which stamps by ``len(result(rid))``: a row's tokens
arrive a block at a time, so three gaps in four are 0), ``serve.measure``,
``serve.kernel_paths``, ``serve.cache_positions``,
``serve.warm_prompt_lengths``, ``check.sample_requests``, and from
``serve_afmoe`` the tick-by-tick reading of the routed experts' counters
(``Counted``, ``counters_between``) — and brings only:

  * the model's construction (built to be loaded) from ``weights_sdar.py``;
    prompts never hold the mask token (``without_mask_token``);
  * the cache's bytes: K and V a position over all 48 layers;
  * ``not_yet_delivered``: against a backlog the window's end finds rows
    mid-prompt or inside their first block; ``serve.measure`` would count
    them as failed (its rule is a wave engine's).  Here one is left out only
    on the stamps' evidence that it COULD have no token yet: fewer ticks in
    its slot than its prompt has chunks plus a block has positions (a first
    block takes at most that many denoising forwards);
  * ``served_gaps`` over ``reference/sdar_arch.py``.  A causal reference
    cannot be teacher-forced over the served tokens: a token's logits depend
    on which of its block's positions were still masked when it was
    unmasked.  The engine says at which forward-in-block each token was
    unmasked (``ServingEngine.unmask_steps``); from that the reference
    rebuilds what every forward of every block was fed and runs them all in
    one pass (the clean sequence and its noised copies side by side), the
    head over the rows that are read alone.  Read at every position a
    forward unmasked: how far the served token's logit lies below the
    reference's best there (``served_gap_max``, ``served_gap_mean``, and
    ``served_gap_over_pct``: the share of those positions whose gap passes
    the cell's ``check.gap_tail`` — how MANY positions prefer another token
    than the reference's first is the weights' and the tokens' doing,
    near-ties being many in one request and few in the next, so the mean
    swings with the seed; how FAR below the best a wrongly preferred token
    can lie is the size of the program's rounding, so gaps of three times
    the bf16 program's are all but absent from it and common in an int8
    one), and how far the served position's log-confidence lies below that of the
    reference's most confident still-masked position of the block at that
    forward (``served_pick_gap_mean``; 0 where the reference's confidence
    passes the threshold).  A request's last block is left out where
    ``max_new_tokens`` cut it (its undelivered positions are not known);
  * three controls (``control.py``): the reference with int8-rounded
    matrices; under a plain causal mask (a program that kept a speculative
    window's mask); with every block's K/V as its last denoising forward
    left them (a program that skipped the commit forward).  Each puts its
    own token first at the rows the engine served and picks its own most
    confident position; both are read against the sound reference.

``run()`` repeats ``serve_lfm2.run``'s body where it could not be imported:
that function builds its model and calls its reference itself.
"""

import gc
import time

import jax
import numpy as np

from benchmark.harness import (check, flops_bytes_sdar, serve, serve_afmoe,
                               stats, traffic, weights_sdar)
from benchmark.harness.compile_log import CompileLog
from benchmark.reference import sdar_arch

NUMBERS = ("served_gap_max", "served_gap_mean", "served_gap_over_pct",
           "served_pick_gap_mean")
CONTROLS = ("int8", "causal", "no_commit")


# -- the model --------------------------------------------------------------

def program_config(cfg, max_positions):
    """The program's config of one configuration file: the router keeps its
    published width, the held experts are this rank's; the rotary table is
    built for the positions the cell can reach."""
    from paddle_tpu.models.sdar import SdarMoeConfig
    fields = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts_per_tok",
        "norm_topk_prob", "decoder_sparse_step", "rope_theta",
        "rms_norm_eps", "tie_word_embeddings", "block_length",
        "mask_token_id", "denoising_steps", "remasking_strategy",
        "confidence_threshold")}
    routed = cfg.get("num_experts_routed", cfg["num_experts"])
    if routed != cfg["num_experts"] * cfg.get("ep_size", 1):
        raise ValueError(
            f"{cfg['num_experts']} held experts x ep_size "
            f"{cfg.get('ep_size', 1)} are not the router's {routed}")
    return SdarMoeConfig(
        dtype=cfg["dtype"], num_experts=routed,
        ep_size=cfg.get("ep_size", 1), ep_rank=cfg.get("ep_rank", 0),
        mlp_only_layers=tuple(cfg["mlp_only_layers"]),
        max_position_embeddings=min(int(max_positions),
                                    cfg["max_position_embeddings"]),
        **fields)


def build_model(cfg, seed, max_positions):
    """The program's model holding weights the benchmark made from the
    seed; returns (model, weights under the reference's names).  The
    program's model is looked for FIRST, so that a program without it fails
    at once and not after ten gigabytes of weights."""
    from paddle_tpu import nn
    from paddle_tpu.models.sdar import SdarMoeForCausalLM

    with nn.abstract_parameters():
        model = SdarMoeForCausalLM(program_config(cfg, max_positions))
    model.eval()
    made = weights_sdar.make_weights(cfg, seed, cfg["dtype"])
    missing = model.set_state_dict(
        {weights_sdar.program_name(n): w for n, w in made.items()},
        strict=True)
    buffers = {n for n, p in model.named_parameters(include_buffers=True)
               if p.is_buffer}
    if set(missing) - buffers:
        raise KeyError(f"weights not made: {sorted(set(missing) - buffers)}")
    return model, made


def without_mask_token(reqs, cfg):
    """The generator draws ids over the whole vocabulary; a prompt never
    holds the mask token (the token before it stands in)."""
    mask_id = int(cfg["mask_token_id"])
    for r in reqs:
        r.prompt[r.prompt == mask_id] = mask_id - 1
    return reqs


# -- the comparison that decides ``correct`` --------------------------------

def served_gaps(made, cfg, prompt, tokens, steps, control_bits=None):
    """One finished request against the reference (module docstring).
    Returns {"sound": (token gaps, pick gaps)} and, with ``control_bits``,
    the same pair under each of ``CONTROLS``' names: float64 arrays, one
    entry a position a forward unmasked (token gaps) and one a served
    position or, for a control, a (block, forward) pick (pick gaps)."""
    bl = int(cfg["block_length"])
    p = len(prompt)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens, np.int32)])
    when = np.concatenate([np.zeros(p, np.int64),
                           np.asarray(steps, np.int64)])
    whole = len(seq) - len(seq) % bl
    # padded to a power of two (under the block mask a tail of whole clean
    # blocks changes nothing before it): four lengths of reference program
    padded = max(check.PAD_LEAST, 1 << (whole - 1).bit_length())
    seq = np.pad(seq[:whole], (0, padded - whole))
    when = np.pad(when[:whole], (0, padded - whole))
    fed, f, pos = sdar_arch.forwards_fed(seq, when, cfg)
    if not len(f):          # every delivered token lies in the cut block
        none = (np.zeros(0), np.zeros(0))
        return {name: none for name in
                ("sound",) + (CONTROLS if control_bits else ())}
    served = when[pos] == f                     # the rows that unmasked
    group = (pos // bl) * (bl + 1) + f          # one a (block, forward)

    def stats_of(clean, tokens_at, weight_bits=None, mask="block"):
        hidden = sdar_arch.hidden_states(made, cfg, clean, fed,
                                         weight_bits=weight_bits, mask=mask)
        return sdar_arch.head_stats(made, cfg, hidden[f, pos], tokens_at,
                                    weight_bits=weight_bits)

    def most_confident(logconf):
        """Per read row, the largest log-confidence of its (block,
        forward)'s rows, and whether the row holds it (the first that
        does)."""
        top = np.full(group.max() + 1, -np.inf)
        np.maximum.at(top, group, logconf)
        at = np.nonzero(logconf == top[group])[0]
        holder = np.zeros(len(group), bool)
        holder[at[np.unique(group[at], return_index=True)[1]]] = True
        return top[group], holder

    controls = {}
    if control_bits:
        last_fed = sdar_arch.as_last_fed(seq, when, cfg)
        for name, clean, kw in (
                ("int8", seq, {"weight_bits": control_bits}),
                ("causal", seq, {"mask": "causal"}),
                ("no_commit", last_fed, {})):
            best, tok, lse, _ = stats_of(clean, seq[pos][None], **kw)
            controls[name] = (tok, most_confident(best - lse)[1])
    best, _, lse, at = stats_of(
        seq, np.stack([seq[pos]] + [controls[c][0] for c in controls]))
    logconf = best - lse
    top, _ = most_confident(logconf)
    passes = logconf > np.log(float(cfg["confidence_threshold"]))
    pick = np.where(passes, 0.0, top - logconf)
    out = {"sound": ((best - at[0])[served], pick[served])}
    for i, (name, (_, holder)) in enumerate(controls.items()):
        out[name] = ((best - at[1 + i])[served], pick[holder])
    return out


def judge(pairs, limits, gap_tail):
    """The compared numbers beside their limits: [{"name", "value",
    "limit", "ok"}] over the (token gaps, pick gaps) of the sampled
    requests; ``gap_tail``: the gap ``served_gap_over_pct`` counts from."""
    tok = np.concatenate([t for t, _ in pairs]) if pairs else np.zeros(0)
    pick = np.concatenate([k for _, k in pairs]) if pairs else np.zeros(0)
    values = (float(tok.max()) if tok.size else None,
              float(tok.mean()) if tok.size else None,
              100.0 * float((tok > gap_tail).mean()) if tok.size else None,
              float(pick.mean()) if pick.size else None)
    return [{"name": name, "value": value, "limit": float(limits[name]),
             "ok": value is not None and value <= float(limits[name])}
            for name, value in zip(NUMBERS, values)]


# -- a row the window's end finds before its first delivery -----------------

def not_yet_delivered(m, stamps, cell, cfg):
    """``serve_afmoe.mid_prefill_at_end`` for a block engine: a judged
    request without a token by the window's end is left out only where it
    held its slot for fewer ticks than its prompt has chunks plus a block
    has positions.  Returns (``m`` without them, the evidence)."""
    _, w1 = stamps["window"]
    chunk = int(cell["engine"]["prefill_chunk"])
    bl = int(cfg["block_length"])
    ends = [tk[1] for tk in stamps["ticks"]]
    out, keep, ttft_ms, waits = [], [], [], []
    for rec, ttft in zip(m["judged"], m["ttft_ms"]):
        needs = len(rec.req.prompt) // bl * bl // chunk + 1 + bl
        had = sum(1 for t in ends if rec.slot <= t <= w1)
        if not (rec.times and rec.times[0] <= w1) and had < needs:
            out.append({"index": rec.req.index,
                        "prompt_tokens": len(rec.req.prompt),
                        "ticks_needed_at_most": needs, "ticks_had": had})
            continue
        keep.append(rec)
        ttft_ms.append(ttft)
        waits.append((rec.slot - (rec.due + stamps["t_zero"])) * 1e3)
    return dict(m, judged=keep, ttft_ms=ttft_ms, queue_wait_ms=waits,
                failed=m["failed"] - len(out)), out


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
    reqs = without_mask_token(
        traffic.generate(mix, cfg["vocab_size"], seed, seconds), cfg)
    rng = np.random.default_rng([int(seed), 0x3A3A])
    for n in serve.warm_prompt_lengths(cell, reqs):
        eng.submit(rng.integers(1, cfg["mask_token_id"], n).astype(np.int32),
                   max_new_tokens=2)
        eng.drain()
    parts["engine_and_warm_s"] = clock() - t
    parts["compile"] = compiles.drain()
    return eng, made, reqs, compiles, parts


def run(cell, cfg, mix, *, seed, seconds, t_start, say, trace_dir=None,
        control_bits=None):
    """One run of one serve cell of this architecture; the record
    ``serve_lfm2.run`` returns (without its per-slot state) and, with
    ``control_bits``, the three controls' rows under ``control``: the int8
    control's under the numbers' own names, the others' behind ``causal.``
    and ``no_commit.``."""
    clock = time.perf_counter
    counted_before = serve.kernel_paths()
    eng, made, reqs, compiles, parts = setup(cell, cfg, mix, seed, seconds,
                                             t_start)
    eng = serve_afmoe.Counted(eng)
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
                  if stamps["trace_slice"] else None)}
    finished = [
        {"index": rec.req.index, "prompt": rec.req.prompt,
         "tokens": eng.result(rec.rid), "steps": eng.unmask_steps(rec.rid),
         "temperature": rec.req.temperature,
         "in_window": rec.times[-1] >= w0}
        for rec in stamps["order"] if rec.done]
    del eng                     # the pool goes; the reference needs room
    gc.collect()

    m = serve.measure(stamps, mix, seconds)
    failed_by_measure = m["failed"]
    m, not_yet = not_yet_delivered(m, stamps, cell, cfg)
    end_to_end = {
        "output_tok_s": m["tokens"] / seconds,
        "token_gap_p95_ms": stats.percentile(m["gaps_ms"], 95),
        "ttft_p95_ms": stats.percentile(m["ttft_ms"], 95),
        "setup_s": w0 - t_start,
    }
    say("setup", {"setup_s": w0 - t_start, "parts": parts})
    live = [tk[3] for tk in m["ticks"]] or [0]
    reserved = serve.cache_positions(cell["engine"])
    kv_pos = flops_bytes_sdar.kv_bytes_per_position(cfg)
    cache = {"positions_reserved": reserved,
             "reserved_bytes": reserved * kv_pos,
             "live_tokens_mean": sum(live) / len(live),
             "live_tokens_max": max(live),
             "live_kv_bytes_mean": sum(live) / len(live) * kv_pos,
             "pool_peak_blocks_in_use": pool_peak}
    win = counters["window"] or {}
    block_gaps = [g for g in m["gaps_ms"] if g > 0]
    say("window", {
        "seconds": seconds, "ticks": len(m["ticks"]), "tokens": m["tokens"],
        "requests_judged": len(m["judged"]),
        "failed": m["failed"], "failed_by_serve_measure": failed_by_measure,
        "not_yet_delivered_at_end": not_yet,
        "requests_finished": sum(r["in_window"] for r in finished),
        "token_gap_ms": stats.summary(m["gaps_ms"]),
        "block_gap_ms": stats.summary(block_gaps),
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
    gaps = {name: [] for name in ("sound",) + CONTROLS}
    for r in sample:
        for name, pair in served_gaps(made, cfg, r["prompt"], r["tokens"],
                                      r["steps"], control_bits).items():
            gaps[name].append(pair)
    limits, tail = cell["check"]["limits"], cell["check"]["gap_tail"]
    checks.extend(judge(gaps["sound"], limits, tail))
    control = fails = None
    if control_bits:
        judged = {name: judge(gaps[name], limits, tail) for name in CONTROLS}
        fails = {name: not all(row["ok"] for row in rows)
                 for name, rows in judged.items()}
        control = judged["int8"] + [
            dict(row, name=f"{name}.{row['name']}")
            for name in CONTROLS[1:] for row in judged[name]]
    say("check", {"reference_s": clock() - t, "requests": len(sample),
                  "longest": max((len(r["prompt"]) + len(r["tokens"])
                                  for r in sample), default=0),
                  "positions": int(sum(len(g) for g, _ in gaps["sound"])),
                  "reference_compile": compiles.drain(),
                  "compared": checks, "control": control,
                  "control_fails": fails})

    return {
        "cell": cell, "config": cfg, "seconds": seconds, **stamps, **m,
        "kernel_paths": paths, "end_to_end": end_to_end, "checks": checks,
        "cache": cache, "counters": counters,
        "control": control, "correct": all(c["ok"] for c in checks),
        "attempted": len(m["judged"]),
        "memory_peak_bytes": memory_peak,
    }
