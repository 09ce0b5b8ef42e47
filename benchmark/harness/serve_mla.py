"""The serve runner for the latent-attention MoE architecture (MLA over a
latent paged pool; JoyAI-LLM-Flash): ``serve.run``'s signature and flow, as
``serve_afmoe.py`` has it, with what binds that runner to its architecture
and to traffic that shares nothing replaced.

It IMPORTS everything that is the harness's and not the architecture's —
``serve.drive``, ``serve.measure``, ``serve.kernel_paths``, ``serve.gauge``,
``serve.cache_positions``, ``serve.warm_prompt_lengths``, ``check.judge``,
and from ``serve_afmoe`` the tick-by-tick reading of the routed experts'
counters (``Counted``, ``counters_between``) — and brings only:

  * the model's construction (built to be loaded) from ``weights_mla.py``;
  * the pool's bytes a position as stored (``flops_bytes_mla``);
  * ``admissions``: the request log's ``admitted`` events joined to the
    harness's records (by order of submission, as
    ``engine_spans.request_waits`` joins them): when each request was
    admitted, its prompt tokens, and the tokens the prefix trie served;
  * ``live_positions``: ``serve.drive`` stamps a tick's live depth as the
    sum over rows of prompt plus tokens, which counts a document once a
    row; the pool holds it once.  Recounted from the stamps: each live
    request's own positions (its prompt less what it adopted, plus its
    tokens) and each document with a live reader once;
  * ``mid_prefill_at_end`` as ``serve_afmoe`` has it, the chunks a prompt
    needs counted from what the trie did NOT serve;
  * ``sample_shared``: the sample of ``correct`` always holds the longest
    request, another on the SAME document and at least one on another, so
    that a program that read the wrong blocks for a shared prefix, or one
    document's blocks for another's, shows;
  * ``served_gaps`` over ``reference/mla_arch.py``: teacher-forced, the
    head over the served rows alone (cut before the head), padded to a
    multiple of PAD_TO (under a causal mask a tail changes nothing before
    it) and the rows read to a multiple of ROWS_TO, so that a cell compiles a
    few lengths of reference program; two
    controls: the reference with int8-rounded matrices, and the reference
    over ANOTHER document's tokens in the shared prefix's place (what a
    program computes that adopted the wrong blocks).

``run()`` repeats ``serve_afmoe.run``'s body where it could not be imported:
that function builds its model and calls its reference itself.
"""

import bisect
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import (check, flops_bytes_mla, serve, serve_afmoe,
                               stats, traffic, weights_mla)
from benchmark.harness.compile_log import CompileLog
from benchmark.reference import mla_arch

PAD_TO = 1024
ROWS_TO = 256
CONTROLS = ("int8", "other_doc")


# -- the model --------------------------------------------------------------

def program_config(cfg, max_positions):
    """The program's config of one configuration file: the router keeps its
    published width, the held experts are this rank's; the rotary table is
    built for the positions the cell can reach."""
    from paddle_tpu.models.latent_moe import LatentMoeConfig
    fields = {k: cfg[k] for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "moe_layer_freq", "num_attention_heads",
        "num_key_value_heads", "head_dim", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
        "norm_topk_prob", "routed_scaling_factor", "scoring_func",
        "topk_method", "hidden_act", "attention_bias", "rope_theta",
        "rope_interleave", "rope_scaling", "rms_norm_eps",
        "tie_word_embeddings", "num_nextn_predict_layers") if k in cfg}
    routed = cfg.get("n_experts_routed", cfg["n_routed_experts"])
    if routed != cfg["n_routed_experts"] * cfg.get("ep_size", 1):
        raise ValueError(
            f"{cfg['n_routed_experts']} held experts x ep_size "
            f"{cfg.get('ep_size', 1)} are not the router's {routed}")
    return LatentMoeConfig(
        dtype=cfg["dtype"], n_routed_experts=routed,
        ep_size=cfg.get("ep_size", 1), ep_rank=cfg.get("ep_rank", 0),
        max_position_embeddings=min(int(max_positions),
                                    cfg["max_position_embeddings"]),
        **fields)


def build_model(cfg, seed, max_positions):
    """The program's model holding weights the benchmark made from the
    seed; returns (model, weights under the reference's names).  The
    program's model is looked for FIRST, so that a program without it fails
    at once and not after ten gigabytes of weights."""
    from paddle_tpu import nn
    from paddle_tpu.models.latent_moe import LatentMoeForCausalLM

    with nn.abstract_parameters():
        model = LatentMoeForCausalLM(program_config(cfg, max_positions))
    model.eval()
    made = weights_mla.make_weights(cfg, seed, cfg["dtype"])
    missing = model.set_state_dict(
        {weights_mla.program_name(n): w for n, w in made.items()},
        strict=True)
    buffers = {n for n, p in model.named_parameters(include_buffers=True)
               if p.is_buffer}
    if set(missing) - buffers:
        raise KeyError(f"weights not made: {sorted(set(missing) - buffers)}")
    return model, made


# -- the comparison that decides ``correct`` --------------------------------

def served_gaps(made, cfg, prompt, tokens, control_bits=None,
                other_prefix=None):
    """Per served position, reference-best logit minus the served token's
    logit.  With ``control_bits`` also the same for the token each control
    puts first: the reference with int-rounded weights, and — with
    ``other_prefix``, another document's tokens — the reference over a
    sequence that has them in the shared prefix's place.  Returns {"sound":
    gaps, and a control's name: gaps}."""
    p, t = len(prompt), len(tokens)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(tokens, np.int32)])
    # the rows read: the served ones, their count rounded up to ROWS_TO (a
    # few shapes of head and of gap, not one a request)
    t_read = -(-t // ROWS_TO) * ROWS_TO
    padded = -(-(p - 1 + t_read) // PAD_TO) * PAD_TO
    ids = np.zeros(padded, np.int32)
    ids[:len(full) - 1] = full[:-1]
    nxt = np.zeros(padded, np.int32)
    nxt[:len(full) - 1] = full[1:]
    read = slice(p - 1, p - 1 + t_read)
    ref = mla_arch.logits(made, cfg, ids, rows=read)
    out = {"sound": np.asarray(
        check._gap_below_best(ref, jnp.asarray(nxt[read])))[:t]}
    if control_bits is None:
        return out

    def first_of(seq, **control):
        low = mla_arch.logits(made, cfg, seq, rows=read, **control)
        first = jnp.argmax(low, axis=-1).astype(jnp.int32)
        return np.asarray(check._gap_below_best(ref, first))[:t]

    out["int8"] = first_of(ids, weight_bits=control_bits)
    if other_prefix is not None:
        swapped = ids.copy()
        swapped[:len(other_prefix)] = other_prefix
        out["other_doc"] = first_of(swapped)
    return out


def sample_shared(finished, k, seed):
    """``k`` of the finished records: the longest (prompt + output) always,
    then one more on the longest's document, then one on another document,
    then the seed's draw of the rest; greedy ones only."""
    greedy = sorted((r for r in finished
                     if r["temperature"] == 0.0 and r["tokens"]),
                    key=lambda r: r["index"])
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rng = np.random.default_rng([int(seed), 0xC0DE])
    rest = [r for r in greedy if r is not longest]
    rng.shuffle(rest)
    same = [r for r in rest if r["tenant"] == longest["tenant"]][:1]
    other = [r for r in rest if r["tenant"] != longest["tenant"]][:1]
    picked = [longest] + same + other
    more = [r for r in rest if all(r is not x for x in picked)]
    return (picked + more)[:max(int(k), len(picked))]


# -- what the trie served, and what the pool holds --------------------------

def admissions(order):
    """Per submitted record that the engine admitted: (admission time on
    the harness's clock, prompt tokens, tokens adopted from the prefix
    trie), joined to the request log by order of submission; {} where the
    program has no such log."""
    from paddle_tpu import observability as obs
    if not hasattr(obs, "clock"):
        return {}
    records = [rec for rec in obs.get_request_log().records().values()
               if not any(ev["name"] == "rejected" for ev in rec)]
    if len(records) < len(order):
        return {}
    to_s = obs.clock.perf_counter_to_event_ms(0.0)   # event ms at clock 0
    out = {}
    for rec, events in zip(order, records[len(records) - len(order):]):
        for ev in events:
            if ev["name"] == "admitted":
                out[id(rec)] = ((ev["t_ms"] - to_s) * 1e-3,
                                len(rec.req.prompt),
                                int(ev["attrs"].get("prefix_hit_tokens", 0)))
                break
    return out


def live_positions(stamps, adopted):
    """The ticks with their live depth recounted as the pool holds it: a
    live request's own positions (prompt less what it adopted, plus the
    tokens it has) and every document with a live reader that adopted it,
    and no live reader that wrote it, once."""
    recs = [r for r in stamps["order"] if r.times]
    ticks = []
    for t_a, t_b, occ, _ in stamps["ticks"]:
        depth, wrote, read = 0, set(), {}
        for r in recs:
            if r.times[0] > t_b or (r.done and r.times[-1] < t_b):
                continue
            hit = adopted.get(id(r), (0, 0, 0))[2]
            depth += (len(r.req.prompt) - hit
                      + bisect.bisect_right(r.times, t_b))
            if hit:
                read[r.req.tenant] = hit
            else:
                wrote.add(r.req.tenant)
        depth += sum(h for tenant, h in read.items() if tenant not in wrote)
        ticks.append((t_a, t_b, occ, depth))
    return ticks


def mid_prefill_at_end(m, stamps, cell, adopted):
    """``serve_afmoe.mid_prefill_at_end`` where the trie serves part of a
    prompt: a judged request without a token by the window's end is left
    out only where it held its slot for fewer ticks than the part of its
    prompt the trie did not serve has chunks."""
    _, w1 = stamps["window"]
    chunk = int(cell["engine"]["prefill_chunk"])
    ends = [tk[1] for tk in stamps["ticks"]]
    out, keep, ttft_ms, waits = [], [], [], []
    for rec, ttft in zip(m["judged"], m["ttft_ms"]):
        hit = adopted.get(id(rec), (0, 0, 0))[2]
        needs = -(-(len(rec.req.prompt) - hit) // chunk)
        had = sum(1 for t in ends if rec.slot <= t <= w1)
        if not (rec.times and rec.times[0] <= w1) and had < needs:
            out.append({"index": rec.req.index,
                        "prompt_tokens": len(rec.req.prompt),
                        "adopted_tokens": hit, "chunks_needed": needs,
                        "ticks_had": had})
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
    ``serve_afmoe.run`` returns, plus ``admissions`` and, with
    ``control_bits``, both controls' rows under ``control`` (the
    other-document control's names start with ``other_doc.``)."""
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
         "tokens": eng.result(rec.rid), "temperature": rec.req.temperature,
         "tenant": rec.req.tenant, "in_window": rec.times[-1] >= w0}
        for rec in stamps["order"] if rec.done]
    del eng                     # the pool goes; the reference needs room
    gc.collect()

    adopted = admissions(stamps["order"])
    stamps = dict(stamps, ticks=live_positions(stamps, adopted))
    m = serve.measure(stamps, mix, seconds)
    failed_by_measure = m["failed"]
    m, mid_prefill = mid_prefill_at_end(m, stamps, cell, adopted)
    end_to_end = {
        "output_tok_s": m["tokens"] / seconds,
        "token_gap_p95_ms": stats.percentile(m["gaps_ms"], 95),
        "ttft_p95_ms": stats.percentile(m["ttft_ms"], 95),
        "setup_s": w0 - t_start,
    }
    say("setup", {"setup_s": w0 - t_start, "parts": parts})
    live = [tk[3] for tk in m["ticks"]] or [0]
    reserved = serve.cache_positions(cell["engine"])
    kv_pos = flops_bytes_mla.kv_bytes_per_position(cfg)
    cache = {"positions_reserved": reserved,
             "reserved_bytes": reserved * kv_pos,
             "live_tokens_mean": sum(live) / len(live),
             "live_tokens_max": max(live),
             "live_kv_bytes_mean": sum(live) / len(live) * kv_pos,
             "pool_peak_blocks_in_use": pool_peak}
    win = counters["window"] or {}
    in_win = [(p, h) for t, p, h in adopted.values() if w0 <= t <= w1]
    tick_ms = [(b - a) * 1e3 for a, b, _, _ in m["ticks"]]
    say("window", {
        "seconds": seconds, "ticks": len(m["ticks"]), "tokens": m["tokens"],
        "requests_judged": len(m["judged"]),
        "failed": m["failed"], "failed_by_serve_measure": failed_by_measure,
        "mid_prefill_at_end": mid_prefill,
        "requests_finished": sum(r["in_window"] for r in finished),
        "admitted_in_window": len(in_win),
        "prompt_tokens_admitted": sum(p for p, _ in in_win),
        "prompt_tokens_adopted": sum(h for _, h in in_win),
        "token_gap_ms": stats.summary(m["gaps_ms"]),
        "ttft_ms": stats.summary(m["ttft_ms"]),
        "tick_ms": stats.summary(tick_ms),
        "generator_late_ms": stats.summary(m["late_ms"]),
        "occupancy_mean": (sum(tk[2] for tk in m["ticks"])
                           / max(1, len(m["ticks"]))),
        "occupancy_by_fifth": [
            round(float(np.mean([tk[2] for tk in part])), 2)
            for part in np.array_split(np.asarray(m["ticks"]), 5)
            if len(part)],
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
    sample = sample_shared(pool, int(cell["check"]["sample"]), seed)
    shared = int(mix.get("shared_prefix_len", 0))
    prefixes = {r["tenant"]: r["prompt"][:shared] for r in finished}
    gaps = {name: [] for name in ("sound",) + CONTROLS}
    for r in sample:
        others = [p for tenant, p in sorted(prefixes.items())
                  if tenant != r["tenant"]]
        for name, g in served_gaps(
                made, cfg, r["prompt"], r["tokens"], control_bits,
                others[0] if others and shared else None).items():
            gaps[name].append(g)
    limits = cell["check"]["limits"]
    checks.extend(check.judge(gaps["sound"], limits))
    control = None
    if control_bits:
        control = check.judge(gaps["int8"], limits) + [
            dict(row, name="other_doc." + row["name"])
            for row in check.judge(gaps["other_doc"], limits)]
    say("check", {"reference_s": clock() - t, "requests": len(sample),
                  "documents": sorted({r["tenant"] for r in sample}),
                  "longest": max((len(r["prompt"]) + len(r["tokens"])
                                  for r in sample), default=0),
                  "positions": int(sum(len(g) for g in gaps["sound"])),
                  "reference_compile": compiles.drain(),
                  "compared": checks, "control": control})

    return {
        "cell": cell, "config": cfg, "seconds": seconds, **stamps, **m,
        "kernel_paths": paths, "end_to_end": end_to_end, "checks": checks,
        "cache": cache, "counters": counters,
        "admissions": sorted(adopted.values()),
        "control": control, "correct": all(c["ok"] for c in checks),
        "attempted": len(m["judged"]),
        "memory_peak_bytes": memory_peak,
    }
