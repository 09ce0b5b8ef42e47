"""Benchmark harness — prints ONE JSON line with the headline metric.

Measures MFU (and tokens/sec/chip) for Llama-3-8B-architecture training on
the available accelerator, per BASELINE.md's measurement plan + the round-1
verdict's corrections:

  * depth curve: runs the deepest layer count that fits HBM **and** a
    shallower point, so "MFU transfers to full depth" is measured, not
    asserted (detail.curve);
  * two FLOPs conventions reported side by side:
      - mfu_6nd:   6·N·D (params-only, no attention term — the convention
        BASELINE.md names);
      - mfu_attn:  6·N·D + 12·L·H·S²·B (adds causal-unhalved attention
        matmul FLOPs: QKᵀ and AV, fwd+2×bwd, H = hidden size);
    the headline value is mfu_6nd for comparability with round 1.
  * the heaviest config runs under the fastest strategy that fits:
    zero_stage=3 with NO remat when activations fit HBM (+4% MFU,
    measured round 4), selective-"dots" recompute as the fallback; each
    curve point records its ``remat`` mode.

Engineering note: a chip belongs to ONE process at a time, and a hard OOM
wedges the TPU client (every later allocation fails).  So the parent never
imports jax: the device query, the correctness lane and each measurement
run in their OWN subprocess, one after another, and the parent picks depths
analytically (14 bytes/param state + saved-activation estimate vs HBM).
There is no CPU path: a child that finds no TPU, or fails for any other
reason, fails the run, and the parent shows the child's stderr.

vs_baseline = MFU / 0.45 (the north-star target; the reference publishes no
number of its own — BASELINE.md).
"""

import argparse
import json
import os
import subprocess
import sys
import time

HIDDEN = 4096
INTER = 14336
PER_LAYER = (HIDDEN * HIDDEN + 2 * HIDDEN * 1024 + HIDDEN * HIDDEN
             + 3 * HIDDEN * INTER + 2 * HIDDEN)  # GQA attn + swiglu + norms


def n_params(layers, vocab):
    return layers * PER_LAYER + 2 * vocab * HIDDEN  # untied embed + head


def predicted_bytes(layers, vocab, batch, seq):
    """HBM estimate: bf16 params + fp32 master/m/v (14 B/param), saved
    matmul activations under the 'dots' remat policy (~100 KB/token/layer),
    fp32 logits working set (~3 copies)."""
    tokens = batch * seq
    state = n_params(layers, vocab) * 14
    acts = layers * tokens * 100_000
    logits = tokens * vocab * 4 * 3
    return state + acts + logits + int(1e9)  # +1 GB runtime slack


def require_tpu():
    """The first jax device, which must be a TPU: no bench path measures
    anything else.  Also places the compile cache (children only — the
    parent never gets here)."""
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"bench.py: needs a TPU; jax.devices()[0] is "
            f"{dev.platform}:{dev.device_kind}. Nothing was measured.\n")
        raise SystemExit(2)
    enable_compile_cache()
    return dev


def measure(layers, vocab, batch, seq, steps, warmup, remat: str = "dots"):
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.io import DataLoader, MMapTokenDataset
    from paddle_tpu.models import LlamaForCausalLM, llama3_8b_config
    from paddle_tpu.optimizer import AdamW

    hcg = dist.HybridCommunicateGroup(devices=jax.devices())
    dist.set_hybrid_group(hcg)
    pt.seed(0)
    cfg = llama3_8b_config(num_hidden_layers=layers, vocab_size=vocab,
                           recompute=(remat != "none"),
                           recompute_policy=("dots" if remat == "none"
                                             else remat),
                           max_position_embeddings=seq)
    model = LlamaForCausalLM(cfg)
    n = sum(int(np.prod(p.shape)) for _, p in
            model.named_parameters() if p.trainable)
    opt = AdamW(learning_rate=1e-4, weight_decay=0.01)
    step, params, opt_state = dist.build_train_step(model, opt, hcg=hcg,
                                                    zero_stage=3)

    # input pipeline through the native C++ loader (io/native.py): a token
    # bin on disk, mmap windows, threaded batch assembly, fetched *inside*
    # the timed loop — host input time is part of the MFU number (or
    # provably overlapped), per the round-3 verdict.  A loader that cannot
    # build raises here with the compiler's output.
    rng = np.random.RandomState(0)
    n_samples = 64 * batch
    toks = rng.randint(0, min(cfg.vocab_size, 65535),
                       n_samples * (seq + 1)).astype(np.uint16)
    f = tempfile.NamedTemporaryFile(suffix=".bin", delete=False)
    toks.tofile(f)
    f.close()
    ds = MMapTokenDataset(f.name, seq_len=seq + 1, stride=seq + 1)
    # prefetch_factor=1 → no Python prefetch thread (the C++ worker
    # pool already runs ahead); keeps generator shutdown deterministic
    dl = DataLoader(ds, batch_size=batch, shuffle=True, num_workers=2,
                    prefetch_factor=1)

    def _stream():
        while True:  # cycle epochs; the loader reshuffles each pass
            yield from dl

    _it = _stream()

    def next_batch():
        ids = next(_it)
        return dist.shard_batch({"input_ids": jnp.asarray(ids[:, :-1]),
                                 "labels": jnp.asarray(ids[:, 1:])}, hcg)

    try:
        key = jax.random.key(0)
        # AOT executable: XLA's compile-time memory analysis of the step
        # (resident args + transient temp) rides the point beside the
        # runtime peak, and the loop below pays no second jit compile
        step = step.lower(params, opt_state, next_batch(), key).compile()
        ma = step.memory_analysis()
        hbm = {"args": int(ma.argument_size_in_bytes),
               "temp": int(ma.temp_size_in_bytes),
               "output": int(ma.output_size_in_bytes)}
        loss = None
        for i in range(warmup):
            loss, params, opt_state = step(params, opt_state, next_batch(),
                                           jax.random.fold_in(key, i))
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for i in range(steps):
            loss, params, opt_state = step(
                params, opt_state, next_batch(),
                jax.random.fold_in(key, warmup + i))
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    finally:  # an OOM mid-loop must not leak the bin file / C++ workers
        _it.close()
        ds.close()
        os.unlink(f.name)
    hbm["peak"] = int(jax.local_devices()[0].memory_stats()
                      ["peak_bytes_in_use"])
    return (dt / steps, float(loss), n, cfg.hidden_size, hbm)


def run_single(args):
    """--single mode: one measurement in this process, one JSON line out."""
    import jax

    from paddle_tpu.observability.costmodel import profile_for_device

    dev = require_tpu()
    peak_flops = profile_for_device(dev).peak_bf16_flops
    step_time, loss, n, hidden, hbm = measure(
        args.layers, args.vocab, args.batch, args.seq,
        args.steps, args.warmup, remat=args.remat)
    tokens = args.batch * args.seq
    n_chips = len(jax.devices())
    f_6nd = 6.0 * n * tokens
    f_attn = f_6nd + 12.0 * args.layers * hidden * args.seq * tokens
    denom = step_time * peak_flops * n_chips
    point = {"layers": args.layers, "vocab": args.vocab,
             "batch": args.batch, "seq": args.seq, "params": n,
             "remat": args.remat,
             "step_time_s": round(step_time, 4),
             "tokens_per_sec_per_chip": round(tokens / step_time / n_chips),
             "hbm": hbm,
             "loss": round(loss, 4),
             "peak_bf16_flops": peak_flops,
             "mfu_6nd": round(f_6nd / denom, 4),
             "mfu_attn": round(f_attn / denom, 4)}
    print("POINT " + json.dumps(point))


def spawn_child(argv, tag, timeout, extra_env=None):
    """Run ``python bench.py *argv`` as its own process — the only thing
    that may hold the chip while it runs — and return the JSON after the
    ``tag`` line it prints.  The parent must still be off jax (checked):
    a parent that has touched jax holds the chip and the child hangs.
    Any failure is the run's failure, with the child's stderr shown."""
    assert "jax" not in sys.modules, \
        "bench.py parent imported jax: it would hold the chip its " \
        "children need"
    cmd = [sys.executable, os.path.abspath(__file__), *argv]
    env = dict(os.environ, **(extra_env or {}))
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        err = e.stderr if isinstance(e.stderr, str) else (
            e.stderr or b"").decode(errors="replace")
        sys.stderr.write(err)
        sys.stderr.write(f"bench.py: child {' '.join(argv)} timed out "
                         f"after {timeout} s\n")
        raise SystemExit(124) from None
    for line in r.stdout.splitlines():
        if r.returncode == 0 and line.startswith(tag + " "):
            return json.loads(line[len(tag) + 1:])
    sys.stderr.write(r.stderr)
    sys.stderr.write(f"bench.py: child {' '.join(argv)} exited "
                     f"{r.returncode} without a {tag} line\n")
    raise SystemExit(r.returncode or 1)


def spawn_point(layers, vocab, batch, seq, steps, warmup,
                timeout=480, extra_env=None, remat="dots"):
    return spawn_child(
        ["--single", "--layers", str(layers), "--vocab", str(vocab),
         "--batch", str(batch), "--seq", str(seq), "--steps", str(steps),
         "--warmup", str(warmup), "--remat", remat],
        "POINT", timeout, extra_env)


def run_lane(args):
    """--lane mode: the device query and (unless --no-lane) the
    correctness lane, in a child of their own."""
    import jax

    dev = require_tpu()
    limit = int(dev.memory_stats()["bytes_limit"])
    lane = None if args.no_lane else tpu_lane_summary()
    print("LANE " + json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "hbm_bytes_limit": limit},
        "lane": lane}))


# ---------------------------------------------------------------------------
# --op mode: the checked-in op-level perf harness (round-3 verdict #7).
# Reproduces the measurement tables that ops/norms.py and flags.py cite,
# so kernel perf claims and dispatch thresholds are re-derivable from the
# repo instead of resting on docstring numbers.  Results accumulate into
# BENCH_OPS.json (one section per op, device-tagged).
# ---------------------------------------------------------------------------

def _time_compiled(fn, args, steps, extra=1000):
    """Mean per-application wall time of a shape-preserving op.

      * applications are CHAINED in-graph (fori_loop, output feeds next
        input) — a per-call Python loop measures dispatch latency, not
        device time;
      * the chain reduces to ONE scalar whose host fetch is the barrier;
      * the per-application time is the two-point difference
        (wall(steps + extra) − wall(steps)) / extra, which cancels
        whatever fixed cost a launch and a fetch carry.

    Memory analysis comes from the single-application program.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    single = jax.jit(fn).lower(*args).compile()
    ma = single.memory_analysis()
    mem = {"args": int(ma.argument_size_in_bytes),
           "temp": int(ma.temp_size_in_bytes),
           "output": int(ma.output_size_in_bytes)}

    def wall(n_iters):
        chained = jax.jit(
            lambda first, *rest: jnp.sum(lax.fori_loop(
                0, n_iters, lambda i, acc: fn(acc, *rest), first
            ).astype(jnp.float32))
        ).lower(*args).compile()
        float(chained(*args))                       # warm + wait
        t0 = time.perf_counter()
        float(chained(*args))                       # scalar fetch = barrier
        return time.perf_counter() - t0

    per = (wall(steps + extra) - wall(steps)) / extra
    return per, mem


def run_op_rms_norm(steps):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.norms import rms_norm_reference
    from paddle_tpu.ops.pallas.rms_norm import rms_norm_pallas

    shapes = [(512, 65536), (4096, 32768), (2048, 16384), (8192, 8192),
              (8192, 4096)]
    dtypes = ["bfloat16", "float32"]
    rows = []
    for rows_n, dim in shapes:
        for dname in dtypes:
            dt = getattr(jnp, dname)
            key = jax.random.key(0)
            x = jax.random.normal(key, (rows_n, dim), dt)
            w = jnp.ones((dim,), dt)
            t_ref, m_ref = _time_compiled(
                lambda a, b: rms_norm_reference(a, b), (x, w), steps)
            t_pal, m_pal = _time_compiled(
                lambda a, b: rms_norm_pallas(a, b, 1e-6), (x, w), steps)
            nbytes = rows_n * dim * x.dtype.itemsize
            rows.append({"shape": [rows_n, dim], "dtype": dname,
                         "xla_ms": round(t_ref * 1e3, 4),
                         "pallas_ms": round(t_pal * 1e3, 4),
                         "speedup": round(t_ref / t_pal, 3),
                         # chained iterations let XLA keep sub-VMEM arrays
                         # resident (implied B/W exceeds HBM peak); only
                         # larger-than-VMEM rows compare HBM-bound kernels
                         "vmem_resident_caveat": nbytes < 128 * 2 ** 20,
                         "mem_xla": m_ref, "mem_pallas": m_pal})
    # re-derive the dispatch threshold: smallest row length whose bf16
    # speedup clears 1.1x on every measured point at or above it — the
    # flag default should equal this
    pref = dtypes[0]
    by_dim = {}
    for r in rows:
        if r["dtype"] == pref:
            by_dim.setdefault(r["shape"][1], []).append(r["speedup"])
    dims = sorted(by_dim)
    threshold = None
    for i, d in enumerate(dims):
        if all(min(by_dim[dd]) >= 1.1 for dd in dims[i:]):
            threshold = d
            break
    return {"steps": steps, "rows": rows,
            "derived_min_dim_threshold": threshold,
            "threshold_rule": "smallest dim with >=1.1x pallas speedup at "
                              f"every measured dim above it ({pref})",
            "conclusion": "no threshold clears the bar -> the Pallas "
                          "route stays disabled by default "
                          "(FLAGS_rms_norm_pallas_min_dim); the round-3 "
                          "1.73x claim was dispatch latency, not kernel "
                          "time" if threshold is None else
                          f"route rows >= {threshold}"}


def run_op_flash(steps, warmup):
    """Flash-attention block sweep at full-train-step MFU, one child per
    block pair (flags.py block-default provenance).  Runs in the PARENT:
    it must stay off jax so each child can take the chip."""
    blocks = [(256, 512), (512, 512), (512, 1024), (1024, 1024),
              (1024, 2048)]
    rows = []
    for bq, bkv in blocks:
        p = spawn_point(4, 8192, 2, 2048, steps, warmup,
                        extra_env={"FLAGS_flash_attention_block_q": str(bq),
                                   "FLAGS_flash_attention_block_kv":
                                       str(bkv)})
        rows.append({"block_q": bq, "block_kv": bkv,
                     "mfu_6nd": p["mfu_6nd"],
                     "step_time_s": p["step_time_s"]})
    best = max(rows, key=lambda r: r["mfu_6nd"])
    return {"workload": "llama3-arch 4L bs2 seq2048 vocab8192, zero3 + "
                        "dots remat, full train step", "steps": steps,
            "rows": rows, "best": best}


def run_op_decode_attention(steps):
    """Flash-decode vs XLA-math sweep over (max_length x batch x depth) —
    the measurement behind FLAGS_decode_attention_min_len and the b=8
    long-context serving claim (BENCH_DECODE.json decode rows).  Each row
    records the per-application time of both paths AND the dispatcher's
    chosen path for that shape, so the threshold is re-derivable."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import flags
    from paddle_tpu.ops.attention import (cached_decode_attention_reference,
                                          decode_attention_path)
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas

    # the serving model's head geometry (llama3-arch GQA 32/8, d=128)
    hq, hkv, d = 32, 8, 128
    grid = [(1, 2048), (8, 2048), (1, 8192), (8, 8192)]
    depth_pts = lambda L: sorted({128, L // 4, L - 1})
    extra, dtype = 1000, jnp.bfloat16
    rng = np.random.RandomState(0)
    rows = []
    for b, L in grid:
        for depth in depth_pts(L):
            q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), dtype)
            k = jnp.asarray(rng.normal(size=(b, L, hkv, d)), dtype)
            v = jnp.asarray(rng.normal(size=(b, L, hkv, d)), dtype)
            # per-row positions, serving-shaped: slots at heterogeneous
            # depths; max(pos) = depth is what the live-prefix read bounds
            pos = jnp.asarray([depth - (i * depth) // (2 * max(b - 1, 1))
                               for i in range(b)], jnp.int32)
            t_ref, _ = _time_compiled(
                lambda q_, k_, v_: cached_decode_attention_reference(
                    q_, k_, v_, pos), (q, k, v), steps, extra=extra)
            t_pal, _ = _time_compiled(
                lambda q_, k_, v_: decode_attention_pallas(q_, k_, v_, pos),
                (q, k, v), steps, extra=extra)
            path, why = decode_attention_path(b, 1, hq, hkv, d, L)
            row = {"batch": b, "max_length": L, "depth": int(depth),
                   "heads": [hq, hkv], "head_dim": d, "dtype": str(dtype.__name__),
                   "xla_ms": round(t_ref * 1e3, 4),
                   "pallas_ms": round(t_pal * 1e3, 4),
                   "speedup": round(t_ref / t_pal, 3) if t_pal else None,
                   "chosen_path": path}
            if why:
                row["fallback_reason"] = why
            if path == "pallas_decode":
                # kernel pre-flight (ISSUE 14) for the exact spec this
                # row's dispatch selected — static, rides the row so
                # BENCH_DECODE.json carries the VMEM/streamed evidence
                from paddle_tpu.static_analysis import (
                    analyze_kernels, decode_attention_spec, kernel_report)
                kspec = decode_attention_spec(b, 1, hq, hkv, d, kv_len=L)
                kr = kernel_report(kspec)
                row["kernel_preflight"] = {
                    "vmem_bytes": kr["vmem_bytes"],
                    "streamed_bytes": kr["streamed_bytes"],
                    "findings": len(kr["findings"])}
            rows.append(row)
            print(f"[decode-attn] b={b} L={L} depth={depth}: "
                  f"xla {t_ref*1e3:.3f} ms, pallas {t_pal*1e3:.3f} ms "
                  f"-> {path}", file=sys.stderr)

            # int8-KV re-sweep (ISSUE 13): same shape, cache quantized
            # per 128-token granule — the chunk the kernel dequantizes
            # inside its KV loop; the streamed-tail bytes halve, the
            # dispatch contract must not move
            gran = 128
            if L % gran:
                continue
            ng = L // gran

            def _q(x):
                g = x.reshape(b, ng, gran, hkv, d).astype(jnp.float32)
                sc = jnp.max(jnp.abs(g), axis=(2, 4)) / 127.0  # (b,ng,hkv)
                sc = jnp.maximum(sc, 1e-8)
                qi = jnp.round(g / sc[:, :, None, :, None]
                               ).astype(jnp.int8)
                return qi.reshape(b, L, hkv, d), sc

            k8, ks = _q(k)
            v8, vs = _q(v)
            t_ref8, _ = _time_compiled(
                lambda q_, k_, v_, ks_, vs_:
                    cached_decode_attention_reference(
                        q_, k_, v_, pos, k_scale=ks_, v_scale=vs_),
                (q, k8, v8, ks, vs), steps, extra=extra)
            t_pal8, _ = _time_compiled(
                lambda q_, k_, v_, ks_, vs_: decode_attention_pallas(
                    q_, k_, v_, pos, k_scale=ks_, v_scale=vs_),
                (q, k8, v8, ks, vs), steps, extra=extra)
            row8 = dict(row, dtype="int8+f32scale",
                        cache="int8",
                        xla_ms=round(t_ref8 * 1e3, 4),
                        pallas_ms=round(t_pal8 * 1e3, 4),
                        speedup=(round(t_ref8 / t_pal8, 3)
                                 if t_pal8 else None))
            if path == "pallas_decode":
                from paddle_tpu.static_analysis import (
                    decode_attention_spec, kernel_report)
                kr8 = kernel_report(decode_attention_spec(
                    b, 1, hq, hkv, d, kv_len=L, quantized=True,
                    n_granules=ng))
                row8["kernel_preflight"] = {
                    "vmem_bytes": kr8["vmem_bytes"],
                    "streamed_bytes": kr8["streamed_bytes"],
                    "findings": len(kr8["findings"])}
            rows.append(row8)
            print(f"[decode-attn] b={b} L={L} depth={depth} int8: "
                  f"xla {t_ref8*1e3:.3f} ms, pallas {t_pal8*1e3:.3f} ms",
                  file=sys.stderr)
    return {"steps": steps, "rows": rows,
            "dispatch_min_len": int(flags.flag("decode_attention_min_len")),
            "block_kv_cap": int(flags.flag("decode_attention_block_kv")),
            "read_model": "pallas rows stream only the live cache prefix "
                          "(per-row positions ride in as scalar prefetch "
                          "and size each row's in-kernel block walk) — "
                          "per-step time tracks depth; "
                          "xla rows stream the whole max_length every step",
            "note": "chosen_path records the cached_decode_attention "
                    "dispatch for each shape at the committed flag default"}


_OP_SECTIONS = {"rms_norm": lambda a: run_op_rms_norm(a.steps),
                "flash": lambda a: run_op_flash(a.steps, a.warmup),
                "decode_attention": lambda a: run_op_decode_attention(a.steps)}


def run_op_bench(args):
    if args.op == "flash":      # spawns children: this process stays off jax
        device = spawn_child(["--lane", "--no-lane"], "LANE",
                             timeout=300)["device"]
        kind, platform = device["kind"], device["platform"]
    else:
        dev = require_tpu()
        kind, platform = dev.device_kind, dev.platform
    section = _OP_SECTIONS[args.op](args)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_OPS.json")
    blob = {}
    if os.path.exists(path):
        with open(path) as f:
            blob = json.load(f)
    section["device"] = kind
    section["platform"] = platform
    section["when"] = time.strftime("%Y-%m-%d")
    blob[args.op] = section
    with open(path, "w") as f:
        json.dump(blob, f, indent=1)
    print(json.dumps({"metric": f"op_bench_{args.op}",
                      "value": 1, "unit": "artifact",
                      "vs_baseline": 0.0,
                      "detail": {"artifact": "BENCH_OPS.json",
                                 "section": section}}))


# ---------------------------------------------------------------------------
# --decode mode: the serving perf harness (round-4 verdict #1).
# The serving stack (decode scan, cached prefill, fused_multi_transformer)
# shipped in rounds 3-4 with zero perf numbers; this measures it.  Results
# accumulate into BENCH_DECODE.json.  All timings follow the discipline of
# _time_compiled: iterations chained IN-GRAPH, one scalar fetch as the
# barrier, two-point difference to cancel the fixed launch-and-fetch cost
# and (for decode) the prefill cost.
# ---------------------------------------------------------------------------

def _two_point(build, n1, n2, reps=2):
    """``build(n)`` -> zero-arg callable running n chained iterations on
    device and returning a scalar.  Per-iteration seconds via the two-point
    difference; ``reps`` walls each, min taken (host jitter)."""
    f1, f2 = build(n1), build(n2)
    float(f1())
    float(f2())                                    # compile + warm both

    def wall(f):
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            float(f())                             # scalar fetch = barrier
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    return (wall(f2) - wall(f1)) / (n2 - n1)


def _decode_model(max_pos=8192):
    """The bench's measured model: the 940M llama3-arch point of the MFU
    curve (4 layers, vocab 8192 — BENCH_r05 head config), bf16, eval."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, llama3_8b_config

    pt.seed(0)
    cfg = llama3_8b_config(num_hidden_layers=4, vocab_size=8192,
                           max_position_embeddings=max_pos)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n = sum(int(np.prod(p.shape)) for _, p in model.named_parameters())
    return model, model.state_dict(include_buffers=True), n


def _prefill_latency(model, params, batch, prompt, n1=4, n2=12):
    """Seconds for ONE prefill pass (static pos=0 → the flash-kernel
    route when eligible), chained on the cache carry."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from paddle_tpu.models.generation import init_kv_cache
    from paddle_tpu.nn.layer import bind_params

    vocab = model.config.vocab_size
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, vocab, (batch, prompt)), jnp.int32)
    cache0 = init_kv_cache(model.config, batch, prompt)

    def build(n):
        @jax.jit
        def f(params, ids, cache):
            with bind_params(model, params):
                def body(i, carry):
                    cache, acc, ids = carry
                    logits, cache = model.decode_step(ids, cache, 0)
                    s = jnp.sum(logits[:, -1].astype(jnp.float32))
                    # feed the result back into the next iteration's
                    # tokens — without this data dependency XLA hoists
                    # the whole forward out of the loop as invariant
                    # (observed: "0.3 ms" for a 15-TFLOP prefill)
                    ids = (ids + jnp.abs(s).astype(jnp.int32) % 2) % vocab
                    return (cache, acc + s, ids)
                _, acc, _ = lax.fori_loop(0, n, body,
                                          (cache, jnp.float32(0.0), ids))
                return acc
        g = f.lower(params, ids, cache0).compile()
        return lambda: g(params, ids, cache0)

    return _two_point(build, n1, n2)


def _decode_per_step(model, params, batch, prompt, max_len,
                     t1=16, t2=144):
    """Seconds per steady-state greedy decode step (the incremental
    cache-carrying path, traced pos → XLA math attention).  The scan of
    t2 vs t1 tokens differences away BOTH the fixed launch-and-fetch cost
    and the prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from paddle_tpu.models.generation import init_kv_cache
    from paddle_tpu.nn.layer import bind_params

    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, model.config.vocab_size, (batch, prompt)), jnp.int32)
    cache0 = init_kv_cache(model.config, batch, max_len)
    # quantized-decode hooks (models/quantized.py): dequant-in-graph
    bind_target = getattr(model, "unwrapped", model)
    prepare = getattr(model, "_prepare_params", lambda p: p)

    def build(t):
        @jax.jit
        def f(params, ids, cache):
            with bind_params(bind_target, prepare(params)):
                logits, cache = model.decode_step(ids, cache, 0)
                nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

                def step(carry, _):
                    cache, pos, tok = carry
                    logits, cache = model.decode_step(tok[:, None], cache,
                                                      pos)
                    new = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                    return (cache, pos + 1, new), tok
                carry, toks = lax.scan(
                    step, (cache, jnp.int32(prompt), nxt), None, length=t)
                return jnp.sum(toks) + jnp.sum(carry[2])
        g = f.lower(params, ids, cache0).compile()
        return lambda: g(params, ids, cache0)

    return _two_point(build, t1, t2)


def _generate_e2e(model, batch, prompt, new_tokens, max_len):
    """End-to-end wall seconds of the user-facing ``generate()`` call
    (compiled-program cache warm) — includes host dispatch and the token
    fetch, i.e. the latency a serving user actually observes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, model.config.vocab_size, (batch, prompt)), jnp.int32)
    out = model.generate(ids, max_new_tokens=new_tokens,
                         max_length=max_len)          # compile + warm
    np.asarray(out)
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        out = model.generate(ids, max_new_tokens=new_tokens,
                             max_length=max_len)
        np.asarray(out)                                # host fetch barrier
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def _fmt_weights(layers, embed, heads, head_dim, ffn):
    """Random bf16 weight lists in fused_multi_transformer's paddle layout."""
    import jax
    import jax.numpy as jnp

    ks = iter(jax.random.split(jax.random.key(0), layers * 8))

    def mk(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32) *
                scale).astype(jnp.bfloat16)

    s_attn = (2.0 / embed) ** 0.5
    s_ffn = (2.0 / ffn) ** 0.5
    return {
        "ln_scales": [jnp.ones((embed,), jnp.bfloat16)
                      for _ in range(layers)],
        "ln_biases": [jnp.zeros((embed,), jnp.bfloat16)
                      for _ in range(layers)],
        "qkv_weights": [mk((3, heads, head_dim, embed), s_attn)
                        for _ in range(layers)],
        "qkv_biases": None,
        "linear_weights": [mk((heads * head_dim, embed), s_attn)
                           for _ in range(layers)],
        "linear_biases": None,
        "ffn_ln_scales": [jnp.ones((embed,), jnp.bfloat16)
                          for _ in range(layers)],
        "ffn_ln_biases": [jnp.zeros((embed,), jnp.bfloat16)
                          for _ in range(layers)],
        "ffn1_weights": [mk((embed, ffn), s_attn) for _ in range(layers)],
        "ffn1_biases": None,
        "ffn2_weights": [mk((ffn, embed), s_ffn) for _ in range(layers)],
        "ffn2_biases": None,
    }


def _mht_unfused(x, w, cache_kvs, time_step, epsilon=1e-5):
    """The SAME stack as fused_multi_transformer, written the way a
    nn.Layer stack traces it: a Python loop of per-layer primitive calls
    (layer_norm, einsum, cached math attention, matmuls).  The comparator
    that prices whether the whole-stack op buys anything under XLA."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.attention import cached_decode_attention

    b, s, _ = x.shape
    out = x
    new_caches = []
    pos = time_step
    for i in range(len(w["qkv_weights"])):
        residual = out
        h = F.layer_norm(out, [out.shape[-1]], w["ln_scales"][i],
                         w["ln_biases"][i], epsilon=epsilon)
        wq = w["qkv_weights"][i]
        _, nh, hd, e = wq.shape
        qkv = jnp.einsum("bse,cnhe->cbsnh", h, wq)
        q, k, v = qkv[0], qkv[1], qkv[2]
        cache = cache_kvs[i]
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.swapaxes(k, 1, 2).astype(cache.dtype)[None],
            (0, 0, 0, pos, 0))
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.swapaxes(v, 1, 2).astype(cache.dtype)[None],
            (1, 0, 0, pos, 0))
        new_caches.append(cache)
        attn = cached_decode_attention(q, jnp.swapaxes(cache[0], 1, 2),
                                       jnp.swapaxes(cache[1], 1, 2), pos)
        out = residual + attn.reshape(b, s, nh * hd) @ w["linear_weights"][i]
        residual = out
        h = F.layer_norm(out, [out.shape[-1]], w["ffn_ln_scales"][i],
                         w["ffn_ln_biases"][i], epsilon=epsilon)
        h = F.gelu(h @ w["ffn1_weights"][i]) @ w["ffn2_weights"][i]
        out = residual + h
    return out, new_caches


def _fused_vs_stack(batch=1, prompt=8, max_len=1024, t1=8, t2=72,
                    layers=2, embed=2048, heads=16, head_dim=128,
                    ffn=8192):
    """fused_multi_transformer (one whole-stack op call) vs the identical
    math as a per-layer loop, same weights, both jitted end-to-end —
    per-step decode time from chained scans.  (Numerical parity of the
    two formulations is a CPU-lane oracle test, tests/test_breadth_ops.py
    + test_autograd_quant_fused.py; the chip run times the two paths as
    separate programs.)"""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import fused_multi_transformer
    w = _fmt_weights(layers, embed, heads, head_dim, ffn)
    x0 = (jax.random.normal(jax.random.key(1), (batch, prompt, embed),
                            jnp.float32)).astype(jnp.bfloat16)
    caches0 = [jnp.zeros((2, batch, heads, max_len, head_dim),
                         jnp.bfloat16) for _ in range(layers)]

    def fused_step(x, caches, pos):
        return fused_multi_transformer(
            x, w["ln_scales"], w["ln_biases"], w["qkv_weights"],
            w["qkv_biases"], w["linear_weights"], w["linear_biases"],
            w["ffn_ln_scales"], w["ffn_ln_biases"], w["ffn1_weights"],
            w["ffn1_biases"], w["ffn2_weights"], w["ffn2_biases"],
            cache_kvs=caches, time_step=pos)

    def stack_step(x, caches, pos):
        return _mht_unfused(x, w, caches, pos)

    def build_for(step_fn):
        def build(t):
            @jax.jit
            def f(x0, caches):
                out, caches = step_fn(x0, caches, 0)     # prefill
                def body(carry, _):
                    x, caches, pos = carry
                    out, caches = step_fn(x, caches, pos)
                    return (out[:, -1:], caches, pos + 1), None
                carry, _ = jax.lax.scan(
                    body, (out[:, -1:], caches, jnp.int32(prompt)), None,
                    length=t)
                return jnp.sum(carry[0].astype(jnp.float32))
            g = f.lower(x0, caches0).compile()
            return lambda: g(x0, caches0)
        return build

    per_fused = _two_point(build_for(fused_step), t1, t2)
    per_stack = _two_point(build_for(stack_step), t1, t2)
    return {"dims": {"layers": layers, "embed_dim": embed, "heads": heads,
                     "head_dim": head_dim, "ffn_dim": ffn, "batch": batch,
                     "prompt": prompt, "max_length": max_len,
                     "dtype": "bfloat16"},
            "parity": "CPU-lane oracle tests (see docstring)",
            "fused_per_step_ms": round(per_fused * 1e3, 4),
            "stack_per_step_ms": round(per_stack * 1e3, 4),
            "fused_over_stack": round(per_stack / per_fused, 3)}


def _cache_hbm_row(eng):
    """Per-step KV-cache residency accounting (BASELINE.md graph-lint
    conventions): resident bytes with the step's cache operand donated
    (1x, the shipped configuration) vs the un-donated double-buffer
    (2x) the static_analysis donation rule exists to catch."""
    cb = int(eng.cache_hbm_bytes)
    return {"cache_bytes": cb,
            "per_step_resident_bytes": {"donated": cb,
                                        "no_donation": 2 * cb},
            "step_cache_donated": True,
            "graph_lint_findings": len(eng.lint_step())}


def _mesh_preflight_row(eng, mesh="mp2dp2"):
    """Mesh pre-flight snapshot (ISSUE 8, BASELINE.md "Mesh pre-flight
    conventions"): the engine's once-jitted step linted under its
    DECLARED mp2dp2 shardings — an abstract mesh, so this runs on any
    host — with the per-axis predicted collective bytes per step, the
    predicted peak HBM per device, and the cache cross-check.  findings
    must be 0: the serving layouts are pre-validated for the ROADMAP
    item-1 mesh deployment before any multi-chip compile exists."""
    pf = eng.mesh_preflight(mesh)
    return {"mesh": pf["mesh"],
            "findings": len(pf["findings"]),
            "comm_bytes_per_step_per_axis": {
                a: row["bytes_per_step"]
                for a, row in pf["comm"]["per_axis"].items()},
            "predicted_peak_hbm_bytes_per_device":
                pf["hbm"]["peak_bytes_per_device"],
            "predicted_cache_bytes_per_device":
                pf["hbm"]["cache_bytes_per_device"],
            "cache_check": pf["cache_check"]}


def _kernel_preflight_row(eng):
    """Kernel pre-flight snapshot (ISSUE 14, BASELINE.md "Kernel
    pre-flight conventions"): static VMEM/bounds/alignment/
    streamed-bytes analysis of the Pallas kernels this engine's
    dispatch would select, projected to the TPU-eligible geometry — no
    compile, no device.  findings must be 0: the serving layouts are
    pre-validated against kernel VMEM OOMs and index-map bugs."""
    kp = eng.kernel_preflight()
    return {"vmem_bytes": kp["vmem_bytes"],
            "vmem_budget_frac": kp["vmem_budget_frac"],
            "streamed_bytes": kp["streamed_bytes"],
            "findings": len(kp["findings"])}


def _serving_bench(model):
    """Continuous-batching engine under a Poisson-ish synthetic arrival
    trace (paddle_tpu/serving): exponential inter-arrival gaps measured
    in scheduler ticks, mixed prompt/output lengths, fixed seed.  The
    whole trace runs twice through the SAME engine — the first pass pays
    every compile (one step program + one prefill program per prompt
    bucket), the second is the steady-state measurement.  Reported:
    wall tokens/s of the timed pass, mean slot occupancy (the quantity
    continuous batching exists to maximise), and the engine's own trace
    counters proving the step function compiled once."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine

    slots, max_len, n_req = 8, 2048, 48
    plo, phi, nlo, nhi, mean_gap = 32, 256, 32, 128, 2.0
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size
    prompts = [rng.randint(0, vocab, rng.randint(plo, phi + 1))
               .astype(np.int32) for _ in range(n_req)]
    news = rng.randint(nlo, nhi + 1, n_req)
    arrivals = np.cumsum(rng.exponential(mean_gap, n_req)).astype(int)
    eng = ServingEngine(model, num_slots=slots, max_length=max_len)

    def run_trace():
        rids, occ, t = [], [], 0
        n_sub = 0
        while n_sub < n_req or eng.num_active or eng.queue_depth:
            while n_sub < n_req and arrivals[n_sub] <= t:
                rids.append(eng.submit(prompts[n_sub],
                                       max_new_tokens=int(news[n_sub])))
                n_sub += 1
            eng.step()
            occ.append(eng.last_occupancy)
            t += 1
        return rids, occ

    run_trace()                                    # compile + warm
    t0 = time.perf_counter()
    rids, occ = run_trace()                        # steady-state pass
    wall = time.perf_counter() - t0
    toks = sum(len(eng.result(r)) for r in rids)
    out = {"num_slots": slots, "max_length": max_len,
           "requests": n_req,
           "prompt_len_range": [plo, phi],
           "new_tokens_range": [nlo, nhi],
           "arrival": f"exponential inter-arrival, mean {mean_gap} "
                      f"ticks, fixed seed",
           "wall_s": round(wall, 4),
           "generated_tokens": int(toks),
           "tokens_per_sec": round(toks / wall, 1),
           "mean_slot_occupancy": round(float(np.mean(occ)) / slots, 3),
           "step_traces": eng.step_traces,
           "prefill_traces": eng.prefill_traces,
           # cache HBM accounting (ISSUE 6): the once-jitted step takes
           # and returns the full cache; its donate_argnums alias lets
           # XLA reuse the buffer in place, so a tick keeps 1x the cache
           # resident instead of the 2x an un-donated carry pins — the
           # graph-lint donation rule guards the 1x
           "cache_hbm": _cache_hbm_row(eng),
           # mesh pre-flight (ISSUE 8): the same step, pre-validated
           # for the mp2dp2 deployment it will run under when ROADMAP
           # item 1 lands — predicted comm + per-device HBM, 0 findings
           "mesh_preflight": _mesh_preflight_row(eng),
           # kernel pre-flight (ISSUE 14): the Pallas kernels this
           # layout's dispatch would select, statically checked for
           # VMEM fit / bounds / alignment — 0 findings
           "kernel_preflight": _kernel_preflight_row(eng),
           # SLO snapshot straight from the observability registry (the
           # engine's own series; BASELINE.md conventions) — TTFT/TPOT/
           # queue-wait percentiles span BOTH passes, so the warm pass's
           # compile stalls sit in the tail, not the median
           "metrics": eng.metrics(),
           "note": "second pass through a warm engine; occupancy is "
                   "busy slots / num_slots averaged over ticks "
                   "(idle arrival gaps included); metrics histograms "
                   "span both passes"}
    out["paged"] = _paged_serving_bench(model)
    out["chunked"] = _chunked_serving_bench(model)
    return out


def _chunked_serving_bench(model):
    """Head-of-line-blocking A/B (ISSUE 5): the SAME trace — short
    requests decoding, a LONG prompt arriving mid-decode, more shorts
    behind it — through the wave engine and the chunked mixed-step
    engine.  The reported number is the p99 of the per-tick wall time
    over ticks where decodes were in flight (what an in-flight request
    experiences as its inter-token gap): the wave engine's admission
    tick dispatches the whole long prefill before the decode step, so
    its tail spikes by a full prefill latency; the chunked engine bounds
    every tick at num_slots + prefill_chunk tokens, so its p99 stays
    near its p50.  TPOT percentiles from both engines' registries ride
    along, plus chunk-queue depth and the budget-1 trace counters."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine

    slots, max_len, long_len, chunk = 8, 2048, 1024, 256
    plo, phi, nlo, nhi = 32, 64, 64, 96
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size
    shorts = [rng.randint(0, vocab, rng.randint(plo, phi + 1))
              .astype(np.int32) for _ in range(2 * slots)]
    long_p = rng.randint(0, vocab, long_len).astype(np.int32)
    news = rng.randint(nlo, nhi + 1, 2 * slots + 1)

    def run_trace(eng):
        """Fill the slots with shorts, tick until steady decode, drop
        the long prompt in, keep shorts arriving; per-tick wall times
        are recorded only while decodes are in flight."""
        ticks = []
        for i in range(slots):
            eng.submit(shorts[i], max_new_tokens=int(news[i]))
        for _ in range(4):
            eng.step()
        eng.submit(long_p, max_new_tokens=int(news[slots]))
        n_sub = slots
        while eng.num_active or eng.queue_depth or eng.num_pending:
            if n_sub < len(shorts):
                eng.submit(shorts[n_sub],
                           max_new_tokens=int(news[n_sub + 1]))
                n_sub += 1
            busy = eng.num_active > 0
            t0 = time.perf_counter()
            eng.step()
            if busy:
                ticks.append((time.perf_counter() - t0) * 1e3)
        return ticks

    def measure(eng):
        run_trace(eng)                             # compile + warm
        return run_trace(eng)                      # steady-state pass

    wave = ServingEngine(model, num_slots=slots, max_length=max_len)
    ck = ServingEngine(model, num_slots=slots, max_length=max_len,
                       chunked=True, prefill_chunk=chunk)
    tw = measure(wave)
    tc = measure(ck)

    def pct(v, q):
        return round(float(np.percentile(v, q)), 3)

    cm = ck.metrics()
    return {"num_slots": slots, "max_length": max_len,
            "long_prompt_len": long_len, "prefill_chunk": chunk,
            "short_prompt_len_range": [plo, phi],
            "trace": f"{slots} shorts decoding, {long_len}-token prompt "
                     f"arrives mid-decode, {slots} more shorts behind it",
            "tick_ms_wave": {"p50": pct(tw, 50), "p99": pct(tw, 99),
                             "max": pct(tw, 100)},
            "tick_ms_chunked": {"p50": pct(tc, 50), "p99": pct(tc, 99),
                                "max": pct(tc, 100)},
            "hol_p99_ratio_wave_over_chunked": round(
                pct(tw, 99) / max(pct(tc, 99), 1e-9), 2),
            "tpot_ms_wave": wave.metrics()["tpot_ms"],
            "tpot_ms_chunked": cm["tpot_ms"],
            "chunk_queue_depth": cm["chunked"]["chunk_queue_depth"],
            "prefill_chunks_2pass": cm["chunked"]["prefill_chunks"],
            "step_traces": ck.step_traces,
            "prefill_traces": ck.prefill_traces,
            "note": "per-tick wall time over decode-active ticks of the "
                    "warm second pass; the wave row's tail carries the "
                    "whole-prompt prefill stall, the chunked row's tail "
                    "is bounded by the chunk budget (TPOT accounting "
                    "conventions in BASELINE.md)"}


def _slo_serving_bench(model):
    """Goodput-under-SLO A/B (ISSUE 12): the SAME seeded heavy-tail
    load (loadgen: Poisson arrivals, Zipf-bucketed long-prompt mix,
    shared-prefix tenants) replayed through the wave engine and the
    chunked mixed-step engine, judged against one (TTFT p99, TPOT p99)
    deadline pair.  Targets are derived from the CHUNKED engine's own
    measured pass — p99 × 1.5 headroom — then both engines' RequestLogs
    are joined against them post hoc (slo_report with explicit
    targets), so the comparison is one fixed ruler, not per-engine
    flags.  The wave engine's whole-prompt prefill stalls inflate
    in-flight requests' TPOT past the ruler; the chunked engine bounds
    every tick, so its goodput must be strictly higher on this mix.
    A third identical replay through each warm engine must reproduce
    the second's timeline signature and sampled outputs exactly — the
    seeded-loadgen determinism contract (BASELINE.md "SLO accounting
    conventions")."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import LoadSpec, ServingEngine, generate_load
    from paddle_tpu.serving import replay as lg_replay

    slots, max_len, chunk, n_req = 8, 2048, 256, 32
    buckets, out_med, out_lo, out_hi = (32, 64, 1024), 48.0, 16, 96
    # the long-prompt mix: the top bucket is a whole-prompt prefill
    # stall several in-flight decode lifetimes long, and zipf a=1.0
    # gives it real mass — the HOL pressure chunked prefill exists for
    spec = LoadSpec(
        n_requests=n_req, vocab=model.config.vocab_size,
        arrival="poisson", mean_gap=1.0,
        prompt_dist="zipf", prompt_buckets=buckets, prompt_zipf_a=1.0,
        prompt_max=max(buckets),
        output_dist="lognormal", output_median=out_med, output_sigma=0.5,
        output_min=out_lo, output_max=out_hi,
        tenants=2, shared_prefix_len=4)
    load = generate_load(spec, seed=11)

    def measure(eng):
        lg_replay(eng, load)                  # A: compile + warm
        b = lg_replay(eng, load)              # B: steady-state measure
        c = lg_replay(eng, load)              # C: determinism replay
        return b, c

    wave_b, wave_c = measure(
        ServingEngine(model, num_slots=slots, max_length=max_len))
    ck_b, ck_c = measure(
        ServingEngine(model, num_slots=slots, max_length=max_len,
                      chunked=True, prefill_chunk=chunk))
    # the ruler: chunked pass-B observed p99s with 1.5x headroom
    t_ttft = round(ck_b["slo"]["ttft_ms"]["p99"] * 1.5, 3)
    t_tpot = round(ck_b["slo"]["tpot_ms"]["p99"] * 1.5, 3)
    log = obs.get_request_log()

    def judge(rep):
        slo = log.slo_report(since_uid=rep["mark"],
                             until_uid=rep["end_mark"], ttft_ms=t_ttft,
                             tpot_ms=t_tpot, wall_s=rep["wall_s"])
        return {"goodput": slo["goodput"],
                "goodput_tok_s": slo["goodput_tok_s"],
                "attained": slo["attained"],
                "violations": slo["violations"],
                "ttft_ms": slo["ttft_ms"], "tpot_ms": slo["tpot_ms"],
                "rejected": rep["rejected"],
                "generated_tokens": rep["generated_tokens"],
                "ticks": rep["ticks"],
                "step_traces": max(rep["step_traces"])}

    wave_row, ck_row = judge(wave_b), judge(ck_b)
    deterministic = (
        wave_b["signature"] == wave_c["signature"]
        and wave_b["outputs"] == wave_c["outputs"]
        and ck_b["signature"] == ck_c["signature"]
        and ck_b["outputs"] == ck_c["outputs"])
    return {
        "num_slots": slots, "max_length": max_len,
        "prefill_chunk": chunk, "requests": n_req,
        "load": {"arrival": "poisson, mean gap 1.0 ticks",
                 "prompt_mix": f"zipf-bucketed {list(buckets)} a=1.0",
                 "output_mix": f"lognormal median {out_med} "
                               f"clamp [{out_lo},{out_hi}]",
                 "tenants": 2, "shared_prefix_len": 4, "seed": 11},
        "slo_targets_ms": {"ttft_p99": t_ttft, "tpot_p99": t_tpot,
                           "rule": "chunked measured pass p99 x 1.5"},
        "wave": wave_row,
        "chunked": ck_row,
        "chunked_strictly_better": ck_row["goodput"] > wave_row["goodput"],
        "deterministic_replay": deterministic,
        "note": "same seeded load through both engines (pass A compiles, "
                "B measures, C replays); goodput = fraction of ALL "
                "submitted requests (rejections included) retiring "
                "within both deadlines, TTFT measured from submit "
                "(BASELINE.md 'SLO accounting conventions')"}


def _paged_serving_bench(model):
    """Paged-KV engine over a SHARED-PROMPT trace: every second request
    opens with the same system prompt (full KV blocks of it), so the
    prefix cache should adopt those blocks instead of recomputing them.
    Reported against the pool: blocks in use at peak (the HBM the paged
    cache actually committed) vs the preallocated pool, the prefix-cache
    hit rate over all prompt tokens, and suffix-only prefill compute.
    Conventions in BASELINE.md (cache-memory accounting)."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine

    slots, max_len, n_req, bl = 8, 2048, 48, 128
    sys_len, plo, phi, nlo, nhi, mean_gap = 256, 32, 256, 32, 128, 2.0
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size
    sys_prompt = rng.randint(0, vocab, sys_len).astype(np.int32)
    prompts = []
    for i in range(n_req):
        tail = rng.randint(0, vocab,
                           rng.randint(plo, phi + 1)).astype(np.int32)
        # every second request shares the system prompt
        prompts.append(np.concatenate([sys_prompt, tail])
                       if i % 2 else tail)
    news = rng.randint(nlo, nhi + 1, n_req)
    arrivals = np.cumsum(rng.exponential(mean_gap, n_req)).astype(int)
    eng = ServingEngine(model, num_slots=slots, max_length=max_len,
                        paged=True, block_len=bl)

    def run_trace():
        rids, occ, t, n_sub = [], [], 0, 0
        while n_sub < n_req or eng.num_active or eng.queue_depth:
            while n_sub < n_req and arrivals[n_sub] <= t:
                rids.append(eng.submit(prompts[n_sub],
                                       max_new_tokens=int(news[n_sub])))
                n_sub += 1
            eng.step()
            occ.append(eng.last_occupancy)
            t += 1
        return rids, occ

    run_trace()                                    # compile + warm
    t0 = time.perf_counter()
    rids, occ = run_trace()                        # steady-state pass
    wall = time.perf_counter() - t0
    toks = sum(len(eng.result(r)) for r in rids)
    st = eng.kv.stats
    prompt_tokens = int(sum(len(p) for p in prompts))
    return {"num_slots": slots, "max_length": max_len,
            "block_len": bl, "pool_blocks": eng.kv.num_blocks,
            "requests": n_req, "shared_prompt_len": sys_len,
            "trace": "every 2nd request opens with the shared system "
                     "prompt; exponential inter-arrival, fixed seed",
            "wall_s": round(wall, 4),
            "generated_tokens": int(toks),
            "tokens_per_sec": round(toks / wall, 1),
            "mean_slot_occupancy": round(float(np.mean(occ)) / slots, 3),
            "peak_blocks_in_use": st["peak_blocks_in_use"],
            "peak_pool_occupancy": round(
                st["peak_blocks_in_use"] / eng.kv.usable_blocks, 3),
            "blocks_cached_end": eng.kv.cached_blocks(),
            "evictions": st["evictions"],
            "prefix_hit_tokens_2pass": st["prefix_hit_tokens"],
            "prefix_hit_rate": round(
                st["prefix_hit_tokens"] / (2 * prompt_tokens), 3),
            "prefill_tokens_computed_2pass": eng.prefill_tokens_computed,
            "step_traces": eng.step_traces,
            "prefill_traces": eng.prefill_traces,
            "cache_hbm": _cache_hbm_row(eng),
            "mesh_preflight": _mesh_preflight_row(eng),
            "kernel_preflight": _kernel_preflight_row(eng),
            # registry snapshot: percentiles + the pool's cache
            # accounting (metrics.kv_cache.prefix_hit_rate uses admitted
            # prompt tokens as denominator, so it matches the
            # prefix_hit_rate field above by construction)
            "metrics": eng.metrics(),
            "note": "same warm-engine two-pass protocol as the "
                    "contiguous row; hit counters span BOTH passes "
                    "(hit_rate denominator = 2x trace prompt tokens)"}


def _spec_decode_bench(model):
    """Speculative-decoding A/B (ISSUE 7): the SAME trace through a
    plain engine and a spec engine (``spec_decode=True``), twice over —

      * a **repetition-heavy** trace (motif-tiled prompts, the
        summarisation/code-edit shape prompt-lookup drafting targets):
        the self-drafter should land multi-token accepts, so
        ``accepted_per_step`` > 1 and wall tok/s rises toward the
        acceptance-rate multiple of the weight-stream bound;
      * an **adversarial low-match** trace (every prompt a permutation —
        no repeated n-gram for the drafter to match): accepts stay near
        1, and the number that matters is parity — spec outputs must be
        token-identical to plain greedy outputs even while every draft
        is being rejected and rolled back.

    Accounting conventions (BASELINE.md): tok/s counts COMMITTED tokens
    only — drafted/rejected tokens never enter any throughput number;
    ``draft_hit_rate`` = committed draft tokens / proposed draft tokens.
    On CPU this is a plumbing smoke (the step is compute-bound, so the
    accept-rate win shows up in ticks, not ms); the claim that
    accepted_per_step multiplies tok/s at the weight-stream bound is a
    TPU measurement, recorded pending like growth_check_b8."""
    import numpy as np

    from paddle_tpu.serving import ServingEngine

    slots, max_len, spec_k, n_req = 8, 2048, 4, 24
    motif_len, reps, nnew = 16, 12, 96
    plo, phi = 64, 192
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size

    # repetition-heavy: each prompt tiles its own motif (plus a unique
    # head so prefix caching can't blur the A/B)
    rep_prompts = [
        np.concatenate([rng.randint(0, vocab, 2).astype(np.int32),
                        np.tile(rng.randint(0, vocab, motif_len)
                                .astype(np.int32), reps)])
        for _ in range(n_req)]
    # adversarial: a permutation has every token once — no n-gram ever
    # recurs inside the prompt, so prompt-lookup has nothing to match
    adv_prompts = [
        rng.permutation(vocab)[:rng.randint(plo, phi + 1)]
        .astype(np.int32) for _ in range(n_req)]

    def run(eng, prompts):
        rids = [eng.submit(p, max_new_tokens=nnew) for p in prompts]
        ticks = 0
        while eng.num_active or eng.queue_depth or eng.num_pending:
            eng.step()
            ticks += 1
        return [eng.result(r) for r in rids], ticks

    def ab(prompts, label):
        plain = ServingEngine(model, num_slots=slots, max_length=max_len)
        spec = ServingEngine(model, num_slots=slots, max_length=max_len,
                             spec_decode=True, spec_k=spec_k)
        run(plain, prompts), run(spec, prompts)     # compile + warm
        t0 = time.perf_counter()
        out_p, ticks_p = run(plain, prompts)
        t_plain = time.perf_counter() - t0
        t0 = time.perf_counter()
        out_s, ticks_s = run(spec, prompts)
        t_spec = time.perf_counter() - t0
        toks = sum(len(o) for o in out_s)
        sm = spec.metrics()["spec"]
        return {"trace": label,
                "requests": len(prompts), "new_tokens": nnew,
                "greedy_parity": out_p == out_s,
                "tokens_per_sec_plain": round(
                    sum(len(o) for o in out_p) / t_plain, 1),
                "tokens_per_sec_spec": round(toks / t_spec, 1),
                "ticks_plain": ticks_p, "ticks_spec": ticks_s,
                "accepted_per_step": sm["accepted_per_step"],
                "draft_hit_rate": sm["draft_hit_rate"],
                "drafted_tokens_2pass": sm["drafted_tokens"],
                "rollbacks_2pass": sm["rollbacks"],
                "step_traces": spec.step_traces}

    rep = ab(rep_prompts, "repetition-heavy (motif-tiled prompts)")
    adv = ab(adv_prompts, "adversarial low-match (permutation prompts)")
    return {"spec_k": spec_k, "num_slots": slots, "max_length": max_len,
            "repetition_heavy": rep, "adversarial": adv,
            "note": "same trace through plain and spec engines, warm "
                    "second pass timed; tok/s counts committed tokens "
                    "only (BASELINE.md spec-decode conventions)"}


def _spec_model_bench(model):
    """Draft-MODEL vs n-gram drafter A/B (ISSUE 20): the same traces
    through two spec engines that differ only in their drafter —
    prompt-lookup n-gram vs a truncated-target draft model
    (``draft_model_from``, rejection-sampling acceptance) — on

      * a **novel-text** trace (permutation prompts: no n-gram ever
        recurs, so prompt-lookup STARVES — the draft model must beat it
        on accepted/step here, the headline gate), and
      * the **PR-7 repetition trace** (motif-tiled prompts, where
        prompt-lookup is strongest — the draft model only has to stay
        competitive, not win).

    Each arm reports accepted/step, hit rate, and the **draft-step
    overhead fraction** (host wall spent proposing / total wall — the
    cost side of the speculation trade; BASELINE.md excludes draft
    FLOPs from every tok/s numerator).  The mesh rows record the
    flash-decode dispatch decision for this engine's shapes under
    mp2dp2 — the verify window must choose ``pallas_decode_shard_map``
    (ISSUE 20 tentpole b).  CPU = plumbing smoke; the tok/s claim is
    the pending TPU re-check."""
    import numpy as np

    from paddle_tpu.models import draft_model_from
    from paddle_tpu.serving import ServingEngine

    slots, max_len, spec_k, n_req = 8, 2048, 4, 24
    motif_len, reps, nnew = 16, 12, 96
    plo, phi = 64, 192
    draft_layers = 4
    vocab = model.config.vocab_size
    rng = np.random.RandomState(0)
    # the PR-7 repetition trace: motif-tiled prompts, unique heads
    rep_prompts = [
        np.concatenate([rng.randint(0, vocab, 2).astype(np.int32),
                        np.tile(rng.randint(0, vocab, motif_len)
                                .astype(np.int32), reps)])
        for _ in range(n_req)]
    # novel-text: permutations — every token once, nothing for the
    # n-gram drafter to match (the paper's case for a learned drafter)
    rng = np.random.RandomState(20)
    novel_prompts = [
        rng.permutation(vocab)[:rng.randint(plo, phi + 1)]
        .astype(np.int32) for _ in range(n_req)]
    dm, dparams = draft_model_from(model, num_layers=draft_layers)

    def run(eng, prompts):
        rids = [eng.submit(p, max_new_tokens=nnew) for p in prompts]
        ticks = 0
        while eng.num_active or eng.queue_depth or eng.num_pending:
            eng.step()
            ticks += 1
        return [eng.result(r) for r in rids], ticks

    def arm(drafter_kw, label, prompts):
        eng = ServingEngine(model, num_slots=slots, max_length=max_len,
                            spec_decode=True, spec_k=spec_k, **drafter_kw)
        out_warm, _ = run(eng, prompts)             # compile + warm
        # time the drafter's host-side proposal work on the timed pass
        d = eng._drafter
        spent = [0.0]
        attr = "propose_batch" if getattr(d, "uses_device", False) \
            else "propose"
        orig = getattr(d, attr)

        def timed(*a, **kw):
            t0 = time.perf_counter()
            r = orig(*a, **kw)
            spent[0] += time.perf_counter() - t0
            return r
        setattr(d, attr, timed)
        t0 = time.perf_counter()
        out, ticks = run(eng, prompts)
        t = time.perf_counter() - t0
        setattr(d, attr, orig)
        sm = eng.metrics()["spec"]
        row = {"drafter": label, "ticks": ticks,
               "tokens_per_sec": round(
                   sum(len(o) for o in out) / t, 1),
               "accepted_per_step": sm["accepted_per_step"],
               "draft_hit_rate": sm["draft_hit_rate"],
               "drafted_tokens_2pass": sm["drafted_tokens"],
               "rollbacks_2pass": sm["rollbacks"],
               "draft_overhead_frac": round(spent[0] / t, 3),
               "step_traces": eng.step_traces,
               # greedy replay: pass 2 must re-commit pass 1's tokens
               "deterministic_replay": out == out_warm}
        if getattr(d, "uses_device", False):
            row["draft_step_traces"] = d.draft_traces
        return eng, out, row

    def ab(prompts, tag):
        _, out_n, row_n = arm({"drafter": "ngram"}, "ngram", prompts)
        eng_m, out_m, row_m = arm(
            {"drafter": "model", "draft_model": (dm, dparams)},
            "model", prompts)
        return eng_m, {"trace": tag, "ngram": row_n, "model": row_m,
                       "greedy_parity": out_n == out_m}

    eng_m, novel = ab(novel_prompts, "novel-text (permutation prompts)")
    _, rep = ab(rep_prompts, "repetition-heavy (PR-7 motif trace)")
    lint_findings = len(eng_m.lint_step())

    # mesh dispatch rows: the decision the mp2dp2 engine's trace makes
    # for this engine's decode shapes (needs >= 4 devices; static)
    mesh_paths = []
    import jax
    if jax.device_count() >= 4:
        from paddle_tpu import flags as _flags
        from paddle_tpu.distributed import env as _denv
        from paddle_tpu.ops.attention import (decode_attention_path,
                                              reason_kind)
        c = model.config
        hq, hkv = int(c.num_attention_heads), int(c.num_key_value_heads)
        hd = int(c.head_dim)
        old = _flags.flag("pallas_interpret")
        _flags.set_flags({"pallas_interpret": True})
        try:
            mesh = ServingEngine._resolve_mesh("mp2dp2")
            with _denv.use_mesh(mesh):
                for b, s, what in ((slots, spec_k + 1, "spec_verify"),
                                   (slots, 1, "decode"),
                                   (1, 1, "decode_b1")):
                    path, why = decode_attention_path(b, s, hq, hkv,
                                                      hd, 8192)
                    row = {"what": what, "b": b, "s": s,
                           "chosen_path": path}
                    if why is not None:
                        row["fallback_reason"] = str(why)
                        row["reason_kind"] = reason_kind(why)
                    mesh_paths.append(row)
        finally:
            _flags.set_flags({"pallas_interpret": old})

    novel_win = (novel["model"]["accepted_per_step"].get("mean", 0)
                 or 0) > (novel["ngram"]["accepted_per_step"]
                          .get("mean", 0) or 0)
    return {"spec_k": spec_k, "num_slots": slots, "max_length": max_len,
            "draft_layers": draft_layers,
            "novel_text": novel, "repetition_heavy": rep,
            "model_beats_ngram_on_novel": bool(novel_win),
            "deterministic_replay": bool(
                novel["model"]["deterministic_replay"]
                and novel["ngram"]["deterministic_replay"]
                and rep["model"]["deterministic_replay"]
                and rep["ngram"]["deterministic_replay"]),
            "lint_findings": lint_findings,
            "mesh_paths": mesh_paths,
            "note": "same trace through an n-gram-drafted and a "
                    "draft-model spec engine; tok/s counts committed "
                    "tokens only and EXCLUDES draft FLOPs from the "
                    "numerator (BASELINE.md rejection-sampling "
                    "conventions); draft_overhead_frac is the cost "
                    "side"}


def _mesh_serving_bench(model):
    """Mesh-sharded serving A/B (ISSUE 9), two halves:

      * **mp engine** — the SAME trace through a single-chip engine and
        a ``mesh="mp2dp2"``-placed engine (params/cache per
        decode_mesh_specs, declared in/out shardings, cache donated):
        greedy outputs must be token-identical, the step compiles once,
        and the pre-flight PREDICTIONS are asserted against the
        program's ACTUALS — placed per-device cache bytes vs the
        HBM-liveness estimate (``mesh_placement_check``,
        FLAGS_graph_lint_hbm_tol), and the predicted mp collectives vs
        the collective ops in the compiled HLO (presence must agree;
        GSPMD may fuse, so the count is recorded, not asserted —
        BASELINE.md predicted-vs-measured conventions);
      * **dp router** — a shared-system-prompt trace (two tenant
        families, random arrival order) through a 2-replica
        ``ReplicaRouter`` under the prefix-affinity policy vs
        round-robin: the pooled prefix hit rate must be strictly higher
        under prefix routing (the whole point of hashing warm tries),
        outputs identical under both.

    Needs a four-chip host."""
    import re

    import numpy as np

    import jax
    from paddle_tpu.serving import ReplicaRouter, ServingEngine

    ndev = len(jax.devices())
    if ndev < 4:
        raise SystemExit(f"bench.py: mesh_serving needs 4 devices for "
                         f"mp2dp2; this host has {ndev}")
    slots, max_len, n_req, bl = 8, 2048, 32, 128
    sys_len, plo, phi, nlo, nhi = 256, 32, 128, 32, 96
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size
    prompts = [rng.randint(0, vocab, rng.randint(plo, phi + 1))
               .astype(np.int32) for _ in range(n_req)]
    news = rng.randint(nlo, nhi + 1, n_req)

    def run(eng):
        rids = [eng.submit(p, max_new_tokens=int(news[i]))
                for i, p in enumerate(prompts)]
        while eng.num_active or eng.queue_depth or eng.num_pending:
            eng.step()
        return [eng.result(r) for r in rids]

    single = ServingEngine(model, num_slots=slots, max_length=max_len)
    meshed = ServingEngine(model, num_slots=slots, max_length=max_len,
                           mesh="mp2dp2")
    run(single), run(meshed)                       # compile + warm
    t0 = time.perf_counter()
    out_single = run(single)
    t_single = time.perf_counter() - t0
    t0 = time.perf_counter()
    out_mesh = run(meshed)
    t_mesh = time.perf_counter() - t0
    toks = sum(len(o) for o in out_mesh)

    pf = meshed.mesh_preflight()
    # compiled actuals: re-jit the RAW step body (python_fn — no trace
    # counted against the budget) with the engine's own jit kwargs and
    # count the collective ops GSPMD actually emitted
    jf = jax.jit(meshed._step_fn.python_fn, **meshed._step_fn.jit_kwargs)
    hlo = jf.lower(*meshed._lint_args()).compile().as_text()
    compiled = {k: len(re.findall(rf"\b{k}(?:-start)?\(", hlo))
                for k in ("all-reduce", "all-gather", "all-to-all",
                          "collective-permute")}
    pred_mp = pf["comm"]["per_axis"]["mp"]
    pred_count = int(sum(pred_mp["collectives"].values()))
    mp_block = {
        "mesh": "mp2dp2",
        "greedy_parity": out_single == out_mesh,
        "generated_tokens": int(toks),
        "tokens_per_sec_single_chip": round(toks / t_single, 1),
        "tokens_per_sec_mesh": round(toks / t_mesh, 1),
        "step_traces": meshed.step_traces,
        "preflight_findings": len(pf["findings"]),
        "placement_check": pf["placement_check"],
        "comm_predicted_bytes_per_axis": {
            a: row["bytes_per_step"]
            for a, row in pf["comm"]["per_axis"].items()},
        "comm_predicted_mp_collectives": {
            k: int(v) for k, v in sorted(pred_mp["collectives"].items())},
        "compiled_collective_ops": compiled,
        "comm_check_ok": (compiled["all-reduce"] > 0) == (pred_count > 0)}

    # dp router A/B: two tenant families sharing system prompts,
    # arrival order randomised — round-robin splits each family across
    # both replicas (every other request recomputes the prefix cold),
    # prefix-affinity routing lands each family on its warm trie
    r2 = np.random.RandomState(1)
    fams = [r2.randint(0, vocab, sys_len).astype(np.int32)
            for _ in range(2)]
    rtrace = [np.concatenate([fams[int(r2.rand() < 0.5)],
                              r2.randint(0, vocab, r2.randint(2, phi))
                              .astype(np.int32)]) for _ in range(n_req)]
    rnews = r2.randint(nlo, nhi + 1, n_req)

    def run_router(policy):
        router = ReplicaRouter(model, num_replicas=2, policy=policy,
                               paged=True, block_len=bl,
                               num_slots=slots, max_length=max_len)
        t0 = time.perf_counter()
        rids = []
        for i, p in enumerate(rtrace):
            rids.append(router.submit(p, max_new_tokens=int(rnews[i])))
            router.step()
            router.step()          # stagger: the trie warms mid-trace
        outs = dict(router.drain())
        wall = time.perf_counter() - t0
        agg = router.metrics()["aggregate"]
        return [outs[r] for r in rids], agg, wall

    out_px, agg_px, wall_px = run_router("prefix")
    out_rr, agg_rr, wall_rr = run_router("round_robin")
    router_block = {
        "replicas": 2, "trace_requests": n_req,
        "shared_prompt_len": sys_len,
        "trace": "two tenant families share system prompts, random "
                 "arrival order, submissions interleaved with ticks",
        "greedy_parity_across_policies": out_px == out_rr,
        "prefix_policy": {
            "prefix_hit_rate_pooled": agg_px["prefix_hit_rate_pooled"],
            "prefix_hit_rate_per_replica":
                agg_px["prefix_hit_rate_per_replica"],
            "aggregate_tokens": agg_px["tokens_generated"],
            "aggregate_tokens_per_sec": round(
                agg_px["tokens_generated"] / wall_px, 1),
            "prefix_routed_tokens": agg_px["prefix_routed_tokens"]},
        "round_robin": {
            "prefix_hit_rate_pooled": agg_rr["prefix_hit_rate_pooled"],
            "prefix_hit_rate_per_replica":
                agg_rr["prefix_hit_rate_per_replica"],
            "aggregate_tokens": agg_rr["tokens_generated"],
            "aggregate_tokens_per_sec": round(
                agg_rr["tokens_generated"] / wall_rr, 1)},
        "prefix_beats_round_robin": (
            agg_px["prefix_hit_rate_pooled"]
            > agg_rr["prefix_hit_rate_pooled"])}

    return {"mp_engine": mp_block, "dp_router": router_block,
            "note": "wall includes each router's first-pass compiles; "
                    "aggregate tok/s sums per-replica committed tokens, "
                    "pooled hit rate re-divides summed hits by summed "
                    "prompt tokens — BASELINE.md multi-replica "
                    "accounting"}


def _int8_serving_bench(model):
    """Int8 quantized KV-cache A/B/C (ISSUE 13): the SAME seeded
    loadgen trace replayed through three paged engines — bf16 KV,
    int8 KV, and int8 KV + int8 weight_only_linear — so capacity,
    streamed bytes, tok/s and greedy parity are all judged on one
    trace.  Capacity is pool-byte accounting (cache_hbm_bytes of
    identically-configured pools): at the bf16 engine's pool budget
    the int8 pool admits ~2x the resident sessions, and each decode
    step streams ~0.51x the cache bytes per live context token (int8
    payload + amortized per-block scales — BASELINE.md 'Quantization
    accounting conventions').  The parity oracle runs one prefill +
    one cached decode step with the cache quantized vs not and
    reports the max |logit delta|, fed into the
    serving.kv_dequant_error summary the engines export."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.generation import init_kv_cache
    from paddle_tpu.serving import LoadSpec, ServingEngine, generate_load
    from paddle_tpu.serving import replay as lg_replay

    slots, max_len, bl, n_req = 8, 2048, 128, 32
    buckets, out_med, out_lo, out_hi = (64, 128, 512), 64.0, 32, 128
    probe_len, seed = 384, 11
    # every output >= 32 tokens: the parity horizon the issue pins
    spec = LoadSpec(
        n_requests=n_req, vocab=model.config.vocab_size,
        arrival="poisson", mean_gap=1.0,
        prompt_dist="zipf", prompt_buckets=buckets, prompt_zipf_a=1.0,
        prompt_max=max(buckets),
        output_dist="lognormal", output_median=out_med, output_sigma=0.3,
        output_min=out_lo, output_max=out_hi,
        tenants=2, shared_prefix_len=4)
    load = generate_load(spec, seed=seed)

    def measure(**kw):
        eng = ServingEngine(model, num_slots=slots, max_length=max_len,
                            paged=True, block_len=bl, **kw)
        lg_replay(eng, load)                  # A: compile + warm
        b = lg_replay(eng, load)              # B: steady-state measure
        c = lg_replay(eng, load)              # C: determinism replay
        return eng, b, c

    e16, b16, c16 = measure()
    e8, b8, c8 = measure(kv_cache_dtype="int8")
    ew, bw, cw = measure(kv_cache_dtype="int8", int8_weights=True)

    # -- capacity at equal pool bytes (default pool = slots sessions) --
    pool16, pool8 = e16.cache_hbm_bytes, e8.cache_hbm_bytes
    cap_ratio = pool16 / pool8
    c = model.config
    nb = slots * (max_len // bl) + 1          # default pool sizing
    per_tok16 = pool16 / (nb * bl)            # full-precision cache
    per_tok8 = pool8 / (nb * bl)              # payload + amortized scales
    full_dtype = str(c.dtype)                 # bf16 on TPU, f32 CPU smoke

    # -- parity oracle: first cached read of quantized K/V -------------
    rng = np.random.RandomState(3)
    ids = jnp.asarray(
        rng.randint(0, c.vocab_size, probe_len)[None], jnp.int32)

    def probe_logits(quantized):
        cache = init_kv_cache(c, 1, max_len, quantized=quantized)
        _, cache = model.decode_step(ids, cache, 0)
        out, _ = model.decode_step(
            jnp.asarray([[5]], jnp.int32), cache,
            jnp.asarray([probe_len], jnp.int32))
        return np.asarray(out[0, -1].astype(jnp.float32))

    delta = float(np.abs(probe_logits(True) - probe_logits(False)).max())
    e8.observe_dequant_error(delta)
    ew.observe_dequant_error(delta)

    def parity(rep):
        pairs = [(a, b) for a, b in zip(b16["outputs"], rep["outputs"])
                 if a is not None and b is not None]
        return {"greedy_parity": all(a == b for a, b in pairs),
                "compared": len(pairs),
                "horizon_tokens": min((len(a) for a, _ in pairs),
                                      default=0)}

    def row(eng, rep):
        return {"tokens_per_sec": round(
                    rep["generated_tokens"] / rep["wall_s"], 1),
                "generated_tokens": rep["generated_tokens"],
                "ticks": rep["ticks"], "rejected": rep["rejected"],
                "step_traces": max(rep["step_traces"]),
                "kv_dtype": eng.kv_dtype,
                "cache_pool_bytes": eng.cache_hbm_bytes}

    deterministic = all(
        b["signature"] == cc["signature"] and b["outputs"] == cc["outputs"]
        for b, cc in ((b16, c16), (b8, c8), (bw, cw)))
    return {
        "num_slots": slots, "max_length": max_len, "block_len": bl,
        "requests": n_req,
        "load": {"arrival": "poisson, mean gap 1.0 ticks",
                 "prompt_mix": f"zipf-bucketed {list(buckets)} a=1.0",
                 "output_mix": f"lognormal median {out_med} "
                               f"clamp [{out_lo},{out_hi}]",
                 "tenants": 2, "shared_prefix_len": 4, "seed": seed},
        "bf16": row(e16, b16),
        "int8_kv": dict(row(e8, b8), **parity(b8)),
        "int8_kv_int8_weights": dict(row(ew, bw), **parity(bw)),
        "capacity_at_equal_pool_bytes": {
            "bf16_resident_sessions": slots,
            "int8_resident_sessions": int(slots * cap_ratio),
            "capacity_ratio": round(cap_ratio, 3),
            "admits_ge_1p8x": cap_ratio >= 1.8},
        "per_step_streamed_cache_bytes": {
            "full_precision_dtype": full_dtype,
            "full_per_context_token": round(per_tok16, 1),
            "int8_per_context_token": round(per_tok8, 1),
            "ratio": round(per_tok8 / per_tok16, 3),
            "le_0p55x": per_tok8 / per_tok16 <= 0.55},
        "logit_error_oracle": {
            "max_abs_logit_delta": round(delta, 5),
            "documented_bound": 0.25,
            "within_bound": delta < 0.25,
            "probe": f"prefill {probe_len} tokens bf16 vs int8 cache, "
                     "compare the first cached decode step's logits"},
        "deterministic_replay": deterministic,
        "note": "one seeded load through all three engines (pass A "
                "compiles, B measures, C replays); capacity is pool-"
                "byte entitlement at the default slots*max_blocks+1 "
                "pool; streamed bytes are per live context token with "
                "per-block scales amortized in (BASELINE.md "
                "'Quantization accounting conventions')"}


def _perf_model_bench(model):
    """Roofline cost-model attribution (ISSUE 15): ONE seeded loadgen
    trace through a bf16-KV and an int8-KV paged engine, reporting each
    engine's per-bound tick attribution, per-term predicted totals and
    measured/predicted ratio percentiles from ``perf_report()``.  The
    int8 engine's predicted kv-stream term must shrink by exactly the
    committed ``per_step_streamed_cache_bytes`` ratio (the model and
    the pool accounting share the same per-token arithmetic —
    BASELINE.md 'Cost-model accounting conventions'), drift findings
    must be 0, and the once-jitted step contract must hold."""
    from paddle_tpu.serving import LoadSpec, ServingEngine, generate_load
    from paddle_tpu.serving import replay as lg_replay

    slots, max_len, bl, n_req = 8, 2048, 128, 32
    buckets, out_med, out_lo, out_hi = (64, 128, 512), 64.0, 32, 128
    seed = 11
    spec = LoadSpec(
        n_requests=n_req, vocab=model.config.vocab_size,
        arrival="poisson", mean_gap=1.0,
        prompt_dist="zipf", prompt_buckets=buckets, prompt_zipf_a=1.0,
        prompt_max=max(buckets),
        output_dist="lognormal", output_median=out_med, output_sigma=0.3,
        output_min=out_lo, output_max=out_hi,
        tenants=2, shared_prefix_len=4)
    load = generate_load(spec, seed=seed)

    def measure(**kw):
        eng = ServingEngine(model, num_slots=slots, max_length=max_len,
                            paged=True, block_len=bl, **kw)
        lg_replay(eng, load)                  # A: compile + warm
        rep = lg_replay(eng, load)            # B: steady-state measure
        return eng, rep, eng.perf_report()

    e16, b16, p16 = measure()
    e8, b8, p8 = measure(kv_cache_dtype="int8")

    def row(rep, perf):
        return {"ticks_modeled": perf["ticks_modeled"],
                "bounds": perf["bounds"],
                "predicted_ms": perf["predicted_ms"],
                "ratio": perf["ratio"],
                "kv_bytes_per_token":
                    perf["model_inputs"]["kv_bytes_per_token"],
                "weight_bytes": perf["model_inputs"]["weight_bytes"],
                "drift_findings": len(perf["drift"]),
                "anomalies": sum(perf["anomalies"].values()),
                "step_traces": max(rep["step_traces"])}

    kv16 = p16["model_inputs"]["kv_bytes_per_token"]
    kv8 = p8["model_inputs"]["kv_bytes_per_token"]
    kv_ratio = kv8 / kv16
    # the committed int8_serving streamed-bytes row measures the SAME
    # ratio from pool-byte accounting; the model must agree with it
    pool_ratio = None
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_DECODE.json")
    if os.path.exists(path):
        with open(path) as f:
            committed = json.load(f)
        skey = "llama_940m_serving"
        pool_ratio = (committed.get(skey, {}).get("int8_serving", {})
                      .get("per_step_streamed_cache_bytes", {})
                      .get("ratio"))
    consistent = (pool_ratio is None
                  or abs(kv_ratio - float(pool_ratio)) <= 0.01)
    drift = row(b16, p16)["drift_findings"] + row(b8, p8)["drift_findings"]
    return {
        "num_slots": slots, "max_length": max_len, "block_len": bl,
        "requests": n_req, "seed": seed,
        "profile": p16["profile"],
        "bf16": row(b16, p16),
        "int8_kv": row(b8, p8),
        "kv_term_ratio_int8_over_full": round(kv_ratio, 3),
        "committed_streamed_ratio": pool_ratio,
        "kv_ratio_consistent": bool(consistent),
        "drift_findings": drift,
        "step_traces": max(max(b16["step_traces"]), max(b8["step_traces"])),
        "note": "per-bound tick attribution from ServingEngine."
                "perf_report() after a warm replay; the predicted side "
                "is schedule-deterministic, the ratio percentiles are "
                "wall clock (absolute values meaningless on the "
                "cpu_smoke profile — only stability and the dtype "
                "ratios are gated there)"}


def _preempt_serving_bench(model):
    """Preemptive scheduling + tiered KV cache A/B/C (ISSUE 16): the
    SAME seeded heavy-tail loadgen trace replayed under a POOL TOO
    TIGHT for the working set through three paged engines —
    FIFO-blocking (``preempt="off"``: admission waits for a running
    request to retire), preempt+swap (victim blocks copied to the
    pinned host pool, resumed by swap-in), and preempt+recompute
    (victim blocks freed, resumed by re-prefill through the prefix
    trie).  The trace carries two priority classes: the minority
    tenant is INTERACTIVE (priority 5, a tight TTFT deadline stamped
    at submit), the majority tenant is BATCH (priority 0, TPOT-only —
    a throughput class doesn't die of queueing).  Deadlines are
    self-calibrated from the swap engine's own measured pass (per-
    class p99 x 1.5) and stamped identically for all three engines,
    then each engine's judged pass is joined against the RECORDED
    per-request deadlines — so the ruler is one fixed pair of
    class-SLOs, not per-engine flags.  The FIFO engine must park
    interactive arrivals behind batch residents (admission_wait blows
    their TTFT); both preemptive engines evict a batch victim instead
    and must win goodput STRICTLY, while serving GREEDY
    TOKEN-IDENTICAL outputs for every request (preempted ones
    included).  Also banked: preemption/swap counters for the judged
    pass, the victim-decision signature replaying byte-identical on a
    twin engine, and the resident-session capacity row — peak
    in-flight sessions (active + swapped-out awaiting resume) at
    EQUAL HBM pool bytes, the host tier's capacity multiplier
    (BASELINE.md 'Preemption accounting conventions')."""
    import numpy as np

    from paddle_tpu import flags as _fl
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import LoadSpec, ServingEngine, generate_load

    slots, max_len, bl, n_req = 8, 2048, 128, 32
    nb, hostb = 24, 64
    buckets, out_med, out_lo, out_hi = (32, 64, 1024), 48.0, 16, 96
    seed = 11
    # zipf a=1.0 over the buckets gives the top bucket real mass: a
    # near-pool-sized resident whose block footprint starves admission
    spec = LoadSpec(
        n_requests=n_req, vocab=model.config.vocab_size,
        arrival="poisson", mean_gap=1.0,
        prompt_dist="zipf", prompt_buckets=buckets, prompt_zipf_a=1.0,
        prompt_max=max(buckets),
        output_dist="lognormal", output_median=out_med, output_sigma=0.5,
        output_min=out_lo, output_max=out_hi,
        tenants=2, shared_prefix_len=4)
    load = generate_load(spec, seed=seed)
    order = sorted(range(len(load)),
                   key=lambda i: (load[i].arrival, load[i].index))
    # tenant 1 is the zipf-minority: the interactive class
    hi = [r.tenant == 1 for r in load]
    log = obs.get_request_log()
    slo_keys = ("serving_slo_ttft_ms", "serving_slo_tpot_ms")
    slo_saved = _fl.get_flags(slo_keys)

    def drive(eng, deadlines=None):
        """loadgen.replay's exact tick schedule, submitting each
        request with its class priority and (judged passes) the
        class-SLO stamp, plus a per-tick sample of in-flight sessions
        (active + swapped-out awaiting resume) for the capacity row."""
        mark = log.mark()
        tick = nxt = peak = 0
        rids, t0 = {}, time.perf_counter()
        while (nxt < len(order) or eng.queue_depth or eng.num_active
               or eng.num_pending or eng.num_preempted):
            while nxt < len(order) and load[order[nxt]].arrival <= tick:
                i = order[nxt]
                r = load[i]
                if deadlines is not None:
                    t_ttft, t_tpot = deadlines
                    _fl.set_flags({
                        # batch TTFT unbounded: a throughput class
                        "serving_slo_ttft_ms": t_ttft if hi[i] else 0.0,
                        "serving_slo_tpot_ms": t_tpot})
                try:
                    rids[i] = eng.submit(r.prompt, priority=5 if hi[i]
                                         else 0,
                                         max_new_tokens=r.max_new_tokens)
                except ValueError:
                    pass
                nxt += 1
            eng.step()
            peak = max(peak, eng.num_active + eng.num_preempted)
            tick += 1
        wall = time.perf_counter() - t0
        end_mark = log.mark()
        outputs = [eng.result(rids[i]) if i in rids else None
                   for i in range(len(load))]
        return {"mark": mark, "end_mark": end_mark, "wall_s": wall,
                "ticks": tick, "peak": peak, "outputs": outputs,
                "generated_tokens": sum(len(o) for o in outputs if o),
                "uids": {i: eng.request_uid(r) for i, r in rids.items()},
                "signature": log.timeline_signature(
                    since_uid=mark, until_uid=end_mark)}

    def build(**kw):
        return ServingEngine(model, num_slots=slots, max_length=max_len,
                             paged=True, block_len=bl, num_blocks=nb,
                             **kw)

    def _retired_lat(rep):
        """(interactive ttft_ms, all tpot_ms) lists for a pass."""
        recs = log.records(rep["mark"], rep["end_mark"])
        uid_hi = {rep["uids"][i] for i in rep["uids"] if hi[i]}
        ttfts, tpots = [], []
        for uid, evs in recs.items():
            ret = next((e["attrs"] for e in evs
                        if e["name"] == "retired"), None)
            if not ret or ret.get("reason") == "cancelled":
                continue
            if uid in uid_hi and ret.get("ttft_ms") is not None:
                ttfts.append(float(ret["ttft_ms"]))
            if ret.get("tpot_ms") is not None:
                tpots.append(float(ret["tpot_ms"]))
        return ttfts, tpots

    try:
        # -- calibration: swap engine, warm pass then measured pass ----
        e_sw = build(preempt="swap", host_blocks=hostb)
        drive(e_sw)                           # A: compile + warm
        cal = drive(e_sw)                     # B: steady-state calibrate
        ttfts, tpots = _retired_lat(cal)
        t_ttft = round(float(np.percentile(ttfts, 99)) * 1.5, 3)
        t_tpot = round(float(np.percentile(tpots, 99)) * 1.5, 3)
        dl = (t_ttft, t_tpot)

        # -- judged passes: same stamp, same trace, three engines ------
        sw_pre = e_sw.metrics()
        sw_b = drive(e_sw, deadlines=dl)      # C: judged
        sw_sig = e_sw.preempt_signature()     # decision log through C

        e_off = build(preempt="off")
        drive(e_off)
        off_b = drive(e_off, deadlines=dl)

        e_rc = build(preempt="recompute")
        drive(e_rc)
        rc_pre = e_rc.metrics()
        rc_b = drive(e_rc, deadlines=dl)

        # twin engine, identical pass sequence (warm, calibrate,
        # judged): its judged-pass timeline and outputs must reproduce
        # e_sw's exactly, and the victim decisions (tick, victim,
        # waiter, mode, slot, progress) must hash byte-identical — the
        # determinism contract the saturated smoke also gates.  A
        # SAME-engine re-replay would not do: under a tight pool the
        # prefix trie's LRU carryover differs at each pass boundary.
        twin = build(preempt="swap", host_blocks=hostb)
        drive(twin)
        drive(twin)
        sw_c = drive(twin, deadlines=dl)
        sig_stable = twin.preempt_signature() == sw_sig
    finally:
        _fl.set_flags(slo_saved)

    def judge(eng, rep, pre):
        # no explicit targets: the join runs against the per-request
        # deadlines recorded at submit — the class-SLO stamp
        slo = log.slo_report(since_uid=rep["mark"],
                             until_uid=rep["end_mark"],
                             wall_s=rep["wall_s"])
        m = eng.metrics()
        row = {"goodput": slo["goodput"],
               "goodput_tok_s": slo["goodput_tok_s"],
               "attained": slo["attained"],
               "violations": slo["violations"],
               "ttft_ms": slo["ttft_ms"], "tpot_ms": slo["tpot_ms"],
               "interactive_ttft_ms": (lambda xs: {
                   "count": len(xs),
                   "max": round(max(xs, default=0.0), 3)})(
                       _retired_lat(rep)[0]),
               "generated_tokens": rep["generated_tokens"],
               "ticks": rep["ticks"],
               "step_traces": int(eng.step_traces),
               "lint_findings": len(eng.lint_step())}
        if pre is not None:                    # judged-pass deltas
            row["preemptions"] = (
                sum(m["preempt"]["preemptions"].values())
                - sum(pre["preempt"]["preemptions"].values()))
            row["resumes"] = (
                sum(m["preempt"]["resumes"].values())
                - sum(pre["preempt"]["resumes"].values()))
        return row

    off_row = judge(e_off, off_b, None)
    sw_row = judge(e_sw, sw_b, sw_pre)
    rc_row = judge(e_rc, rc_b, rc_pre)
    ht, ht0 = (e_sw.metrics()["kv_cache"]["host_tier"],
               sw_pre["kv_cache"]["host_tier"])
    sw_row["swap"] = {
        k: ht[k] - ht0[k]
        for k in ("swapped_out_blocks", "swapped_in_blocks",
                  "swap_out_bytes", "swap_in_bytes",
                  "host_demotions", "host_promotions")}
    perf = e_sw.perf_report()
    if perf.get("enabled"):
        sw_row["predicted_swap_ms"] = round(
            perf["predicted_ms"].get("swap_ms", 0.0), 4)

    identical = (off_b["outputs"] == sw_b["outputs"] == rc_b["outputs"])
    deterministic = (sw_c["signature"] == sw_b["signature"]
                     and sw_c["outputs"] == sw_b["outputs"])
    better = (sw_row["goodput"] > off_row["goodput"]
              and rc_row["goodput"] > off_row["goodput"])
    peak_off, peak_sw, peak_rc = (off_b["peak"], sw_b["peak"],
                                  rc_b["peak"])
    return {
        "num_slots": slots, "max_length": max_len, "block_len": bl,
        "requests": n_req,
        "pool": {"hbm_blocks": nb, "host_blocks": hostb,
                 "note": "tight by design — the top prompt bucket's "
                         "block footprint is most of the pool"},
        "load": {"arrival": "poisson, mean gap 1.0 ticks",
                 "prompt_mix": f"zipf-bucketed {list(buckets)} a=1.0",
                 "output_mix": f"lognormal median {out_med} "
                               f"clamp [{out_lo},{out_hi}]",
                 "tenants": 2, "shared_prefix_len": 4, "seed": seed,
                 "interactive_requests": sum(hi),
                 "classes": "tenant 1 = interactive (priority 5, "
                            "TTFT+TPOT SLO); tenant 0 = batch "
                            "(priority 0, TPOT-only)"},
        "slo_targets_ms": {"interactive_ttft_p99": t_ttft,
                           "tpot_p99": t_tpot,
                           "rule": "swap engine measured pass, per-"
                                   "class p99 x 1.5, stamped at submit "
                                   "for all three engines"},
        "fifo_blocking": off_row,
        "preempt_swap": sw_row,
        "preempt_recompute": rc_row,
        "preempt_goodput_strictly_better": bool(better),
        "outputs_token_identical": bool(identical),
        "resident_capacity_at_equal_hbm_bytes": {
            "hbm_pool_bytes": e_off.cache_hbm_bytes,
            "peak_in_flight_sessions": {
                "fifo_blocking": peak_off,
                "preempt_swap": peak_sw,
                "preempt_recompute": peak_rc},
            "capacity_ratio_swap_over_fifo": round(
                peak_sw / max(1, peak_off), 3),
            "swap_holds_more_sessions": peak_sw > peak_off,
            "note": "in-flight = active slots + swapped-out awaiting "
                    "resume; all three engines hold the SAME HBM pool "
                    "— the swap tier's extra sessions live in host RAM"},
        "preempt_signature_stable": bool(sig_stable),
        "deterministic_replay": bool(deterministic),
        "note": "same seeded load, same tight pool, one class-SLO "
                "stamp (swap engine: warm, calibrate, judged passes; "
                "a twin swap engine replays the identical sequence "
                "for the determinism gates; the others: warm + "
                "judged); goodput counts ALL submitted requests, "
                "preempted-then-finished included; swap bytes never "
                "count as streamed KV bytes (BASELINE.md 'Preemption "
                "accounting conventions')"}


def _control_plane_bench(model):
    """Cost-model-driven control plane A/B (ISSUE 17): the SAME seeded
    saturated two-class trace through a 2-replica router under
    queue-depth (reactive) vs predictive SLO admission.  Class-SLO
    deadlines are calibrated from an UNSATURATED pass of the same
    request mix (p99 x 1.5 — what latency looks like uncontended), and
    FLAGS_serving_admission_calib from the calibration engines' own
    measured/predicted ratio, then both judged arms replay the
    saturated trace with identical per-class stamps.  The reactive arm
    places interactive arrivals behind batch residents; the predictive
    arm prices each placement against the roofline model and parks
    over-SLO batch work in the hold queue.  Gated: predictive goodput
    >= reactive with a STRICT win on at least one SLO class, greedy
    token-identical outputs for every request both arms admitted, a
    twin predictive replay reproducing the timeline + outputs
    byte-identically, once-jitted steps, zero lint findings.  Also
    banked: the deterministic replica-autoscaler action trace over a
    SimEngine fleet, and the device-free fleet-simulator scale row
    (100k requests x 16 replicas; the acceptance row for the <60 s
    host-wall budget)."""
    import numpy as np

    from paddle_tpu import flags as _fl
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import LoadSpec, ServingEngine, generate_load
    from paddle_tpu.serving import fleet_sim as _fs
    from paddle_tpu.serving.autoscaler import ReplicaAutoscaler
    from paddle_tpu.serving.router import ReplicaRouter

    replicas, slots, max_len, bl, nb, n_req = 2, 8, 2048, 128, 48, 48
    buckets, out_med, out_lo, out_hi = (32, 64, 512), 48.0, 16, 96
    seed = 13

    def mkspec(gap):
        return LoadSpec(
            n_requests=n_req, vocab=model.config.vocab_size,
            arrival="poisson", mean_gap=gap,
            prompt_dist="zipf", prompt_buckets=buckets,
            prompt_zipf_a=1.0, prompt_max=max(buckets),
            output_dist="lognormal", output_median=out_med,
            output_sigma=0.5, output_min=out_lo, output_max=out_hi,
            tenants=2, shared_prefix_len=4)

    # one request mix, two arrival schedules: the judged trace arrives
    # ~6x faster than the calibration trace (saturation is the point)
    load = generate_load(mkspec(1.0), seed=seed)
    load_cal = generate_load(mkspec(6.0), seed=seed)
    hi = [r.tenant == 1 for r in load]          # zipf-minority class
    log = obs.get_request_log()
    keys = ("serving_slo_ttft_ms", "serving_slo_tpot_ms",
            "serving_admission", "serving_admission_calib")
    saved = _fl.get_flags(keys)

    def build():
        return ReplicaRouter(
            engines=[ServingEngine(model, num_slots=slots,
                                   max_length=max_len, paged=True,
                                   block_len=bl, num_blocks=nb)
                     for _ in range(replicas)],
            policy="least_loaded")

    def drive(router, trace, deadlines=None):
        """loadgen.replay's tick schedule through the router,
        submitting each request with its class priority and SLO stamp
        (captured at ROUTER submit — held requests keep theirs)."""
        order = sorted(range(len(trace)),
                       key=lambda i: (trace[i].arrival, trace[i].index))
        mark = log.mark()
        tick = nxt = 0
        rids, t0 = {}, time.perf_counter()
        while (nxt < len(order) or router.pending_held
               or any(not router.replica_empty(i)
                      for i in router.live_replicas)):
            while (nxt < len(order)
                   and trace[order[nxt]].arrival <= tick):
                i = order[nxt]
                r = trace[i]
                t_ttft, t_tpot = deadlines or (0.0, 0.0)
                _fl.set_flags({
                    # batch TTFT unbounded: a throughput class
                    "serving_slo_ttft_ms": t_ttft if hi[i] else 0.0,
                    "serving_slo_tpot_ms": t_tpot})
                try:
                    rids[i] = router.submit(
                        r.prompt, max_new_tokens=r.max_new_tokens,
                        priority=5 if hi[i] else 0)
                except ValueError:
                    pass
                nxt += 1
            router.step()
            tick += 1
        wall = time.perf_counter() - t0
        end_mark = log.mark()
        outputs = []
        for i in range(len(trace)):
            try:
                outputs.append(router.result(rids[i])
                               if i in rids else None)
            except KeyError:        # held then rejected as infeasible
                outputs.append(None)
        return {"mark": mark, "end_mark": end_mark, "wall_s": wall,
                "ticks": tick, "outputs": outputs,
                "generated_tokens": sum(len(o) for o in outputs if o),
                "uids": {i: router.request_uid(r)
                         for i, r in rids.items()},
                "signature": log.timeline_signature(
                    since_uid=mark, until_uid=end_mark)}

    def class_rows(rep, dl):
        """Per-SLO-class goodput from the judged pass's retired events
        joined against the one class-SLO stamp."""
        t_ttft, t_tpot = dl
        recs = log.records(rep["mark"], rep["end_mark"])
        uid_cls = {rep["uids"][i]: hi[i] for i in rep["uids"]}
        rows = {c: {"requests": 0, "attained": 0, "ttft_ms": []}
                for c in ("interactive", "batch")}
        for uid, evs in recs.items():
            if uid not in uid_cls:
                continue
            ret = next((e["attrs"] for e in evs
                        if e["name"] == "retired"), None)
            if not ret or ret.get("reason") == "cancelled":
                continue
            c = "interactive" if uid_cls[uid] else "batch"
            row = rows[c]
            row["requests"] += 1
            ok = True
            ttft = ret.get("ttft_ms")
            tpot = ret.get("tpot_ms")
            if c == "interactive" and ttft is not None:
                row["ttft_ms"].append(float(ttft))
                ok = ok and ttft <= t_ttft
            if t_tpot > 0 and tpot is not None:
                ok = ok and tpot <= t_tpot
            if ok:
                row["attained"] += 1
        for c, row in rows.items():
            xs = sorted(row.pop("ttft_ms"))
            if c == "interactive":
                row["ttft_max_ms"] = round(xs[-1], 3) if xs else 0.0
            row["goodput"] = (round(row["attained"]
                                    / row["requests"], 4)
                              if row["requests"] else 1.0)
        return rows

    def judge(router, rep, dl):
        slo = log.slo_report(since_uid=rep["mark"],
                             until_uid=rep["end_mark"],
                             wall_s=rep["wall_s"])
        engines = [router.engines[i] for i in router.live_replicas]
        row = {"goodput": slo["goodput"],
               "goodput_tok_s": slo["goodput_tok_s"],
               "attained": slo["attained"],
               "violations": slo["violations"],
               "classes": class_rows(rep, dl),
               "generated_tokens": rep["generated_tokens"],
               "ticks": rep["ticks"],
               "step_traces": max(int(e.step_traces) for e in engines),
               "lint_findings": sum(len(e.lint_step())
                                    for e in engines),
               "control_plane": router.metrics()["aggregate"]
                                               ["control_plane"]}
        return row

    try:
        # -- calibration: unsaturated pass, queue-depth placement ------
        _fl.set_flags({"serving_admission": "queue_depth",
                       "serving_admission_calib": 1.0})
        r_cal = build()
        drive(r_cal, load_cal)                # A: compile + warm
        cal = drive(r_cal, load_cal)          # B: steady-state measure
        recs = log.records(cal["mark"], cal["end_mark"])
        uid_hi = {cal["uids"][i] for i in cal["uids"] if hi[i]}
        ttfts, tpots = [], []
        for uid, evs in recs.items():
            ret = next((e["attrs"] for e in evs
                        if e["name"] == "retired"), None)
            if not ret or ret.get("reason") == "cancelled":
                continue
            if uid in uid_hi and ret.get("ttft_ms") is not None:
                ttfts.append(float(ret["ttft_ms"]))
            if ret.get("tpot_ms") is not None:
                tpots.append(float(ret["tpot_ms"]))
        t_ttft = round(float(np.percentile(ttfts, 99)) * 1.5, 3)
        t_tpot = round(float(np.percentile(tpots, 99)) * 1.5, 3)
        dl = (t_ttft, t_tpot)
        ratios = [e.perf_report()["ratio"].get("p50")
                  for e in r_cal.engines]
        ratios = [r for r in ratios if r]
        calib = round(sum(ratios) / len(ratios), 6) if ratios else 1.0

        # -- judged arm A: reactive queue-depth placement --------------
        r_qd = build()
        drive(r_qd, load)
        qd_b = drive(r_qd, load, deadlines=dl)

        # -- judged arm B: predictive admission + priced hold queue ----
        _fl.set_flags({"serving_admission": "predictive",
                       "serving_admission_calib": calib})
        r_pr = build()
        drive(r_pr, load)
        pr_b = drive(r_pr, load, deadlines=dl)

        # twin predictive router, identical pass sequence: timeline and
        # outputs must reproduce byte-identically (admission decisions
        # are pure functions of scheduler state — no wall-clock input)
        r_tw = build()
        drive(r_tw, load)
        tw_b = drive(r_tw, load, deadlines=dl)
    finally:
        _fl.set_flags(saved)

    qd_row = judge(r_qd, qd_b, dl)
    pr_row = judge(r_pr, pr_b, dl)
    both = [i for i in range(len(load))
            if qd_b["outputs"][i] is not None
            and pr_b["outputs"][i] is not None]
    identical = all(qd_b["outputs"][i] == pr_b["outputs"][i]
                    for i in both)
    deterministic = (tw_b["signature"] == pr_b["signature"]
                     and tw_b["outputs"] == pr_b["outputs"])
    wins = [c for c in ("interactive", "batch")
            if pr_row["classes"][c]["goodput"]
            > qd_row["classes"][c]["goodput"]]

    # -- replica autoscaler: deterministic action trace over SimEngines
    as_keys = ("serving_admission", "perf_model", "serving_slo_ttft_ms",
               "serving_slo_tpot_ms", "serving_autoscale_min_ticks",
               "serving_autoscale_cooldown")
    as_saved = _fl.get_flags(as_keys)
    _fl.set_flags({"serving_admission": "predictive",
                   "perf_model": "on",
                   "serving_slo_ttft_ms": 0.0,
                   "serving_slo_tpot_ms": 40.0,
                   "serving_autoscale_min_ticks": 4,
                   "serving_autoscale_cooldown": 8})
    try:
        def autoscale_once():
            sspec = _fs.SimSpec.default()
            fleet = _fs.FleetSim(2, sspec, seed=0, num_slots=4,
                                 max_length=512)
            scaler = ReplicaAutoscaler(
                fleet.router, min_replicas=2, max_replicas=6,
                engine_factory=lambda: _fs.SimEngine(
                    sspec, num_slots=4, max_length=512, seed=99))
            trace = _fs._loadgen.generate_load(
                _fs.fleet_load_spec(400, replicas=2, num_slots=4),
                seed=3)
            it = iter(trace)
            nxt, t = next(it, None), 0.0
            while (nxt is not None or fleet.router.pending_held
                   or any(not fleet.router.replica_empty(i)
                          for i in fleet.router.live_replicas)):
                while nxt is not None and nxt.arrival <= t:
                    fleet.submit(nxt.prompt,
                                 max_new_tokens=nxt.max_new_tokens)
                    nxt = next(it, None)
                fleet.step()
                scaler.observe()
                t += 1.0
            for _ in range(300):          # idle tail: drain + retire
                fleet.step()
                scaler.observe()
            return scaler.report()
        a1 = autoscale_once()
        a2 = autoscale_once()
    finally:
        _fl.set_flags(as_saved)
    counts = {}
    for a in a1["actions"]:
        counts[a["action"]] = counts.get(a["action"], 0) + 1
    autoscale = {
        "requests": 400, "start_replicas": 2, "max_replicas": 6,
        "actions": counts,
        "final_live_replicas": a1["live_replicas"],
        "scaled_up_under_pressure": counts.get("add", 0) > 0,
        "drained_then_retired_on_slack":
            counts.get("retire", 0) == counts.get("drain", 0) > 0,
        "deterministic": a1["actions"] == a2["actions"]}

    # -- fleet simulator scale row (the <60 s acceptance budget) -------
    fl_rep = _fs.run_fleet(requests=100_000, replicas=16,
                           admission="predictive", seed=0)
    fleet_row = {k: fl_rep[k] for k in
                 ("requests", "replicas", "ticks", "generated_tokens",
                  "host_wall_s", "sim_wall_s", "sim_tok_per_s",
                  "goodput", "signature")}
    fleet_row["under_60s_host_wall"] = fl_rep["host_wall_s"] < 60.0

    return {
        "replicas": replicas, "num_slots": slots,
        "max_length": max_len, "block_len": bl, "requests": n_req,
        "seed": seed,
        "load": {"arrival": "poisson, mean gap 1.0 ticks (judged) / "
                            "6.0 (calibration)",
                 "prompt_mix": f"zipf-bucketed {list(buckets)} a=1.0",
                 "output_mix": f"lognormal median {out_med} "
                               f"clamp [{out_lo},{out_hi}]",
                 "interactive_requests": sum(hi),
                 "classes": "tenant 1 = interactive (priority 5, "
                            "TTFT+TPOT SLO); tenant 0 = batch "
                            "(priority 0, TPOT-only)"},
        "slo_targets_ms": {"interactive_ttft_p99": t_ttft,
                           "tpot_p99": t_tpot,
                           "rule": "unsaturated calibration pass, "
                                   "per-class p99 x 1.5, stamped at "
                                   "submit for both judged arms"},
        "admission_calib": calib,
        "queue_depth": qd_row,
        "predictive": pr_row,
        "predictive_goodput_ge": pr_row["goodput"] >= qd_row["goodput"],
        "strictly_better_classes": wins,
        "outputs_token_identical_where_both_admit": bool(identical),
        "deterministic_replay": bool(deterministic),
        "autoscale": autoscale,
        "fleet_sim": fleet_row,
        "note": "same saturated trace, one class-SLO stamp, fresh "
                "router per arm (warm + judged passes); deadlines "
                "captured at router submit ride through the hold "
                "queue; the fleet row replays the heavy-tail scenario "
                "through SimEngine replicas on the cost-model clock "
                "(BASELINE.md 'Simulated-clock accounting "
                "conventions')"}


def _disagg_serving_bench(model):
    """Disaggregated prefill/decode A/B over the multi-host plane
    (ISSUE 18): the SAME seeded loadgen trace — a decode cohort (short
    prompts, long outputs) hit mid-stream by heavy prefill arrivals
    (long prompts, two tokens) — driven through two 2-worker planes
    over LoopbackTransport.  A = colocated (``policy='prefix'``: both
    workers take mixed work), B = disaggregated (``policy='disagg'``:
    w0 prefills, every request migrates to w1 after its first token
    via export_blocks/import_blocks over the transport).

    Clocks: each worker runs on a PRIVATE simulated clock advanced by
    its OWN work per tick (base + per-prefill-token + per-decode-token
    costs).  That models separate hosts — wall clocks don't share
    stalls — which is the thing disaggregation buys: in-process both
    engines step sequentially on one wall clock, so a decode worker
    would be charged for the other host's prefill burn and the win
    could never show.  The engines stamp ttft/tpot through
    ``engine._clock``, so the retired ``tpot_ms`` attrs ARE sim-clock
    readings and the whole A/B is device-free deterministic
    (BASELINE.md 'Multi-host accounting conventions').

    Gates banked for --check-history: decode-cohort TPOT p99 strictly
    better disaggregated, token-identical outputs across arms,
    migration bytes accounted (> 0, one migration per decode-cohort
    request — a two-token heavy prefill retires inside its own wave
    step and never opens a migration window), byte-stable replay of
    BOTH arms, step_traces <= 1, zero lint findings."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu.serving import LoadSpec, ServingEngine, generate_load
    from paddle_tpu.serving.multihost import (EngineWorker,
                                              LoopbackTransport,
                                              MultiHostRouter)

    # fresh registry: jit.traces carries one child per (engine, site)
    # and earlier sections' engines can push the family past
    # metrics_max_children — the overflow child would MERGE this
    # section's step_traces across engines (the loadgen --smoke hazard)
    obs.reset()
    log = obs.get_request_log()

    slots, max_len, bl, nb = 8, 2048, 64, 192
    p_short, p_long, out_dec, out_pre = 16, 1024, 64, 4
    n_dec, n_pre = 6, 10
    seed = 13
    vocab = model.config.vocab_size

    def _cls_spec(n, plen, out):
        # single-bucket zipf pins both lengths: the class IS the shape
        return LoadSpec(n_requests=n, vocab=vocab,
                        arrival="poisson", mean_gap=1.0,
                        prompt_dist="zipf", prompt_buckets=(plen,),
                        prompt_min=plen, prompt_max=plen,
                        output_dist="zipf", output_buckets=(out,),
                        output_min=out, output_max=out,
                        tenants=1, shared_prefix_len=0)

    trace = []
    for r in generate_load(_cls_spec(n_dec, p_short, out_dec), seed=seed):
        trace.append({"arrival": r.arrival, "prompt": r.prompt,
                      "max_new": r.max_new_tokens, "cls": "decode"})
    for r in generate_load(_cls_spec(n_pre, p_long, out_pre),
                           seed=seed + 1):
        # heavy prefills land while the decode cohort is mid-stream
        trace.append({"arrival": r.arrival + 2.0, "prompt": r.prompt,
                      "max_new": r.max_new_tokens, "cls": "prefill"})
    order = sorted(range(len(trace)),
                   key=lambda i: (trace[i]["arrival"], i))

    cost = {"base_ms": 0.5, "prefill_ms_per_token": 0.05,
            "decode_ms_per_token": 0.05}

    class _ClockedWorker(EngineWorker):
        """EngineWorker whose engine reads a private simulated clock,
        advanced by this worker's OWN work each tick.  Imported
        requests arrive with their KV built, so they never pay the
        prefill charge here."""

        def __init__(self, engine, name):
            super().__init__(engine, name)
            self._now_s = 0.0
            engine._clock = lambda: self._now_s
            self._plen = {}
            self._prefilled = set()

        def _rpc_submit(self, payload):
            out = super()._rpc_submit(payload)
            self._plen[out["rid"]] = len(payload["prompt"])
            return out

        def _rpc_import_request(self, payload):
            out = super()._rpc_import_request(payload)
            if out["rid"] is not None:
                self._prefilled.add(out["rid"])
            return out

        def _rpc_step(self, payload):
            out = super()._rpc_step(payload)
            c = cost["base_ms"]
            for rid_s, toks in out["deltas"].items():
                rid = int(rid_s)
                if rid not in self._prefilled:
                    self._prefilled.add(rid)
                    c += (cost["prefill_ms_per_token"]
                          * self._plen.get(rid, 0))
                c += cost["decode_ms_per_token"] * len(toks)
            self._now_s += c * 1e-3
            return out

    def mk_plane(policy, prefill=None):
        from collections import OrderedDict
        workers, engines = OrderedDict(), []
        for i in range(2):
            eng = ServingEngine(model, num_slots=slots,
                                max_length=max_len, prefill_batch=2,
                                paged=True, block_len=bl, num_blocks=nb)
            engines.append(eng)
            w = _ClockedWorker(eng, name=f"w{i}")
            workers[f"w{i}"] = LoopbackTransport(w.handle, name=f"w{i}")
        return MultiHostRouter(workers, policy=policy,
                               prefill=prefill), engines

    def drive(plane):
        mark = log.mark()
        rids = {}
        tick = nxt = 0
        t0 = time.perf_counter()
        while (nxt < len(order) or plane.queue_depth or plane.num_active
               or plane.num_pending or plane.num_preempted):
            while (nxt < len(order)
                   and trace[order[nxt]]["arrival"] <= tick):
                i = order[nxt]
                try:
                    rids[i] = plane.submit(
                        trace[i]["prompt"],
                        max_new_tokens=trace[i]["max_new"])
                except ValueError:
                    break                 # re-admit at the door next tick
                nxt += 1
            plane.step()
            tick += 1
        end_mark = log.mark()
        outputs = [plane.result(rids[i]) if i in rids else None
                   for i in range(len(trace))]
        return {"mark": mark, "end_mark": end_mark, "ticks": tick,
                "outputs": outputs,
                "host_wall_s": round(time.perf_counter() - t0, 3),
                "uids": {i: plane.request_uid(rids[i]) for i in rids},
                "signature": log.timeline_signature(
                    since_uid=mark, until_uid=end_mark)}

    def tpot_p99(rep, cls):
        uids = {rep["uids"][i] for i in rep["uids"]
                if trace[i]["cls"] == cls}
        vals = []
        for uid, evs in log.records(rep["mark"], rep["end_mark"]).items():
            if uid not in uids:
                continue
            ret = next((e["attrs"] for e in evs
                        if e["name"] == "retired"), None)
            if ret and ret.get("tpot_ms") is not None:
                vals.append(float(ret["tpot_ms"]))
        return round(float(np.percentile(vals, 99)), 4) if vals else None

    def run(policy, prefill=None):
        plane, engines = mk_plane(policy, prefill)
        rep = drive(plane)
        rep["aggregate"] = plane.metrics()["aggregate"]
        rep["step_traces"] = max(e.step_traces for e in engines)
        rep["lint_findings"] = sum(len(e.lint_step()) for e in engines)
        plane.shutdown()
        return rep

    a1 = run("prefix")                    # A: colocated
    a2 = run("prefix")                    # A again: replay stability
    b1 = run("disagg", prefill=["w0"])    # B: disaggregated
    b2 = run("disagg", prefill=["w0"])    # B again

    a_p99, b_p99 = tpot_p99(a1, "decode"), tpot_p99(b1, "decode")
    complete = all(o for o in a1["outputs"]) and all(
        o for o in b1["outputs"])
    identical = complete and a1["outputs"] == b1["outputs"]
    deterministic = (a1["signature"] == a2["signature"]
                     and a1["outputs"] == a2["outputs"]
                     and b1["signature"] == b2["signature"]
                     and b1["outputs"] == b2["outputs"])
    agg = b1["aggregate"]
    mig, mig_bytes = int(agg["migrations"]), int(agg["migration_bytes"])

    def _row(rep, p99):
        return {"ticks": rep["ticks"],
                "decode_tpot_p99_ms_sim": p99,
                "prefill_tpot_p99_ms_sim": tpot_p99(rep, "prefill"),
                "migrations": int(rep["aggregate"]["migrations"]),
                "migration_bytes": int(
                    rep["aggregate"]["migration_bytes"]),
                "step_traces": rep["step_traces"],
                "lint_findings": rep["lint_findings"],
                "host_wall_s": rep["host_wall_s"]}

    return {
        "trace": {"seed": seed, "decode_requests": n_dec,
                  "heavy_prefills": n_pre, "prompt_short": p_short,
                  "prompt_long": p_long, "decode_output": out_dec,
                  "prefill_output": out_pre},
        "sim_cost_model": cost,
        "colocated": _row(a1, a_p99),
        "disaggregated": _row(b1, b_p99),
        "decode_tpot_strictly_better": bool(
            a_p99 is not None and b_p99 is not None and b_p99 < a_p99),
        "outputs_token_identical": bool(identical),
        "migrations_cover_decode_cohort": bool(mig >= n_dec),
        "migration_bytes_per_request": (round(mig_bytes / mig, 1)
                                        if mig else 0.0),
        "deterministic_replay": bool(deterministic),
        "step_traces": max(a1["step_traces"], b1["step_traces"]),
        "lint_findings": a1["lint_findings"] + b1["lint_findings"],
        "note": "per-worker simulated clocks (separate hosts don't "
                "share stalls); migration bytes are transport traffic "
                "(export_blocks payload), never streamed-KV bytes — "
                "BASELINE.md 'Multi-host accounting conventions'"}


def _multihost_obs_bench(model):
    """Federated observability cost + fidelity over a 2-worker loopback
    plane (ISSUE 19), measured under INJECTED simulated clocks so every
    figure but the federation wall cost is device-free deterministic:

    * **federation overhead per tick** — the same seeded trace driven
      twice, once bare and once with a full ``federation().merged()``
      pull every plane tick; the row reports the per-pull wall cost and
      its fraction of a bare plane tick (the scrape-budget number an
      operator needs);
    * **offset-estimate error under sim clocks** — each worker's server
      clock runs at a fixed injected skew; the recovered NTP-style
      offset must sit within the estimator's own min-RTT error bound of
      the truth (gated);
    * **pooled vs per-worker p99 agreement** — the federated pooled
      TTFT p99 (recomputed from summed buckets) must land inside the
      envelope of the per-worker p99s (pooling can never manufacture a
      quantile outside its inputs — gated);
    * byte-stable ``fleet_obs_signature`` across two identical-seed
      bare replays (gated), step_traces <= 1."""
    from collections import OrderedDict

    from paddle_tpu import observability as obs
    from paddle_tpu.observability.federation import percentile_from_buckets
    from paddle_tpu.serving import LoadSpec, ServingEngine, generate_load
    from paddle_tpu.serving.multihost import (EngineWorker,
                                              LoopbackTransport,
                                              MultiHostRouter)

    # fresh registry: the exact federated-total arithmetic (and the
    # jit.traces budget readout) must not inherit coalesced children
    # from earlier sections (the loadgen --smoke hazard)
    obs.reset()
    log = obs.get_request_log()

    n_req, slots, max_len, bl = 16, 8, 2048, 64
    seed = 29
    skews = {"w0": 41.0, "w1": -23.0}      # ms each worker clock leads
    spec = LoadSpec(n_requests=n_req, vocab=model.config.vocab_size,
                    arrival="poisson", mean_gap=1.0,
                    prompt_dist="zipf", prompt_buckets=(8, 16, 32),
                    prompt_min=4, prompt_max=32,
                    output_dist="zipf", output_buckets=(4, 8, 16),
                    output_min=4, output_max=16,
                    tenants=2, shared_prefix_len=4)
    load = generate_load(spec, seed=seed)
    order = sorted(range(len(load)),
                   key=lambda i: (load[i].arrival, load[i].index))

    def run(federate_every_tick):
        saved_clock, saved_t0 = log._clock, log._t0
        cell = {"t": 0.0}

        def vclock():                       # 0.1 virtual ms per read
            cell["t"] += 1e-4
            return cell["t"]

        log._clock, log._t0 = vclock, 0.0
        try:
            workers, engines = OrderedDict(), []
            for i in range(2):
                nm = f"w{i}"
                eng = ServingEngine(model, num_slots=slots,
                                    max_length=max_len, prefill_batch=2,
                                    paged=True, block_len=bl)
                eng._clock = vclock
                engines.append(eng)
                w = EngineWorker(eng, name=nm)
                workers[nm] = LoopbackTransport(
                    w.handle, name=nm,
                    server_clock=(lambda s=skews[nm]:
                                  log.now_ms() + s))
            plane = MultiHostRouter(workers, policy="prefix")
            mark = log.mark()
            rids = {}
            tick = nxt = 0
            fed_wall = 0.0
            pulls = 0
            t0 = time.perf_counter()
            while nxt < len(order) or any(not r.done
                                          for r in plane._reqs.values()):
                while (nxt < len(order)
                       and load[order[nxt]].arrival <= tick):
                    r = load[order[nxt]]
                    rids[r.index] = plane.submit(
                        r.prompt, max_new_tokens=r.max_new_tokens)
                    nxt += 1
                plane.step()
                tick += 1
                if federate_every_tick:
                    f0 = time.perf_counter()
                    plane.federation().merged()
                    fed_wall += time.perf_counter() - f0
                    pulls += 1
            wall = time.perf_counter() - t0
            end_mark = log.mark()
            return {"plane": plane, "ticks": tick,
                    "mark": mark, "end_mark": end_mark,
                    "wall_s": wall, "fed_wall_s": fed_wall,
                    "pulls": pulls,
                    "step_traces": max(e.step_traces for e in engines),
                    "signature": plane.fleet_obs_signature(
                        since_uid=mark, until_uid=end_mark)}
        finally:
            log._clock, log._t0 = saved_clock, saved_t0

    base1 = run(federate_every_tick=False)
    base2 = run(federate_every_tick=False)  # determinism arm
    fed = run(federate_every_tick=True)

    base_tick_ms = base1["wall_s"] / max(1, base1["ticks"]) * 1e3
    pull_ms = fed["fed_wall_s"] / max(1, fed["pulls"]) * 1e3

    # offset recovery vs the injected truth (from the bare arm)
    offsets = {}
    offset_ok = True
    worst_err = 0.0
    for nm, t in base1["plane"]._workers.items():
        est = t.stitch.estimator
        err = abs(est.offset_ms - skews[nm])
        worst_err = max(worst_err, err)
        within = est.ready and err <= est.error_bound_ms + 1e-9
        offset_ok = offset_ok and within
        offsets[nm] = {"injected_skew_ms": skews[nm],
                       "recovered_ms": round(est.offset_ms, 6),
                       "error_ms": round(err, 6),
                       "min_rtt_bound_ms": round(est.error_bound_ms, 6),
                       "within_bound": bool(within)}

    # pooled vs per-worker p99: the pooled quantile (summed buckets)
    # must land inside the per-worker envelope
    merged = base1["plane"].federation().merged()
    fam = merged.get("serving.ttft_ms", {})
    pooled_p99 = worker_p99 = None
    envelope_ok = None
    if fam.get("series"):
        pooled_p99 = percentile_from_buckets(
            fam["pooled"]["buckets"], 0.99)
        worker_p99 = {
            row["labels"]["worker"]: round(
                percentile_from_buckets(row["buckets"], 0.99), 6)
            for row in fam["series"]
            if row.get("count") and "worker" in row["labels"]}
        if pooled_p99 is not None and worker_p99:
            lo, hi = min(worker_p99.values()), max(worker_p99.values())
            envelope_ok = bool(lo - 1e-9 <= pooled_p99 <= hi + 1e-9)
            pooled_p99 = round(pooled_p99, 6)

    deterministic = base1["signature"] == base2["signature"]
    return {
        "trace": {"seed": seed, "requests": n_req, "workers": 2,
                  "ticks": base1["ticks"]},
        "federation_overhead": {
            "pulls": fed["pulls"],
            "per_pull_ms": round(pull_ms, 4),
            "bare_tick_ms": round(base_tick_ms, 4),
            "frac_of_tick": round(pull_ms / base_tick_ms, 4)
            if base_tick_ms else None},
        "clock_offsets": offsets,
        "offset_within_bound": bool(offset_ok),
        "offset_worst_error_ms": round(worst_err, 6),
        "pooled_ttft_p99_ms_sim": pooled_p99,
        "worker_ttft_p99_ms_sim": worker_p99,
        "pooled_p99_within_worker_envelope": envelope_ok,
        "deterministic_replay": bool(deterministic),
        "step_traces": max(base1["step_traces"], fed["step_traces"]),
        "note": "virtual clocks: TTFT figures are sim-clock ms (reads "
                "advance 0.1 ms), only federation_overhead is host "
                "wall — BASELINE.md 'Fleet observability conventions'"}


def _merge_decode_artifact(section_key, section):
    """Incremental write: each finished section lands on disk immediately,
    so a later section that fails never loses completed measurements."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_DECODE.json")
    blob = {}
    if os.path.exists(path):
        with open(path) as f:
            blob = json.load(f)
    cur = blob.setdefault(section_key, {})
    cur.update(section)
    with open(path, "w") as f:
        json.dump(blob, f, indent=1)


def run_decode_bench(args):
    """bench.py --decode → BENCH_DECODE.json + one JSON line."""
    import faulthandler
    faulthandler.dump_traceback_later(1200, exit=False)  # hang diagnostics
    from paddle_tpu.observability.costmodel import profile_for_device

    dev = require_tpu()
    peaks = profile_for_device(dev)
    peak_flops = peaks.peak_bf16_flops
    hbm_bps = peaks.hbm_bps
    prefill_pts = [(1, 128), (1, 1024), (8, 1024)]
    decode_pts = [(1, 2048), (8, 2048), (1, 8192), (8, 8192)]

    skey = "llama_940m_serving"
    want = set((args.sections or
                "prefill,decode,int8,e2e,fused").split(","))
    section = {"conventions": {
                   "timing": "in-graph chained iterations, scalar-fetch "
                             "barrier, two-point difference (cancels the "
                             "fixed launch-and-fetch cost; decode rows "
                             "also cancel their prefill)",
                   "peaks": peaks.as_dict()},
               "device": dev.device_kind, "platform": dev.platform,
               "when": time.strftime("%Y-%m-%d")}

    # the 940M model only exists for the sections that drive it — a
    # fused-only rerun must not pay for a 2 GB model build it never uses
    model = params = None
    n = pbytes = 0
    if want & {"prefill", "decode", "int8", "e2e", "serving",
               "spec_decode", "spec_model", "mesh_serving",
               "slo_serving", "int8_serving", "perf_model",
               "preempt_serving", "control_plane", "disagg_serving",
               "multihost_obs"}:
        model, params, n = _decode_model(max_pos=8192)
        pbytes = n * 2                                  # bf16 weights
        c = model.config
        section["model"] = {"family": "llama3-arch", "params": n,
                            "layers": c.num_hidden_layers,
                            "hidden": c.hidden_size,
                            "vocab": c.vocab_size,
                            "kv_heads": c.num_key_value_heads,
                            "dtype": c.dtype}
        section["conventions"]["weight_bytes_bf16"] = pbytes
    _merge_decode_artifact(skey, section)

    # -- prefill ----------------------------------------------------------
    prefill = []
    if "prefill" in want:
        for b, p in prefill_pts:
            print(f"[decode-bench] prefill b={b} p={p} ...",
                  file=sys.stderr)
            sec = _prefill_latency(model, params, b, p)
            fl = 2.0 * n * b * p                       # fwd FLOPs ~ 2·N·D
            prefill.append({"batch": b, "prompt": p,
                            "latency_ms": round(sec * 1e3, 3),
                            "mfu": round(fl / (sec * peak_flops), 4)})
            print(f"prefill b={b} p={p}: {sec*1e3:.2f} ms",
                  file=sys.stderr)
        _merge_decode_artifact(skey, {"prefill": prefill})

    # -- steady-state decode ---------------------------------------------
    # max_length sweep doubles as the llama.py decode-path stance check:
    # the masked math path is O(S·max_len) per step — if per-step time
    # grows materially from 2048 → 8192 the design call is wrong
    decode = []
    prompt0 = 128
    if "decode" in want:
        for b, max_len in decode_pts:
            print(f"[decode-bench] decode b={b} L={max_len} ...",
                  file=sys.stderr)
            sec = _decode_per_step(model, params, b, prompt0, max_len)
            floor = pbytes / hbm_bps                  # weight-stream bound
            decode.append({"batch": b, "prompt": prompt0,
                           "max_length": max_len,
                           "per_step_ms": round(sec * 1e3, 4),
                           "tokens_per_sec_per_chip": round(b / sec, 1),
                           "weight_stream_floor_ms": round(floor * 1e3, 4),
                           "of_weight_stream_bound": round(floor / sec, 3)})
            print(f"decode b={b} L={max_len}: {sec*1e3:.3f} ms/step "
                  f"({b/sec:.0f} tok/s)", file=sys.stderr)
        _merge_decode_artifact(skey, {"decode": decode})

        short_len, long_len = decode_pts[0][1], decode_pts[-1][1]

        def _growth(batch):
            lo = next((d for d in decode if d["batch"] == batch
                       and d["max_length"] == short_len), None)
            hi = next((d for d in decode if d["batch"] == batch
                       and d["max_length"] == long_len), None)
            if lo and hi and long_len > short_len:
                return hi["per_step_ms"] / lo["per_step_ms"]
            return None

        g1, g8 = _growth(1), _growth(max(b for b, _ in decode_pts))
        if g1 is not None:
            mp = {"scope": "b=1",
                  "per_step_growth_short_to_long": round(g1, 3),
                  "max_lengths": [short_len, long_len],
                  "verdict": ("confirmed AT b=1 ONLY: per-step time is "
                              f"flat in max_length through {long_len} — "
                              "the masked math path holds there" if
                              g1 < 1.35 else
                              "reversed even at b=1: per-step time grows "
                              "with max_length — the flash-decode kernel "
                              "regime")}
            if g8 is not None:
                nb = max(b for b, _ in decode_pts)
                mp["growth_check_b" + str(nb)] = {
                    "per_step_growth_short_to_long": round(g8, 3),
                    "max_lengths": [short_len, long_len],
                    "verdict": (f"flat at b={nb}: live-prefix reads "
                                "holding the weight-stream bound" if
                                g8 < 1.35 else
                                f"regression at b={nb}: per-step time "
                                f"grows {round(g8, 2)}x from {short_len} "
                                f"to {long_len} — the dead cache tail is "
                                "being streamed; shapes at kv_len >= "
                                "FLAGS_decode_attention_min_len should "
                                "be riding the flash-decode kernel "
                                "(ops/pallas/decode_attention.py)")}
            _merge_decode_artifact(skey, {"math_path_at_decode": mp})

    # -- weight-only int8 decode (round-4 verdict task 5) ----------------
    if "int8" in want and model is not None:
        from paddle_tpu.models.quantized import quantize_for_decode
        from paddle_tpu.nn.quant import int8_matmul_path

        qmodel = quantize_for_decode(model)
        qbytes, fbytes = qmodel.hbm_bytes()
        c = model.config
        hd = c.head_dim
        # every weight shape the decode step pushes through
        # weight_only_linear — the path field says which matmul ran
        gemms = [(c.hidden_size, c.num_attention_heads * hd),
                 (c.hidden_size, c.num_key_value_heads * hd),
                 (c.num_attention_heads * hd, c.hidden_size),
                 (c.hidden_size, c.intermediate_size),
                 (c.intermediate_size, c.hidden_size),
                 (c.hidden_size, c.vocab_size)]
        rows = []
        for b, max_len in [(1, 2048), (8, 2048)]:
            print(f"[decode-bench] int8 decode b={b} L={max_len} ...",
                  file=sys.stderr)
            sec = _decode_per_step(qmodel, qmodel.state_dict(), b,
                                   prompt0, max_len)
            floor8 = qbytes / hbm_bps
            paths = {int8_matmul_path(b, k, n) for k, n in gemms}
            rows.append({"batch": b, "max_length": max_len,
                         "per_step_ms": round(sec * 1e3, 4),
                         "tokens_per_sec_per_chip": round(b / sec, 1),
                         "int8_weight_stream_floor_ms":
                             round(floor8 * 1e3, 4),
                         "matmul_path": (paths.pop() if len(paths) == 1
                                         else "mixed:" + ",".join(
                                             sorted(paths)))})
            print(f"int8 decode b={b} L={max_len}: {sec*1e3:.3f} ms/step "
                  f"({b/sec:.0f} tok/s)", file=sys.stderr)
        bf16 = {(d["batch"], d["max_length"]): d["per_step_ms"]
                for d in decode}
        for r in rows:
            ref = bf16.get((r["batch"], r["max_length"]))
            if ref:
                r["speedup_vs_bf16"] = round(ref / r["per_step_ms"], 3)
        _merge_decode_artifact(skey, {"int8_decode": {
            "rows": rows,
            "param_store_bytes": {"int8": qbytes, "bf16": fbytes,
                                  "ratio": round(qbytes / fbytes, 3)},
            "note": "per-out-channel absmax int8, dequant staged in-graph "
                    "(nn/quant.py); whether XLA keeps the int8 HBM stream "
                    "through the scan or materialises a bf16 copy is "
                    "exactly what per_step_ms vs the bf16 rows answers"}})

    # -- user-facing generate() wall (includes dispatch + RTT) -----------
    if "e2e" in want:
        print("[decode-bench] generate() e2e ...", file=sys.stderr)
        e2e_new = 64
        e2e = _generate_e2e(model, 1, prompt0, e2e_new, 2048)
        _merge_decode_artifact(skey, {"generate_e2e": {
            "batch": 1, "prompt": prompt0, "new_tokens": e2e_new,
            "max_length": 2048,
            "wall_s": round(e2e, 4),
            "note": "one warm generate() call incl. host dispatch and "
                    "the token fetch — the user-visible latency; the "
                    "in-graph decode rows are the chip-side truth"}})
        print(f"generate e2e: {e2e:.3f} s", file=sys.stderr)

    # -- continuous-batching serving engine ------------------------------
    if "serving" in want:
        print("[decode-bench] serving engine trace ...", file=sys.stderr)
        sv = _serving_bench(model)
        _merge_decode_artifact(skey, {"serving": sv})
        print(f"serving: {sv['tokens_per_sec']} tok/s, occupancy "
              f"{sv['mean_slot_occupancy']}, step_traces "
              f"{sv['step_traces']}", file=sys.stderr)

    # -- goodput under SLO: wave vs chunked on one seeded load -----------
    if "slo_serving" in want:
        print("[decode-bench] slo serving A/B ...", file=sys.stderr)
        sl = _slo_serving_bench(model)
        _merge_decode_artifact(skey, {"slo_serving": sl})
        print(f"slo_serving: goodput wave {sl['wave']['goodput']} vs "
              f"chunked {sl['chunked']['goodput']} under TTFT p99 "
              f"{sl['slo_targets_ms']['ttft_p99']} ms / TPOT p99 "
              f"{sl['slo_targets_ms']['tpot_p99']} ms, deterministic "
              f"{sl['deterministic_replay']}", file=sys.stderr)

    # -- speculative decoding A/B ----------------------------------------
    if "spec_decode" in want:
        print("[decode-bench] spec-decode A/B trace ...", file=sys.stderr)
        sp = _spec_decode_bench(model)
        _merge_decode_artifact(skey, {"spec_decode": sp})
        rh = sp["repetition_heavy"]
        print(f"spec_decode: accepted/step "
              f"{rh['accepted_per_step'].get('mean')}, hit_rate "
              f"{rh['draft_hit_rate']}, parity {rh['greedy_parity']} / "
              f"{sp['adversarial']['greedy_parity']}", file=sys.stderr)

    # -- draft-model vs n-gram drafter A/B -------------------------------
    if "spec_model" in want:
        print("[decode-bench] spec-model drafter A/B trace ...",
              file=sys.stderr)
        sm = _spec_model_bench(model)
        _merge_decode_artifact(skey, {"spec_model": sm})
        nv = sm["novel_text"]
        print(f"spec_model: novel-text accepted/step model "
              f"{nv['model']['accepted_per_step'].get('mean')} vs ngram "
              f"{nv['ngram']['accepted_per_step'].get('mean')} "
              f"(win={sm['model_beats_ngram_on_novel']}), parity "
              f"{nv['greedy_parity']} / "
              f"{sm['repetition_heavy']['greedy_parity']}, draft "
              f"overhead {nv['model']['draft_overhead_frac']}, mesh "
              f"paths {[r['chosen_path'] for r in sm['mesh_paths']]}",
              file=sys.stderr)

    # -- int8 quantized KV-cache serving A/B/C ---------------------------
    if "int8_serving" in want:
        print("[decode-bench] int8 serving A/B/C ...", file=sys.stderr)
        i8 = _int8_serving_bench(model)
        _merge_decode_artifact(skey, {"int8_serving": i8})
        print(f"int8_serving: capacity "
              f"{i8['capacity_at_equal_pool_bytes']['capacity_ratio']}x, "
              f"streamed "
              f"{i8['per_step_streamed_cache_bytes']['ratio']}x, parity "
              f"{i8['int8_kv']['greedy_parity']} over "
              f"{i8['int8_kv']['horizon_tokens']}+ tokens, logit delta "
              f"{i8['logit_error_oracle']['max_abs_logit_delta']}, "
              f"deterministic {i8['deterministic_replay']}",
              file=sys.stderr)

    # -- roofline cost-model attribution ---------------------------------
    if "perf_model" in want:
        print("[decode-bench] perf-model attribution A/B ...",
              file=sys.stderr)
        pm = _perf_model_bench(model)
        _merge_decode_artifact(skey, {"perf_model": pm})
        print(f"perf_model: bf16 bounds "
              f"{ {b: v['ticks'] for b, v in pm['bf16']['bounds'].items()} }"
              f", kv term ratio {pm['kv_term_ratio_int8_over_full']}x "
              f"(consistent with committed "
              f"{pm['committed_streamed_ratio']}: "
              f"{pm['kv_ratio_consistent']}), drift "
              f"{pm['drift_findings']}, step_traces {pm['step_traces']}",
              file=sys.stderr)

    # -- preemptive scheduling + tiered KV cache A/B/C -------------------
    if "preempt_serving" in want:
        print("[decode-bench] preempt serving A/B/C ...", file=sys.stderr)
        ps = _preempt_serving_bench(model)
        _merge_decode_artifact(skey, {"preempt_serving": ps})
        cap = ps["resident_capacity_at_equal_hbm_bytes"]
        print(f"preempt_serving: goodput fifo "
              f"{ps['fifo_blocking']['goodput']} vs swap "
              f"{ps['preempt_swap']['goodput']} vs recompute "
              f"{ps['preempt_recompute']['goodput']} (strictly better "
              f"{ps['preempt_goodput_strictly_better']}), token-identical "
              f"{ps['outputs_token_identical']}, peak sessions "
              f"{cap['peak_in_flight_sessions']}, decision signature "
              f"stable {ps['preempt_signature_stable']}", file=sys.stderr)

    # -- cost-model control plane: predictive admission A/B + fleet sim --
    if "control_plane" in want:
        print("[decode-bench] control plane A/B + fleet sim ...",
              file=sys.stderr)
        cp = _control_plane_bench(model)
        _merge_decode_artifact(skey, {"control_plane": cp})
        fl = cp["fleet_sim"]
        print(f"control_plane: goodput queue_depth "
              f"{cp['queue_depth']['goodput']} vs predictive "
              f"{cp['predictive']['goodput']} (>= "
              f"{cp['predictive_goodput_ge']}, class wins "
              f"{cp['strictly_better_classes']}), token-identical "
              f"{cp['outputs_token_identical_where_both_admit']}, "
              f"deterministic {cp['deterministic_replay']}, autoscale "
              f"{cp['autoscale']['actions']} (stable "
              f"{cp['autoscale']['deterministic']}), fleet "
              f"{fl['requests']} req x {fl['replicas']} replicas in "
              f"{fl['host_wall_s']} s host / {fl['sim_wall_s']} s sim",
              file=sys.stderr)

    # -- disaggregated prefill/decode over the multi-host plane ----------
    if "disagg_serving" in want:
        print("[decode-bench] disaggregated serving A/B ...",
              file=sys.stderr)
        ds = _disagg_serving_bench(model)
        _merge_decode_artifact(skey, {"disagg_serving": ds})
        print(f"disagg_serving: decode TPOT p99 (sim) colocated "
              f"{ds['colocated']['decode_tpot_p99_ms_sim']} ms vs "
              f"disagg {ds['disaggregated']['decode_tpot_p99_ms_sim']} "
              f"ms (strictly better "
              f"{ds['decode_tpot_strictly_better']}), token-identical "
              f"{ds['outputs_token_identical']}, "
              f"{ds['disaggregated']['migrations']} migrations / "
              f"{ds['disaggregated']['migration_bytes']} bytes, "
              f"deterministic {ds['deterministic_replay']}",
              file=sys.stderr)

    # -- federated observability over the multi-host plane ---------------
    if "multihost_obs" in want:
        print("[decode-bench] federated observability ...",
              file=sys.stderr)
        mo = _multihost_obs_bench(model)
        _merge_decode_artifact(skey, {"multihost_obs": mo})
        fo = mo["federation_overhead"]
        print(f"multihost_obs: federation pull "
              f"{fo['per_pull_ms']} ms ({fo['frac_of_tick']}x bare "
              f"tick), offset err {mo['offset_worst_error_ms']} ms "
              f"within bound {mo['offset_within_bound']}, pooled p99 "
              f"in worker envelope "
              f"{mo['pooled_p99_within_worker_envelope']}, "
              f"deterministic {mo['deterministic_replay']}",
              file=sys.stderr)

    # -- mesh-sharded serving: mp engine + dp router A/B -----------------
    if "mesh_serving" in want:
        print("[decode-bench] mesh serving A/B ...", file=sys.stderr)
        ms = _mesh_serving_bench(model)
        _merge_decode_artifact(skey, {"mesh_serving": ms})
        print(f"mesh_serving: parity "
              f"{ms['mp_engine']['greedy_parity']}, preflight "
              f"findings {ms['mp_engine']['preflight_findings']}, "
              f"router pooled hit rate "
              f"{ms['dp_router']['prefix_policy']['prefix_hit_rate_pooled']}"
              f" (prefix) vs "
              f"{ms['dp_router']['round_robin']['prefix_hit_rate_pooled']}"
              f" (round-robin)", file=sys.stderr)

    # -- fused_multi_transformer vs per-layer stack ----------------------
    if "fused" in want:
        print("[decode-bench] fused_multi_transformer vs stack ...",
              file=sys.stderr)
        fv = _fused_vs_stack()
        _merge_decode_artifact(skey, {
            "fused_multi_transformer_vs_stack": fv,
            "fused_conclusion": (
                "the whole-stack op and the per-layer stack compile to "
                f"the same speed (ratio {fv['fused_over_stack']}x) — on "
                "TPU the fusion lives in XLA, the op is API parity by "
                "design" if 0.9 <= fv["fused_over_stack"] <= 1.1 else
                f"measured ratio {fv['fused_over_stack']}x — see rows")})
        print(f"fused/stack per-step: {fv['fused_per_step_ms']} / "
              f"{fv['stack_per_step_ms']} ms", file=sys.stderr)

    if not decode:                    # section-selected rerun: summary only
        print(json.dumps({"metric": "decode_bench_partial", "value": 1,
                          "unit": "artifact", "vs_baseline": 0.0,
                          "detail": {"artifact": "BENCH_DECODE.json",
                                     "sections": sorted(want)}}))
        return
    head = max(decode, key=lambda d: (d["batch"], -d["max_length"]))
    print(json.dumps({
        "metric": ("decode_tokens_per_sec_per_chip_llama3_arch_"
                   f"{round(n / 1e6)}m_bs{head['batch']}"),
        "value": head["tokens_per_sec_per_chip"], "unit": "tokens/s",
        "vs_baseline": 0.0,
        "detail": {"artifact": "BENCH_DECODE.json",
                   "device": dev.device_kind,
                   "prefill": prefill, "decode": decode}}))


def tpu_lane_summary():
    """Self-proving chip correctness (round-4 verdict task 2b): the
    registry sweep (every TARGET_SURFACE op executes on-device, batched —
    op_smoke.run_batched) plus train and decode smoke steps, so the result
    lands in the driver-captured JSON.  Runs in the ``--lane`` child; a
    phase that fails raises and fails the run.  The full lane
    (`bench.py --selftest`) remains the deep check (Mosaic kernel paths at
    engine geometry, forced-flash parity, linalg edges)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.distributed as dist
    from paddle_tpu.framework import op_smoke
    from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
    from paddle_tpu.optimizer import AdamW

    t0 = time.time()
    pt.seed(0)
    fails = op_smoke.run_batched()
    if fails:
        raise RuntimeError(
            f"{len(fails)} registry ops fail on the chip:\n" + "\n".join(
                f"  {k}: {v[:160]}" for k, v in sorted(fails.items())))

    hcg = dist.HybridCommunicateGroup(devices=jax.devices()[:1])
    dist.set_hybrid_group(hcg)
    try:
        pt.seed(7)
        model = LlamaForCausalLM(tiny_llama_config())
        step, params, opt_state = dist.build_train_step(
            model, AdamW(learning_rate=1e-3), hcg=hcg)
        ids = np.random.RandomState(0).randint(0, 256, (4, 17))
        batch = dist.shard_batch(
            {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}, hcg)
        loss, params, opt_state = step(params, opt_state, batch,
                                       jax.random.key(0))
        if not np.isfinite(float(loss)):
            raise RuntimeError(f"train smoke: non-finite loss {loss}")
    finally:
        dist.set_hybrid_group(None)

    pt.seed(11)
    lm = LlamaForCausalLM(tiny_llama_config())
    lm.eval()
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 6)))
    gen = lm.generate(ids, max_new_tokens=4)
    if gen.shape != (2, 10):
        raise RuntimeError(f"decode smoke: generate() shape {gen.shape}")
    return {"op_sweep": {"cases": len(op_smoke.smoke_cases()),
                         "failed": {}},
            "train_smoke": "ok", "decode_smoke": "ok", "passed": True,
            "seconds": round(time.time() - t0, 1)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=None,
                    help="iterations (default: 20 for the train bench, "
                         "50 for --op rms_norm)")
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--single", action="store_true",
                    help="(child) one measurement in this process")
    ap.add_argument("--lane", action="store_true",
                    help="(child) the device query and the tpu_lane "
                         "correctness summary in this process")
    ap.add_argument("--selftest", action="store_true",
                    help="run the real-TPU test lane (pytest -m tpu on this "
                         "chip) instead of the benchmark")
    ap.add_argument("--op", choices=["rms_norm", "flash",
                                     "decode_attention"],
                    help="op-level perf harness: reproduce the kernel "
                         "measurement tables into BENCH_OPS.json")
    ap.add_argument("--decode", action="store_true",
                    help="serving perf harness: prefill latency + decode "
                         "tokens/sec + fused_multi_transformer vs stack "
                         "into BENCH_DECODE.json")
    ap.add_argument("--sections", default=None,
                    help="comma list for the decode/serving harness: "
                         "prefill,decode,int8,e2e,fused (default all) "
                         "plus the opt-in continuous-batching 'serving' "
                         "trace, the 'spec_decode' speculative A/B, "
                         "the 'spec_model' draft-model-vs-n-gram "
                         "drafter A/B (novel-text + repetition traces, "
                         "rejection sampling, mesh dispatch rows) and "
                         "the 'mesh_serving' mp-engine + dp-router A/B "
                         "(needs a four-chip host) and "
                         "the 'slo_serving' goodput-under-SLO wave-vs-"
                         "chunked A/B on one seeded loadgen trace and "
                         "the 'perf_model' roofline attribution A/B "
                         "(bf16 vs int8 KV on one trace) and the "
                         "'preempt_serving' preemption + tiered-KV A/B/C "
                         "(FIFO-blocking vs preempt+swap vs "
                         "preempt+recompute under a tight pool) and the "
                         "'control_plane' predictive-admission A/B + "
                         "replica-autoscaler trace + device-free fleet-"
                         "simulator scale row and the 'disagg_serving' "
                         "colocated-vs-disaggregated multi-host plane "
                         "A/B on per-worker simulated clocks and the "
                         "'multihost_obs' federated-observability row "
                         "(federation pull cost, clock-offset recovery "
                         "under injected skews, pooled-vs-per-worker "
                         "p99 agreement); implies --decode")
    ap.add_argument("--check-history", action="store_true",
                    dest="check_history",
                    help="perf-regression gate: validate the committed "
                         "BENCH_r*.json / BENCH_DECODE.json trajectory "
                         "against the tolerances in observability."
                         "regression.HISTORY_TOLERANCES and exit "
                         "non-zero on any regression (no device needed)")
    ap.add_argument("--no-lane", action="store_true", dest="no_lane",
                    help="skip the embedded tpu_lane correctness summary "
                         "(quick local bench runs)")
    ap.add_argument("--remat", choices=["dots", "full", "none"],
                    default="dots",
                    help="recompute policy for --single (none = no remat; "
                         "+4%% MFU at depths that fit HBM)")
    args = ap.parse_args()
    if args.steps is None:
        args.steps = 50 if args.op == "rms_norm" else 20

    if args.check_history:
        # pure artifact parsing — keep it device-free (and fast) so CI
        # can gate on it before any bench runs
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from paddle_tpu.observability.regression import check_history
        result = check_history()
        print(json.dumps(result, indent=1))
        raise SystemExit(0 if result["ok"] else 1)

    if args.op:
        run_op_bench(args)
        return

    if args.decode or args.sections:
        run_decode_bench(args)
        return

    if args.selftest:
        # The reference's GPU-CI-lane equivalent: Pallas kernels via Mosaic,
        # a registry sweep executing every TARGET_SURFACE op on-device, and
        # train/decode smoke steps.  Run on an idle chip (never concurrently
        # with the bench — see tests/conftest.py).
        env = dict(os.environ, PT_TPU_LANE="1")
        raise SystemExit(subprocess.call(
            [sys.executable, "-m", "pytest", "tests/", "-m", "tpu", "-q"],
            env=env, cwd=os.path.dirname(os.path.abspath(__file__))))

    if args.single:
        run_single(args)
        return

    if args.lane:
        run_lane(args)
        return

    # From here on this process is the PARENT: it never imports jax (a
    # parent that has touched jax holds the chip and its children hang).
    # The device query and the self-proving correctness lane — registry
    # sweep + smoke steps, landing in the printed JSON (round-4 verdict
    # task 2b) — run FIRST, in a child of their own.
    report = spawn_child(["--lane"] + (["--no-lane"] if args.no_lane
                                       else []), "LANE", timeout=1500)
    device, lane = report["device"], report["lane"]
    kind, n_chips = device["kind"], device["count"]
    if lane is not None:
        print(f"tpu_lane: passed={lane['passed']} "
              f"({lane['seconds']}s)", file=sys.stderr)

    # the published-width curve is sized for one chip's HBM as the child
    # reports it; 1 GB headroom under the limit for the runtime's own use
    hbm = device["hbm_bytes_limit"] - 1.0e9
    vocab = args.vocab or 8192
    batch = args.batch or 2
    seq = args.seq or 2048
    depths = [8, 6, 5, 4, 3, 2]

    if args.layers:
        head_depth = args.layers
    else:
        fits = [d for d in depths
                if predicted_bytes(d, vocab, batch, seq) <= hbm * n_chips]
        # one step past the analytic fit first: the estimate prices 'dots'
        # remat activations plus slack and has been a layer conservative
        # on every recorded run (BENCH_r04/r05: 4 layers ran where it
        # predicted 3).  A point that fails fails the run.
        stretch = [d for d in depths if d not in fits][-1:]
        head_depth = (stretch + fits)[0]

    # fastest strategy first: no remat (+4% MFU when activations fit HBM,
    # measured round 4)
    deepest = spawn_point(head_depth, vocab, batch, seq, args.steps,
                          args.warmup, remat="none")
    curve = [deepest]

    # ≥3-point depth curve: deepest, midpoint, half (round-2 verdict #3).
    # Going deeper than the stretch is arithmetic, not will: at vocab 4096
    # even 6 layers is 1.34e9 params x 14 B = 18.8 GB > one v5e's HBM, so
    # extra points come from the shallow side, plus one deep-narrow point
    # (vocab 4096, seq 1024).  Every point runs the head's strategy — the
    # depth extrapolation fits points of ONE strategy.
    half = max(1, deepest["layers"] // 2)
    extra = sorted({half, (deepest["layers"] + half) // 2}
                   - {deepest["layers"]}, reverse=True)
    for d in extra:
        curve.append(spawn_point(d, vocab, batch, seq, args.steps,
                                 args.warmup, remat="none"))
    if not args.layers:
        curve.append(spawn_point(deepest["layers"] + 1, 4096, batch, 1024,
                                 args.steps, args.warmup, remat="none"))

    head = curve[0]
    # honest label: the metric names the MEASURED size; full-depth numbers
    # are a clearly-marked extrapolation of the depth curve, not the value
    name = f"mfu_llama3_arch_{round(head['params'] / 1e6)}m"
    same_cfg = [p for p in curve
                if p["vocab"] == head["vocab"] and p["seq"] == head["seq"]]
    extrap = None
    if len(same_cfg) >= 2:
        import math
        xs = [math.log2(p["layers"]) for p in same_cfg]
        ys = [p["mfu_6nd"] for p in same_cfg]
        n_pts = len(xs)
        mx, my = sum(xs) / n_pts, sum(ys) / n_pts
        denom = sum((x - mx) ** 2 for x in xs)
        slope = (sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / denom
                 if denom else 0.0)
        extrap = {
            "layers": 32,
            "mfu_6nd": round(my + slope * (math.log2(32) - mx), 4),
            "method": f"linear fit of mfu vs log2(depth) over "
                      f"{n_pts} measured points — an estimate, not a "
                      f"measurement (32 layers do not fit one chip's HBM)"}
    out = {"metric": name, "value": head["mfu_6nd"],
           "unit": "fraction_of_peak_bf16",
           "vs_baseline": round(head["mfu_6nd"] / 0.45, 4),
           "detail": {
               "chips": n_chips, "device": kind,
               "platform": device["platform"],
               "strategy": {"zero_stage": 3, "recompute": head["remat"]},
               "conventions": {
                   "mfu_6nd": "6*N*D, no attention FLOPs",
                   "mfu_attn": "6*N*D + 12*L*H*S^2*B, causal not halved",
                   "peak_bf16_flops": head["peak_bf16_flops"]},
               "extrapolation_8b_depth": extrap,
               "curve": curve,
               "tpu_lane": lane}}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
