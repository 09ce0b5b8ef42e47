"""Mesh-sharded ServingEngine (ISSUE 9): the tensor-parallel execution
path of ROADMAP item 1.

Gold standard (the PR-8 pre-flight cashed in): an engine constructed
with ``mesh="mp2dp2"`` places params/cache per ``decode_mesh_specs``,
runs its once-jitted step under DECLARED shardings on the 8 virtual CPU
devices, and its greedy outputs are TOKEN-IDENTICAL to the single-chip
engine — in every cache layout and composition — with the retrace
budget still 1, zero pre-flight findings, and the placed footprints
matching the prediction.  The full 7-layout parity sweep and the CLI
``--execute`` smoke are heavyweight (two engines per layout) and ride
the ``slow`` lane; the fast lane keeps one contiguous parity case plus
the unit surfaces (mesh resolution, the Pallas dispatch gate, the
structured placement-drift finding).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu import flags as flags_mod
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.serving import ReplicaRouter, ServingEngine

MAXLEN = 64


@pytest.fixture(scope="module")
def lm():
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def _trace():
    shared = _prompt(32, 99)
    return [_prompt(5, 1), _prompt(9, 2),
            np.concatenate([shared, _prompt(3, 3)]),
            np.concatenate([shared, _prompt(4, 4)])]


def _run(lm, kw, n_new=5):
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN, **kw)
    rids = [eng.submit(p, max_new_tokens=n_new) for p in _trace()]
    out = dict(eng.drain())
    return [out[r] for r in rids], eng


def test_mesh_engine_contiguous_parity_placement_and_drift(lm):
    """One fast end-to-end case: mp2dp2 parity + budget-1 + clean
    pre-flight with the placement cross-check, then the drift path —
    a perturbed prediction must yield a structured hbm-liveness
    finding, not a bare assert."""
    single, _ = _run(lm, {})
    placed, eng = _run(lm, {"mesh": "mp2dp2"})
    assert placed == single
    assert eng.step_traces == 1
    assert dict(eng.mesh.shape) == {"mp": 2, "dp": 2}
    pf = eng.mesh_preflight()
    assert pf["findings"] == []
    pc = pf["placement_check"]
    assert pc["ok"] and pc["rel_err"] == 0.0
    assert (pc["measured_cache_bytes_per_device"]
            == pc["predicted_cache_bytes_per_device"]
            == eng.cache_hbm_bytes // 4)        # dp2 x mp2 shards
    from paddle_tpu import observability as obs
    snap = obs.default_registry().snapshot()
    assert snap["mesh.measured_cache_bytes_per_device"]["series"][0][
        "value"] == pc["measured_cache_bytes_per_device"]
    # drift: halve the predicted cache bytes — the check must append a
    # structured finding and report ok=False
    bad = {"findings": [], "hbm": dict(
        pf["hbm"], cache_bytes_per_device=pf["hbm"][
            "cache_bytes_per_device"] // 2)}
    res = eng.mesh_placement_check(bad)
    assert not res["ok"]
    assert any(f.rule == "hbm-liveness" and f.severity == "error"
               for f in bad["findings"])


def test_resolve_mesh_forms():
    m = ServingEngine._resolve_mesh("mp2dp2")
    assert dict(m.shape) == {"mp": 2, "dp": 2}
    assert tuple(m.axis_names) == ("mp", "dp")
    assert ServingEngine._resolve_mesh("") is None
    assert ServingEngine._resolve_mesh("mp1") is None   # all-ones: no-op
    import paddle_tpu.distributed as dist
    hcg = dist.HybridCommunicateGroup(dp_degree=2, mp_degree=2,
                                      devices=jax.devices()[:4])
    assert ServingEngine._resolve_mesh(hcg) is hcg.mesh
    assert ServingEngine._resolve_mesh(m) is m
    with pytest.raises(ValueError, match="devices"):
        ServingEngine._resolve_mesh("mp64")


def test_dispatch_gates_pallas_under_mesh():
    """The flash-decode dispatch rule under a mesh (ISSUE 20): an
    ELIGIBLE mesh-sharded decode shape routes to the shard_map-wrapped
    per-shard kernel (``pallas_decode_shard_map``); an ineligible one
    (rows not divisible over dp×sharding) still demotes to the XLA
    gather path with a structured mesh-kind reason."""
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.attention import decode_attention_path

    old = flags_mod.flag("pallas_interpret")
    flags_mod.set_flags({"pallas_interpret": True})
    try:
        path, reason = decode_attention_path(1, 1, 8, 2, 64, 8192)
        assert path == "pallas_decode"
        mesh = ServingEngine._resolve_mesh("mp2dp2")
        with denv.use_mesh(mesh):
            # b=1 can't split over dp*sharding=2: demote, mesh kind
            path, reason = decode_attention_path(1, 1, 8, 2, 64, 8192)
            assert path == "xla_math" and "mesh-sharded" in reason
            assert attention.reason_kind(reason) == attention.KIND_MESH
            # b=4 splits evenly, heads divide mp, per-shard shape fits:
            # the mesh fast path
            path, reason = decode_attention_path(4, 1, 8, 2, 64, 8192)
            assert path == "pallas_decode_shard_map" and reason is None
        # an all-ones mesh is single-chip: no gate
        import paddle_tpu.distributed as dist
        one = dist.HybridCommunicateGroup(devices=jax.devices()[:1]).mesh
        with denv.use_mesh(one):
            path, _ = decode_attention_path(1, 1, 8, 2, 64, 8192)
        assert path == "pallas_decode"
    finally:
        flags_mod.set_flags({"pallas_interpret": old})


def test_shard_map_decode_parity_and_routing():
    """ISSUE 20 acceptance (interpret tier): the shard_map fast path
    numerically matches the XLA gather reference at mp2dp2 on the
    virtual CPU devices — contiguous and paged — and the trace counts
    a ``pallas_decode_shard_map`` kernel_path row (outer dispatch) plus
    per-shard ``pallas_decode`` rows (the body's re-dispatch at
    Hkv/mp-head geometry)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.ops.attention import (cached_decode_attention,
                                          cached_decode_attention_reference,
                                          paged_decode_attention)

    b, s, hq, hkv, d, kv_len, bl = 4, 1, 8, 2, 64, 8192, 128
    rs = np.random.RandomState(17)
    q = jnp.asarray(rs.normal(size=(b, s, hq, d)).astype(np.float32))
    kc = jnp.asarray(rs.normal(size=(b, kv_len, hkv, d)).astype(np.float32))
    vc = jnp.asarray(rs.normal(size=(b, kv_len, hkv, d)).astype(np.float32))
    pos = jnp.asarray([37, 513, 129, 1025], jnp.int32)
    n_blocks = kv_len // bl
    # the stacked pool in kernel layout, read at layer 1; layer 0 is noise
    pool = jnp.stack([
        jnp.asarray(rs.normal(size=(2, b * n_blocks, bl, hkv * d))
                    .astype(np.float32)),
        jnp.stack([jnp.reshape(kc, (b * n_blocks, bl, hkv * d)),
                   jnp.reshape(vc, (b * n_blocks, bl, hkv * d))])])
    tables = jnp.reshape(jnp.arange(b * n_blocks, dtype=jnp.int32),
                         (b, n_blocks))
    reg = obs.default_registry()
    fam = reg.get("ops.kernel_path")
    before = (fam.value(op="decode_attention",
                        path="pallas_decode_shard_map", cache="contiguous")
              if fam is not None else 0)
    old = flags_mod.flag("pallas_interpret")
    flags_mod.set_flags({"pallas_interpret": True})
    try:
        mesh = ServingEngine._resolve_mesh("mp2dp2")
        with denv.use_mesh(mesh):
            got = cached_decode_attention(q, kc, vc, pos)
            got_paged = paged_decode_attention(q, pool, 1, pos, tables)
    finally:
        flags_mod.set_flags({"pallas_interpret": old})
    want = cached_decode_attention_reference(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_paged), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    fam = reg.get("ops.kernel_path")
    assert fam.value(op="decode_attention", path="pallas_decode_shard_map",
                     cache="contiguous") >= before + 1
    assert fam.value(op="decode_attention", path="pallas_decode_shard_map",
                     cache="paged") >= 1
    # the per-shard re-dispatch inside the body took the kernel
    assert fam.value(op="decode_attention", path="pallas_decode",
                     cache="contiguous") >= 1


# -- heavy parity sweep + CLI execute (slow lane) ---------------------------

@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    dict(paged=True, block_len=16),
    dict(chunked=True, prefill_chunk=8),
    dict(paged=True, block_len=16, chunked=True, prefill_chunk=8),
    dict(spec_decode=True, spec_k=4),
    dict(paged=True, block_len=16, spec_decode=True, spec_k=4),
    dict(chunked=True, prefill_chunk=8, spec_decode=True, spec_k=4),
    dict(paged=True, block_len=16, chunked=True, prefill_chunk=8,
         spec_decode=True, spec_k=4),
], ids=["paged", "chunked", "paged+chunked", "spec", "paged+spec",
        "chunked+spec", "paged+chunked+spec"])
def test_all_layouts_mesh_parity(lm, kw):
    """ISSUE 9 acceptance: token-identical greedy outputs between the
    single-chip and mp2dp2 engines in every layout, retrace budget 1,
    pre-flight findings 0, placement check clean."""
    single, _ = _run(lm, dict(kw))
    placed, eng = _run(lm, dict(kw, mesh="mp2dp2"))
    assert placed == single
    assert eng.step_traces == 1
    pf = eng.mesh_preflight()
    assert pf["findings"] == []
    assert pf["placement_check"]["ok"]
    if kw.get("paged"):
        # the pool shards over mp ONLY (any block backs any slot), so
        # per-device cache is 1/2 and the block tables stayed logical
        pc = pf["placement_check"]
        assert (pc["measured_cache_bytes_per_device"]
                == eng.cache_hbm_bytes // 2)
        assert eng.kv.stats["prefix_hit_tokens"] > 0


@pytest.mark.slow
def test_cli_execute_smoke_exits_zero():
    """ISSUE 9 CI satellite: `--mesh mp2dp2 --execute` actually runs
    one placed trace per layout on the virtual devices and exits 0
    (non-zero on parity or pre-flight/placement drift)."""
    from paddle_tpu.static_analysis.__main__ import main

    assert main(["--mesh", "mp2dp2", "--execute", "--slots", "2",
                 "--max-length", "64", "--block-len", "16",
                 "--prefill-chunk", "8", "--spec-k", "4"]) == 0


@pytest.mark.slow
def test_router_over_mesh_replicas(lm):
    """Composition: dp replicas that are EACH mp-sharded (the full
    ROADMAP item-1 topology, mp2 x 2 replicas on 8 virtual devices) —
    routed outputs stay token-identical to a single-chip engine."""
    router = ReplicaRouter(lm, num_replicas=2, policy="prefix",
                          paged=True, block_len=16, num_slots=2,
                          max_length=MAXLEN, mesh="mp2")
    rids = [router.submit(p, max_new_tokens=5) for p in _trace()]
    out = dict(router.drain())
    single, _ = _run(lm, dict(paged=True, block_len=16))
    assert [out[r] for r in rids] == single
