"""Mesh pre-flight suite (ISSUE 8): paddle_tpu/static_analysis's
mesh-aware layer — sharding propagation, the collective-cost model, the
replication-blowup / resharding-hazard / collective-deadlock rules, and
the HBM-liveness estimator.

Contract per rule: one OFFENDER the rule must flag and one clean
fixture it must pass — plus the serving integration (every engine
layout pre-flights clean under its declared mp2dp2 shardings, with the
paged HBM prediction matching ``cache_hbm_bytes`` exactly) and the
mesh-native decode step linted at mp=2 x dp=2 on the 8 virtual CPU
devices.  Everything here is ONE abstract trace per check — no compile,
no device step — so the whole file stays in the fast lane.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import static_analysis as sa
from paddle_tpu.models import LlamaForCausalLM, tiny_llama_config
from paddle_tpu.serving import ServingEngine

MAXLEN = 64


@pytest.fixture(scope="module")
def lm():
    pt.seed(7)
    model = LlamaForCausalLM(tiny_llama_config(context_parallel="gspmd"))
    model.eval()
    return model


def _mesh22():
    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    return Mesh(devs, ("dp", "mp"))


def _only(findings, rule):
    return [f for f in findings if f.rule == rule]


# -- MeshInfo / specs -------------------------------------------------------

def test_mesh_info_accepts_string_dict_mesh_and_abstract_mesh():
    assert sa.MeshInfo.of("mp2dp4").as_dict() == {"mp": 2, "dp": 4}
    assert sa.MeshInfo.of({"dp": 2, "mp": 2}).size("mp") == 2
    assert sa.MeshInfo.of(_mesh22()).as_dict() == {"dp": 2, "mp": 2}
    am = jax.sharding.AbstractMesh((2, 2), ("dp", "mp"))
    assert sa.MeshInfo.of(am).as_dict() == {"dp": 2, "mp": 2}
    with pytest.raises(ValueError, match="mp2dp2"):
        sa.MeshInfo.of("mp2dp2!")


# -- replication blowup -----------------------------------------------------

def test_replication_blowup_flags_replicated_cache(lm):
    """The motivating catch: an engine whose KV cache is NOT mesh-placed
    is fully replicated over mp — every mp peer burns the whole cache's
    HBM.  The finding is sized at exactly cache_hbm_bytes."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    found = _only(
        sa.analyze(eng._step_fn, *eng._lint_args(), mesh="mp2dp2",
                   rules=[sa.ReplicationBlowupRule(min_bytes=1)]),
        "replication-blowup")
    cache = [f for f in found if "'cache'" in f.message]
    assert cache, "replicated cache must be flagged"
    assert cache[0].severity == "error"
    assert cache[0].bytes == eng.cache_hbm_bytes
    assert "'mp'" in cache[0].message
    # dp is never checked: replication over dp is the dp contract
    assert not any("'dp'" in f.message for f in found)

    # clean fixture: the engine's DECLARED shardings (kv heads on mp)
    assert eng.lint_step(mesh="mp2dp2") == []


def test_replication_blowup_respects_threshold_and_allowlist():
    def step(cache, table):
        return cache * 2.0, table * 2.0

    cache = jnp.zeros((256, 256))                 # 256 KiB
    table = jnp.zeros((256, 256))
    # default 1 MiB floor: silent
    assert not _only(sa.analyze(step, cache, table, mesh="mp2"),
                     "replication-blowup")
    # explicit floor: both operands fire...
    rules = [sa.ReplicationBlowupRule(min_bytes=1)]
    assert len(_only(sa.analyze(step, cache, table, mesh="mp2",
                                rules=rules), "replication-blowup")) == 2
    # ...unless allowlisted by label substring (the rope-table contract)
    rules = [sa.ReplicationBlowupRule(min_bytes=1, allow=("table",))]
    found = _only(sa.analyze(step, cache, table, mesh="mp2",
                             rules=rules), "replication-blowup")
    assert len(found) == 1 and "'cache'" in found[0].message


# -- resharding hazard ------------------------------------------------------

def test_resharding_hazard_offender_and_clean():
    mesh = _mesh22()

    def offender(x):
        y = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", None)))
        z = y * 2.0
        return jax.lax.with_sharding_constraint(
            z, NamedSharding(mesh, P("mp", None)))

    x = jnp.zeros((256, 256))                     # over the 64 KiB floor
    found = _only(sa.analyze(offender, x, mesh=mesh,
                             in_shardings=(P("dp", None),)),
                  "resharding-hazard")
    assert found and found[0].severity == "warning"
    assert "dp" in found[0].message and "mp" in found[0].message
    assert found[0].bytes == x.nbytes

    def clean(x):
        y = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", None)))
        return y * 2.0

    assert not _only(sa.analyze(clean, x, mesh=mesh,
                                in_shardings=(P("dp", None),)),
                     "resharding-hazard")
    # tiny tensors reshard for free
    small = jnp.zeros((8, 8))
    assert not _only(sa.analyze(offender, small, mesh=mesh,
                                in_shardings=(P(),)),
                     "resharding-hazard")


# -- collective deadlock ----------------------------------------------------

_PERM = [(i, (i + 1) % 4) for i in range(4)]


def _mesh4():
    return Mesh(np.asarray(jax.devices()[:4]), ("dp",))


def test_collective_deadlock_offender_and_clean():
    """The collective-order lint as a Finding rule: cond branches with
    opposite ppermute rings type-check but deadlock if the predicate
    diverges — mesh-wide, through analyze(mesh=...)."""
    mesh = _mesh4()
    rev = [(i, (i - 1) % 4) for i in range(4)]

    def offender(x):
        def inner(x):
            def a(v):
                return jax.lax.ppermute(v, "dp", _PERM)

            def b(v):
                return jax.lax.ppermute(v, "dp", rev)
            return jax.lax.cond(x[0, 0] > 0, a, b, x)
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)

    found = _only(sa.analyze(offender, jnp.ones((8, 4)), mesh=mesh),
                  "collective-deadlock")
    assert found and found[0].severity == "error"
    assert "different collective" in found[0].message
    assert "shard_map" in found[0].path

    def clean(x):
        def inner(x):
            def a(v):
                return jax.lax.psum(v * 2.0, "dp")

            def b(v):
                return jax.lax.psum(v + 1.0, "dp")
            return jax.lax.cond(x[0, 0] > 0, a, b, x)
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)

    assert not _only(sa.analyze(clean, jnp.ones((8, 4)), mesh=mesh),
                     "collective-deadlock")


def test_collective_deadlock_shim_and_rule_agree():
    """distributed/lint.py is now a thin shim over walk_collectives:
    same violations, same schedule, test_collective_lint.py untouched."""
    from paddle_tpu.distributed import lint
    from paddle_tpu.static_analysis import core, mesh_rules

    assert lint._sub_jaxprs is core.sub_jaxprs
    assert lint._CANONICAL is core.CANONICAL
    assert lint._walk_collectives is mesh_rules.walk_collectives
    assert lint.check_collectives is lint.check_collective_order


# -- collective-cost model --------------------------------------------------

def test_comm_report_counts_explicit_collectives_with_ring_costs():
    mesh = _mesh4()

    def fn(x):
        def inner(x):
            def step(c, _):
                return jax.lax.ppermute(c, "dp", _PERM), None
            c, _ = jax.lax.scan(step, x, None, length=3)
            return jax.lax.psum(c, "dp")
        return shard_map(inner, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))(x)

    x = jnp.ones((8, 4), jnp.float32)
    pf = sa.preflight(fn, x, mesh=mesh)
    per_shard = x.nbytes // 4                     # (2, 4) f32 per device
    row = pf["comm"]["per_axis"]["dp"]
    # ppermute: B per step, x3 scan trips; psum: 2(n-1)/n B
    assert row["collectives"] == {"ppermute": 3, "psum_invariant": 1}
    want = 3 * per_shard + int(2 * 3 * per_shard / 4)
    assert row["bytes_per_step"] == want
    assert pf["comm"]["total_bytes_per_step"] == want
    kinds = {s["kind"] for s in pf["comm"]["sites"]}
    assert kinds == {"collective"}


def test_comm_report_implies_psum_for_contracted_sharded_dot(lm):
    """Megatron accounting: a dot_general whose CONTRACTED dim is
    sharded over mp forces GSPMD to all-reduce the products — the
    tiny llama's o_proj/down_proj row-parallel matmuls, 2 per layer."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    pf = eng.mesh_preflight("mp2dp2")
    implied = [s for s in pf["comm"]["sites"]
               if s["kind"] == "implied_psum"]
    assert len(implied) == 2 * lm.config.num_hidden_layers
    assert all(s["axes"] == ["mp"] for s in implied)
    assert pf["comm"]["per_axis"]["mp"]["bytes_per_step"] > 0
    assert pf["comm"]["per_axis"]["dp"]["bytes_per_step"] == 0


# -- HBM liveness -----------------------------------------------------------

def test_hbm_liveness_paged_matches_cache_hbm_bytes(lm):
    """ISSUE 8 acceptance: the paged engine's predicted per-device cache
    bytes, scaled back by the cache's shard count, equal
    cache_hbm_bytes (within FLAGS_graph_lint_hbm_tol; exactly, today).
    The paged pool shards kv heads over mp ONLY (any block can back any
    slot), so per-device cache is 1/2 under mp2dp2."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN, paged=True,
                        block_len=16)
    pf = eng.mesh_preflight("mp2dp2")
    assert pf["findings"] == []
    cc = pf["cache_check"]
    assert cc["ok"] and cc["rel_err"] == 0.0
    assert cc["engine_cache_hbm_bytes"] == eng.cache_hbm_bytes
    assert cc["cache_bytes_per_device"] * 2 == eng.cache_hbm_bytes
    hbm = pf["hbm"]
    assert hbm["cache_shards"] == 2
    assert (hbm["peak_bytes_per_device"]
            >= hbm["params_bytes_per_device"]
            + hbm["cache_bytes_per_device"])


def test_hbm_liveness_contiguous_shards_cache_over_dp_and_mp(lm):
    """The contiguous cache shards batch over dp AND kv heads over mp:
    1/4 per device under mp2dp2."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    pf = eng.mesh_preflight("mp2dp2")
    assert pf["cache_check"]["ok"]
    assert (pf["cache_check"]["cache_bytes_per_device"] * 4
            == eng.cache_hbm_bytes)


def test_hbm_liveness_is_donation_aware(lm):
    """The estimator's HBM view of the donation rule: the raw step
    (traced WITHOUT the threaded donate_argnums) keeps the caller's
    cache buffer alive alongside the updated copy — predicted peak
    rises by at least the per-device cache."""
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    minfo = sa.MeshInfo.of("mp2dp2")
    shardings = eng._mesh_step_shardings(minfo)
    donated = sa.preflight(eng._step_fn, *eng._lint_args(), mesh=minfo,
                           in_shardings=shardings)
    raw = sa.preflight(eng._step_fn.python_fn, *eng._lint_args(),
                       mesh=minfo, in_shardings=shardings)
    cache_pd = donated["hbm"]["cache_bytes_per_device"]
    assert (raw["hbm"]["peak_bytes_per_device"]
            >= donated["hbm"]["peak_bytes_per_device"] + cache_pd)


# -- mesh-native decode step on the virtual mesh ----------------------------

def test_mesh_decode_step_preflights_clean_mp2dp2(lm):
    """The in-tree mesh-native decode step (generate()'s scan body),
    params/cache COMMITTED onto a concrete 2x2 mesh of the 8 virtual
    CPU devices: the pre-flight derives the specs from the placed
    arrays (no in_shardings), lints clean, and sees the row-parallel
    implied psums over mp."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.generation import _place_on_mesh, init_kv_cache
    from paddle_tpu.nn.layer import bind_params

    hcg = dist.HybridCommunicateGroup(dp_degree=2, mp_degree=2,
                                      devices=jax.devices()[:4])
    dist.set_hybrid_group(hcg)
    try:
        params = lm.state_dict(include_buffers=True)
        cache = init_kv_cache(lm.config, 4, MAXLEN)
        toks = jnp.zeros((4, 1), jnp.int32)
        params, cache, toks = _place_on_mesh(lm, params, cache, toks)
        pos = jnp.zeros((4,), jnp.int32)

        def decode_step(params, cache, tokens, positions):
            with bind_params(lm, params):
                logits, cache = lm.decode_step(tokens, cache, positions)
            return jnp.argmax(logits[:, -1], axis=-1), cache

        pf = sa.preflight(decode_step, params, cache, toks, pos,
                          mesh=hcg.mesh, donate_argnums=(1,))
        assert pf["findings"] == []
        assert pf["comm"]["per_axis"]["mp"]["bytes_per_step"] > 0
        assert pf["hbm"]["cache_shards"] == 4     # dp x mp
        assert (pf["hbm"]["cache_bytes_per_device"] * 4
                == int(sum(l.nbytes
                           for l in jax.tree_util.tree_leaves(cache))))
    finally:
        dist.set_hybrid_group(None)


# -- engine integration: every layout pre-flights clean ---------------------

@pytest.mark.parametrize("kw", [
    dict(chunked=True, prefill_chunk=8),
    dict(spec_decode=True, spec_k=4),
    dict(paged=True, block_len=16, chunked=True, prefill_chunk=8,
         spec_decode=True, spec_k=4),
], ids=["chunked", "spec", "paged+chunked+spec"])
def test_engine_layouts_preflight_clean(lm, kw):
    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN, **kw)
    pf = eng.mesh_preflight("mp2dp2")
    assert pf["findings"] == []
    assert pf["cache_check"]["ok"]
    assert pf["comm"]["per_axis"]["mp"]["bytes_per_step"] > 0


def test_mesh_preflight_sets_observability_gauges(lm):
    from paddle_tpu import observability as obs

    eng = ServingEngine(lm, num_slots=2, max_length=MAXLEN)
    pf = eng.mesh_preflight("mp2dp2")
    snap = obs.default_registry().snapshot()
    comm = snap["mesh.predicted_comm_bytes"]
    vals = {tuple(sorted(c["labels"].items())): c["value"]
            for c in comm["series"]}
    key = (("axis", "mp"), ("engine", eng._eid))
    assert vals[key] == pf["comm"]["per_axis"]["mp"]["bytes_per_step"]
    peak = snap["mesh.predicted_peak_hbm_bytes"]["series"][0]["value"]
    assert peak == pf["hbm"]["peak_bytes_per_device"]


# -- CLI --------------------------------------------------------------------

def test_cli_mesh_smoke_exits_zero():
    """ISSUE 8 acceptance: the whole-stack mesh pre-flight smoke — all
    engine layouts plus the mesh decode step under mp2dp2 — exits 0."""
    from paddle_tpu.static_analysis.__main__ import main

    assert main(["--mesh", "mp2dp2", "--slots", "2",
                 "--max-length", "64", "--block-len", "16",
                 "--prefill-chunk", "8", "--spec-k", "4"]) == 0


def test_cli_json_is_versioned_and_deterministic(capsys):
    from paddle_tpu.static_analysis.__main__ import SCHEMA_VERSION, main

    argv = ["--mesh", "mp2dp2", "--slots", "2", "--max-length", "64",
            "--block-len", "16", "--prefill-chunk", "8",
            "--spec-k", "4", "--json"]
    assert main(argv) == 0
    first = capsys.readouterr().out
    blob = json.loads(first)
    assert blob["schema_version"] == SCHEMA_VERSION
    assert blob["mesh"] == {"mp": 2, "dp": 2}
    assert blob["total_findings"] == 0
    assert "mesh_decode_step" in blob["layouts"]
    for entry in blob["layouts"].values():
        assert entry["findings"] == []
    assert main(argv) == 0
    assert capsys.readouterr().out == first   # byte-identical for CI
