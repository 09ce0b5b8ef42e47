"""chip_smoke.py's legs at ``tiny_llama_config`` on the CPU, Pallas in
interpret mode: plumbing only — that each leg runs, checks what it says it
checks and fails when a check fails.  No timing is asserted; the real run
is ``python chip_smoke.py`` on the chip (tests/test_tpu_lane.py runs the
parity cases there at engine geometry)."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from paddle_tpu import flags
from paddle_tpu.models import tiny_llama_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(b=2, hq=4, hkv=2, d=64, kv_len=256, dtype="float32",
            interpret=True)
TINY_GEOMETRY = {
    "decode_contiguous_s1": TINY,
    "decode_paged_s1": TINY,
    "decode_paged_chunk256": dict(TINY, b=1, kv_len=512),
    "decode_paged_spec_window5": TINY,
    "decode_paged_int8_kv": TINY,
    "decode_paged_window_s1": dict(TINY, window=100),
    "decode_paged_window_chunk256": dict(TINY, b=1, kv_len=512, window=200),
    "moe_experts_gmm": dict(rows=96, experts=6, k=128, n=256,
                            dtype="float32", interpret=True),
    "int8_matmul": dict(rows=8, k=256, n=256, dtype="float32",
                        interpret=True),
    "flash_fwd_bwd": dict(b=1, s=256, hq=4, hkv=2, d=64, dtype="float32",
                          interpret=True),
}


@pytest.fixture
def interpreted():
    """Route dispatch to the Pallas kernels (interpreted) at tiny cache
    lengths, the way a TPU backend routes them at real ones."""
    old = {k: flags.flag(k) for k in ("pallas_interpret",
                                      "decode_attention_min_len",
                                      "flash_attention_force")}
    flags.set_flags({"pallas_interpret": True,
                     "decode_attention_min_len": 256,
                     "flash_attention_force": True})
    yield
    flags.set_flags(old)


def _config():
    return tiny_llama_config(max_position_embeddings=256)


@pytest.mark.parametrize("name", sorted(chip_smoke.PARITY_CASES))
def test_parity_case_tiny(name):
    assert set(TINY_GEOMETRY) == set(chip_smoke.PARITY_CASES)
    err = chip_smoke.PARITY_CASES[name](**TINY_GEOMETRY[name])
    assert 0 <= err < 1e-3                  # f32 operands on the CPU


def test_parity_case_fails_outside_tolerance(monkeypatch):
    monkeypatch.setattr(chip_smoke, "ATTN_TOL", 0.0)
    with pytest.raises(AssertionError, match="outside"):
        chip_smoke.parity_decode(**TINY)


def test_serve_legs_tiny(interpreted):
    cfg = _config()
    model = chip_smoke.build_model(cfg)
    prompts = chip_smoke.smoke_prompts(cfg.vocab_size, (70, 100, 130))
    wave = chip_smoke.serve_leg(
        model, prompts, 4, num_slots=2, max_length=256,
        expect_paths=("decode_attention/pallas_decode/contiguous",
                      "flash_attention/pallas"))
    assert wave["requests"] == 3 and wave["step_traces"] == 1
    assert wave["rounds_agree"] == 1.0
    paged = chip_smoke.serve_leg(
        model, prompts, 4, num_slots=2, max_length=256, paged=True,
        chunked=True, block_len=128, prefill_chunk=64,
        expect_paths=("decode_attention/pallas_decode/paged",
                      "chunked_prefill/paged"))
    assert paged["prefill_traces"] == 0     # the mixed step is the program
    assert chip_smoke.agreeing_share(wave["tokens"], paged["tokens"]) > 0
    with pytest.raises(AssertionError, match="no 'int8_matmul/pallas_int8'"):
        chip_smoke.serve_leg(model, prompts[:1], 2, num_slots=2,
                             max_length=256,
                             expect_paths=("int8_matmul/pallas_int8",))


def test_afmoe_serve_leg_tiny(interpreted):
    """The smoke's second architecture: built to be loaded, served on the
    paged, chunked engine past its window, the grouped product counted."""
    from paddle_tpu.models import tiny_afmoe_config
    cfg = tiny_afmoe_config(max_position_embeddings=256, head_dim=64,
                            hidden_size=128, moe_intermediate_size=128,
                            sliding_window=64, num_experts=8, ep_size=2)
    model = chip_smoke.build_afmoe_model(cfg)
    leg = chip_smoke.serve_leg(
        model, chip_smoke.smoke_prompts(cfg.vocab_size, (70, 100, 130)), 4,
        num_slots=2, max_length=256, paged=True, chunked=True,
        block_len=128, prefill_chunk=64,
        expect_paths=("decode_attention/pallas_decode/paged",
                      "chunked_prefill/paged", "moe_experts/pallas_gmm"))
    assert leg["step_traces"] == 1 and leg["rounds_agree"] == 1.0


def test_serve_leg_fails_when_a_kernel_gives_way():
    """No interpret flag: on the CPU every dispatch takes the XLA path,
    which is exactly what the smoke must not let pass."""
    cfg = _config()
    with pytest.raises(AssertionError, match="gave way"):
        chip_smoke.serve_leg(chip_smoke.build_model(cfg),
                             chip_smoke.smoke_prompts(cfg.vocab_size, (70,)),
                             2, num_slots=2, max_length=256)


def test_train_leg_tiny(interpreted):
    facts = chip_smoke.train_leg(chip_smoke.build_model(_config()),
                                 batch=2, seq=128, steps=4,
                                 learning_rate=1e-2)
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["kernel_paths"].get("flash_attention/pallas")
    with pytest.raises(AssertionError, match="did not fall"):
        chip_smoke.train_leg(chip_smoke.build_model(_config()), batch=2,
                             seq=128, steps=2, learning_rate=0.0)


def test_four_device_legs_tiny():
    four = jax.devices()[:4]
    cfg = _config()
    facts = chip_smoke.train_leg(chip_smoke.build_model(cfg), batch=2,
                                 seq=128, steps=3, devices=four,
                                 learning_rate=1e-2, mp_degree=2,
                                 sharding_degree=2)
    assert facts["mesh"] == {"sharding": 2, "mp": 2}
    mesh = chip_smoke.serve_leg(
        chip_smoke.build_model(cfg),
        chip_smoke.smoke_prompts(cfg.vocab_size, (70, 100)), 3,
        num_slots=2, max_length=256, mesh="mp2dp2", forbid_fallbacks=False)
    assert mesh["step_traces"] == 1


def test_script_refuses_a_cpu_backend():
    """Run as a script with no TPU it exits non-zero, names the missing
    device, prints no result — and does so before building a model."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert r.returncode not in (0, None)
    assert "needs a TPU" in r.stderr and "cpu" in r.stderr
    assert '"ok"' not in r.stdout and r.stdout.strip() == ""


def test_bench_refuses_a_cpu_backend():
    """``python bench.py`` with no TPU: the ``--lane`` child names the
    missing device and the parent fails the run on it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert r.returncode == 2 and r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr and "child --lane exited 2" in r.stderr


def test_bench_parent_stays_off_jax_and_shows_a_failed_childs_stderr():
    """The parent never imports jax (it would hold the chip its children
    need), and a child that dies fails the run with its stderr shown."""
    code = (
        "import sys, bench\n"
        "try:\n"
        "    bench.spawn_child(['--single', '--layers', 'x'], 'POINT', 60)\n"
        "except SystemExit as e:\n"
        "    assert 'jax' not in sys.modules\n"
        "    sys.exit(40 + int(e.code))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=120)
    assert r.returncode == 42, r.stderr
    assert "invalid int value: 'x'" in r.stderr        # the child's words
    assert "exited 2 without a POINT line" in r.stderr


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code and jax
    fills that directory.  Unset: ``<checkout>/.jax_cache``, a fixed path."""
    from paddle_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert ("jax_compilation_cache_dir", fixed) in calls

    code = ("import jax, jax.numpy as jnp\n"
            "from paddle_tpu.utils.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: x @ x + 1)(jnp.ones((64, 64))).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path)             # jax wrote its entries there


def test_paged_step_temporaries_reads_the_compiled_step():
    """A roomy pool: the decode step's temporaries (activations, the
    gathered blocks of two rows) stay far under a layer's K+V, and the
    layer's bytes are the pool's own; a pool of three 8-token blocks is
    smaller than the step's activations, which is what a copy of the pool
    would look like."""
    cfg = _config()
    model = chip_smoke.build_model(cfg)
    facts = chip_smoke.paged_step_temporaries(
        model, num_slots=2, max_length=256, block_len=128, num_blocks=65)
    assert facts["pool_shape"] == [
        cfg.num_hidden_layers, 2, 65, 128,
        cfg.num_key_value_heads * cfg.head_dim]
    assert 0 < facts["temp_bytes"] < facts["layer_kv_bytes"]
    with pytest.raises(AssertionError, match="something copies the pool"):
        chip_smoke.paged_step_temporaries(
            model, num_slots=2, max_length=64, block_len=8, num_blocks=3)
