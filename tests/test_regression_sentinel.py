"""Perf-regression sentinel (paddle_tpu/observability/regression).

Two halves under test: the calibrate-then-monitor EwmaDetector (skip /
warmup semantics, one-sided vs two-sided bands, anomaly counting,
reset) and the ``bench.py --check-history`` offline gate — green on a
minimal trajectory written to ``tmp_path``, red on synthetically-regressed
copies of it (the ISSUE 15 acceptance unit test), and the CLI exit-code
mapping.
"""

import glob
import json
import os
import sys

import pytest

from paddle_tpu.observability.regression import (EwmaDetector,
                                                 HISTORY_TOLERANCES,
                                                 check_history)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- EwmaDetector ------------------------------------------------------------

def test_skip_then_calibrate_then_monitor():
    d = EwmaDetector("t", tol=1.0, warmup=4, skip=2)
    # the first ``skip`` samples (compile spikes) never reach the
    # calibration window — a 1000x outlier leaves no trace
    assert not d.observe(1000.0)
    assert not d.observe(500.0)
    for v in (1.0, 1.1, 0.9, 1.0):
        assert not d.observe(v)            # calibration, never anomalous
    assert d.baseline == pytest.approx(1.0)
    assert d.lo == pytest.approx(0.5)
    assert d.hi == pytest.approx(2.0)
    assert not d.observe(1.3)              # in band
    assert d.anomalies == 0


def test_one_sided_ignores_speedups_catches_slowdowns():
    d = EwmaDetector("lat", tol=1.0, alpha=0.5, warmup=4, skip=0)
    for _ in range(4):
        d.observe(10.0)
    for _ in range(10):
        assert not d.observe(0.01)         # getting faster: not anomalous
    assert d.anomalies == 0
    fired = [d.observe(100.0) for _ in range(6)]
    assert any(fired)
    assert d.anomalies == sum(fired)
    assert d.state()["baseline"] == pytest.approx(10.0)


def test_two_sided_catches_underprediction_and_reset():
    d = EwmaDetector("ratio", tol=1.0, alpha=0.5, warmup=4, skip=0,
                     two_sided=True)
    for _ in range(4):
        d.observe(8.0)
    fired = False
    for _ in range(8):
        fired = d.observe(0.01) or fired   # EWMA sinks below lo = 4.0
    assert fired and d.anomalies >= 1
    d.reset()
    assert d.seen == 0 and d.anomalies == 0
    assert d.baseline is None and d.ewma is None


# -- committed-history gate --------------------------------------------------

def _write_artifacts(tmp):
    """A minimal committed trajectory: two training-bench records and one
    serving artifact holding exactly the keys the cases below mutate (the
    shapes ``bench.py`` writes; the values are the July-record ones)."""
    for n, mfu in ((1, 0.6359), (2, 0.6588)):
        with open(os.path.join(tmp, f"BENCH_r{n:02d}.json"), "w") as f:
            json.dump({"n": n, "parsed": {
                "metric": "mfu_llama3_arch_940m", "value": mfu}}, f)
    decode = {
        "cpu_plumbing_smoke": {
            "serving": {"step_traces": 1},
            "int8_serving": {
                "per_step_streamed_cache_bytes": {"ratio": 0.254},
                "capacity_at_equal_pool_bytes": {"capacity_ratio": 1.97},
                "deterministic_replay": True},
            "perf_model": {"drift_findings": 0,
                           "kv_ratio_consistent": True},
            "spec_model": {
                "model_beats_ngram_on_novel": True,
                "novel_text": {"greedy_parity": True},
                "repetition_heavy": {"greedy_parity": True},
                "deterministic_replay": True, "lint_findings": 0,
                "mesh_paths": [
                    {"chosen_path": "pallas_decode_shard_map"}]}},
        "llama_940m_serving": {"decode": [
            {"batch": 1, "max_length": 2048,
             "tokens_per_sec_per_chip": 385.9,
             "of_weight_stream_bound": 1.074},
            {"batch": 8, "max_length": 8192,
             "tokens_per_sec_per_chip": 1874.0,
             "of_weight_stream_bound": 0.652}]}}
    with open(os.path.join(tmp, "BENCH_DECODE.json"), "w") as f:
        json.dump(decode, f)
    return str(tmp)


def test_check_history_green_on_committed_repo(tmp_path):
    # the checkout itself: green, with whatever it does not carry skipped
    r = check_history()
    assert r["ok"] is True and r["root"] == REPO
    # a full trajectory: every gate present and green
    r = check_history(_write_artifacts(tmp_path))
    assert r["ok"] is True
    names = {c["name"] for c in r["checks"]}
    assert {"bench_r_mfu_trajectory", "int8_streamed_bytes_ratio",
            "step_traces_budget", "decode_head_tok_s",
            "perf_model_row", "spec_model_row"} <= names
    assert all(c["ok"] is True for c in r["checks"])


def _edit(path, fn):
    with open(path) as f:
        blob = json.load(f)
    fn(blob)
    with open(path, "w") as f:
        json.dump(blob, f)


def test_synthetic_mfu_regression_fails(tmp_path):
    root = _write_artifacts(tmp_path)
    latest = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))[-1]
    _edit(latest, lambda b: b["parsed"].update(
        value=b["parsed"]["value"] * 0.5))
    r = check_history(root)
    assert r["ok"] is False
    bad = {c["name"]: c["ok"] for c in r["checks"]}
    assert bad["bench_r_mfu_trajectory"] is False


def test_synthetic_int8_ratio_regression_fails(tmp_path):
    root = _write_artifacts(tmp_path)

    def fatten(b):
        b["cpu_plumbing_smoke"]["int8_serving"][
            "per_step_streamed_cache_bytes"]["ratio"] = 0.9

    _edit(os.path.join(root, "BENCH_DECODE.json"), fatten)
    r = check_history(root)
    assert r["ok"] is False
    bad = {c["name"]: c["ok"] for c in r["checks"]}
    assert bad["int8_streamed_bytes_ratio"] is False


def test_synthetic_retrace_regression_fails(tmp_path):
    root = _write_artifacts(tmp_path)

    def retrace(b):
        b["cpu_plumbing_smoke"]["serving"]["step_traces"] = 3

    _edit(os.path.join(root, "BENCH_DECODE.json"), retrace)
    r = check_history(root)
    assert r["ok"] is False
    bad = {c["name"]: c["ok"] for c in r["checks"]}
    assert bad["step_traces_budget"] is False


def test_synthetic_spec_model_regression_fails(tmp_path):
    root = _write_artifacts(tmp_path)

    def lose_the_win(b):
        row = b["cpu_plumbing_smoke"]["spec_model"]
        row["model_beats_ngram_on_novel"] = False

    _edit(os.path.join(root, "BENCH_DECODE.json"), lose_the_win)
    r = check_history(root)
    assert r["ok"] is False
    bad = {c["name"]: c["ok"] for c in r["checks"]}
    assert bad["spec_model_row"] is False


def test_synthetic_spec_model_mesh_demotion_fails(tmp_path):
    root = _write_artifacts(tmp_path)

    def demote(b):
        for row in b["cpu_plumbing_smoke"]["spec_model"]["mesh_paths"]:
            row["chosen_path"] = "xla_math"

    _edit(os.path.join(root, "BENCH_DECODE.json"), demote)
    r = check_history(root)
    assert r["ok"] is False
    bad = {c["name"]: c["ok"] for c in r["checks"]}
    assert bad["spec_model_row"] is False


def test_missing_artifacts_skip_rather_than_fail(tmp_path):
    r = check_history(str(tmp_path))
    assert r["ok"] is True                  # partial checkouts stay green
    assert any(c["ok"] is None for c in r["checks"])


def test_tolerance_overrides_apply(tmp_path):
    r = check_history(_write_artifacts(tmp_path),
                      tolerances={"decode_head_tok_s_floor": 1e9})
    assert r["ok"] is False
    bad = {c["name"]: c["ok"] for c in r["checks"]}
    assert bad["decode_head_tok_s"] is False
    # the committed defaults are untouched
    assert HISTORY_TOLERANCES["decode_head_tok_s_floor"] == 347.0


# -- CLI exit mapping --------------------------------------------------------

def test_bench_check_history_cli_exit_codes(monkeypatch, capsys, tmp_path):
    """``bench.py --check-history`` exits 0 on a green trajectory and
    non-zero once a tracked metric regresses past tolerance."""
    import functools

    import bench
    from paddle_tpu.observability import regression
    monkeypatch.setattr(regression, "check_history", functools.partial(
        check_history, _write_artifacts(tmp_path)))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--check-history"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 0
    assert json.loads(capsys.readouterr().out)["ok"] is True

    # regress a committed floor past the committed value: same CLI,
    # same artifacts, non-zero exit
    monkeypatch.setitem(regression.HISTORY_TOLERANCES,
                        "decode_head_tok_s_floor", 1e9)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1
    assert json.loads(capsys.readouterr().out)["ok"] is False
