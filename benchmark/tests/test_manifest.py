import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import serve


def test_the_committed_manifest_checks_and_every_reader_loads():
    manifest = mf.load()
    mf.check(manifest)
    for m in manifest["per_layer"]:
        assert callable(mf.load_metric(m["name"]).read), m["name"]


def test_entries_that_differ_in_their_last_part_share_a_reader():
    both = {mf.metric_file("entry.ttft_p50_ms." + tail)
            for tail in ("open", "saturated")}
    assert len(both) == 1 and both.pop().endswith("entry.ttft_p50_ms.py")
    assert mf.metric_file("no.such.metric").endswith("no.such.metric.py")


def test_a_moves_that_its_cell_does_not_report_is_refused():
    manifest = mf.load()
    broken = dict(manifest, per_layer=[
        dict(m, moves="ttft_p95_ms") if m["name"] == "sched.occupancy_mean"
        else m for m in manifest["per_layer"]])
    with pytest.raises(mf.ManifestError, match="does not report"):
        mf.check(broken)


@pytest.mark.parametrize("engine, positions", [
    ({"num_slots": 24, "max_length": 4096}, 24 * 4096),
    ({"num_slots": 96, "max_length": 4096, "paged": True, "block_len": 128,
      "num_blocks": 769}, 768 * 128),
])
def test_cache_positions(engine, positions):
    assert serve.cache_positions(engine) == positions
