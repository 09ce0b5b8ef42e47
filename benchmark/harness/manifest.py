"""``BENCHMARK.json`` and the files it names: loaded, and checked before
anything runs, so that a manifest a later PR breaks fails at once and by
name."""

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(ValueError):
    pass


def _json(path):
    with open(path) as f:
        return json.load(f)


def load():
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_file(name):
    return os.path.join(BENCH, "workloads", name + ".json")


def traffic_file(name):
    return os.path.join(BENCH, "traffic", name + ".json")


def metric_file(name):
    """The reader of one per-layer metric: ``metrics/<name>.py``, or the
    file of the name less its last parts (``a.b.open`` and ``a.b.saturated``
    are two entries with their own ``moves`` and may share ``a.b.py``)."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(BENCH, "metrics", ".".join(parts[:n]) + ".py")
        if os.path.isfile(path):
            return path
    return os.path.join(BENCH, "metrics", name + ".py")


def reports(metric, cell_name):
    """Whether ``metric`` (an entry of end_to_end or per_layer) is reported
    in the cell: in its ``workloads`` list, or in every cell without one."""
    return cell_name in metric.get("workloads", [cell_name])


def check(manifest):
    """Raise ManifestError naming the first thing that is wrong."""
    def bad(msg):
        raise ManifestError(msg)

    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in manifest[kind]:
            if not NAME.match(entry["name"]):
                bad(f"{kind}: name {entry['name']!r} is not "
                    f"[A-Za-z0-9_.-]{{1,64}}")
            if entry["name"] in seen:
                bad(f"{kind}: name {entry['name']!r} appears twice")
            seen.add(entry["name"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad(f"metric {m['name']}: better {m['better']!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                bad(f"metric {m['name']}: unknown cell {c!r}")
    if "setup_s" not in e2e:
        bad("end_to_end lacks setup_s")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            bad(f"per-layer metric {m['name']} moves {m['moves']!r}, which "
                f"is no end-to-end metric")
        for c in m.get("workloads", list(cells)):
            if not reports(e2e[m["moves"]], c):
                bad(f"per-layer metric {m['name']} lists cell {c}, which "
                    f"does not report {m['moves']}")
        if not os.path.isfile(metric_file(m["name"])):
            bad(f"per-layer metric {m['name']}: no {metric_file(m['name'])}")
    for c in configs.values():
        if not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad(f"config {c['name']}: no file {c['file']}")
    for w in cells.values():
        if w["config"] not in configs:
            bad(f"cell {w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            bad(f"cell {w['name']}: chips {w['chips']!r}")
        for path in (cell_file(w["name"]), traffic_file(w["traffic"])):
            if not os.path.isfile(path):
                bad(f"cell {w['name']}: no {path}")
        if not any(reports(m, w["name"]) for m in manifest["per_layer"]):
            bad(f"cell {w['name']} reports no per-layer metric")
        if not any(reports(m, w["name"]) for n, m in e2e.items()
                   if n != "setup_s"):
            bad(f"cell {w['name']} reports no end-to-end metric but setup_s")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad(f"{four} of {len(cells)} cells ask for 4 chips: at most a "
            f"quarter may, and one always may")


def load_cell(manifest, name):
    """(manifest entry, cell file, config file, traffic file) of one cell."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise ManifestError(
            f"no cell {name!r}; known: "
            f"{[w['name'] for w in manifest['workloads']]}")
    config = next(c for c in manifest["configs"]
                  if c["name"] == entry["config"])
    return (entry, _json(cell_file(name)),
            _json(os.path.join(ROOT, config["file"])),
            _json(traffic_file(entry["traffic"])))


def load_metric(name):
    """The reader module of one per-layer metric."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + re.sub(r"\W", "_", name), metric_file(name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
