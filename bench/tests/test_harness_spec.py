"""``BENCHMARK.json`` and the files it names agree: every cell's
configuration and traffic file exists, every metric has its reader,
and every per-layer metric moves an end-to-end metric that each of its
cells reports."""

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _cells(metric):
    return metric.get("workloads", [w["name"] for w in SPEC["workloads"]])


def test_names_and_files():
    for entry in SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
            + SPEC["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_every_metric_has_a_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(_cells(m)) <= set(_cells(moved)), m["name"]
    for w in SPEC["workloads"]:
        reported = [m for m in SPEC["end_to_end"] if w["name"] in _cells(m)]
        assert len(reported) >= 2 and any(
            w["name"] in _cells(m) for m in SPEC["per_layer"])


def test_marks_name_runs_by_the_ops_inside():
    ops = [(0.0, 1.0, "fusion.1"), (1.0, 2.0, "decode_attention_pallas.7"),
           (5.0, 6.0, "fusion.2")]
    starts = [o[0] for o in ops]
    marks = {"decode": harness.DECODE_KERNEL}
    assert trace_reduce._marked("jit_eval", 0.0, 3.0, starts, ops,
                                marks) == "jit_eval [decode]"
    assert trace_reduce._marked("jit_eval", 4.0, 7.0, starts, ops,
                                marks) == "jit_eval"
