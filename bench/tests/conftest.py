"""Tiny copies of the benchmark's cells for CPU tests: the same files under
a temporary root, with the configurations cut to a few hundred vertices,
plus one open-loop cell of path queries and edge updates that exercises the
vocabulary no cell of ``BENCHMARK.json`` uses yet."""
import json
import pathlib
import shutil

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY = {
    "g500-s16": {"generator": {"scale": 8}, "capacity": 1024,
                 "load_batch": 1024},
}

OPEN_CELL = "g500-s16.paths-mixed"

# open loop: path queries between two live vertices, batched <= 16, and
# single-update batches at 10 ops per query: a symmetric edge pair added,
# one removed, a vertex added under an unused key, one removed
PATHS_MIXED = {
    "loop": "open",
    "rate": {"queries_per_s": 5},
    "updates_per_query": 10,
    "templates_per_batch": 1,
    "templates": [
        {"name": "add-pair", "share": 45, "pick": "live2",
         "ops": [["AddE", "a", "b"], ["AddE", "b", "a"]]},
        {"name": "rem-pair", "share": 40, "pick": "edge",
         "ops": [["RemE", "a", "b"], ["RemE", "b", "a"]]},
        {"name": "add-vertex", "share": 10, "pick": "unused",
         "ops": [["AddV", "a"]]},
        {"name": "rem-vertex", "share": 5, "pick": "live",
         "ops": [["RemV", "a"]]},
    ],
    "queries": {"kind": "get_paths", "pairs": "live2", "max_batch": 16},
}


def make_root(dest: pathlib.Path) -> pathlib.Path:
    """A checkout holding ``BENCHMARK.json`` and ``bench/``, with every
    configuration cut to its ``TINY`` size, and ``OPEN_CELL`` added."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, cut in TINY.items():
        p = dest / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        cfg["generator"].update(cut["generator"])
        cfg.update({k: v for k, v in cut.items() if k != "generator"})
        p.write_text(json.dumps(cfg))
    (dest / "bench" / "traffic" / "paths-mixed.json").write_text(
        json.dumps(PATHS_MIXED))
    spec = json.loads((dest / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": OPEN_CELL, "config": "g500-s16",
                              "traffic": "paths-mixed", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "mut_ack_p95_ms":
            m["workloads"].append(OPEN_CELL)
    spec["end_to_end"].append({"name": "query_p95_ms", "unit": "ms",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": [OPEN_CELL]})
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))
