"""Each cell, cut to a few hundred vertices, runs through a real
``GraphCoServer`` on the CPU: the reference agrees on every result code,
every path and every write read back after the restart; and with the timed
path broken underneath, ``correct`` comes out false."""
import json

import pytest

from bench import controls
from bench.harness import run_cell
from bench.registry import Registry
from bench.tests.conftest import OPEN_CELL

CELLS = ["g500-s16.update-heavy", OPEN_CELL]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_reports_its_metrics(tiny_root, cell):
    out = run_cell(tiny_root, cell, 2 ** 40 + 11, 1.5, False,
                   require_tpu=False)
    assert out["correct"], out["checks"]
    assert all(v["value"] == 0 for v in out["checks"].values())
    assert out["failed"] == 0 and out["attempted"] > 0
    reg = Registry(tiny_root)
    assert set(out["metrics"]) == {m["name"] for m in reg.end_to_end(cell)}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    json.dumps(out)


FAULTS = [(c, f) for c in CELLS
          for f in ("control", "state_unchanged", "half_batch",
                    "code_altered")] + [(CELLS[1], "path_altered")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_planted_fault_is_not_correct(tiny_root, cell, fault):
    out = run_cell(tiny_root, cell, 5, 1.5, False, require_tpu=False,
                   on_window=controls.FAULTS[fault])
    assert out["correct"] is False, out["checks"]


def test_get_reach_and_zipfian_keys(tiny_root, tmp_path):
    """The vocabulary no cell uses yet: zipfian keys with a moving hot set,
    and ``get_reach`` queries against a configuration with the index on."""
    from bench.tests.conftest import make_root

    root = make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/g500-s16.json").read_text())
    cfg["server"]["index"] = True
    (root / "bench/configs/g500-idx.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/paths-mixed.json").read_text())
    mix["queries"] = {"kind": "get_reach", "pairs": "out_src",
                      "max_batch": 4}
    mix["keys"] = {"dist": "zipfian", "theta": 0.99,
                   "hot_shift_per_batch": 7}
    mix["templates"].append({"name": "AddE-zipf", "share": 20,
                             "pick": "key2", "ops": [["AddE", "a", "b"]]})
    (root / "bench/traffic/reach-zipf.json").write_text(json.dumps(mix))
    spec["configs"].append({"name": "g500-idx", "source": "test",
                            "file": "bench/configs/g500-idx.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "g500-idx.reach-zipf",
                              "config": "g500-idx", "traffic": "reach-zipf",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("mut_ack_p95_ms", "query_p95_ms"):
            m["workloads"].append("g500-idx.reach-zipf")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_cell(root, "g500-idx.reach-zipf", 3, 1.5, False,
                   require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["query_p95_ms"]["value"] > 0
