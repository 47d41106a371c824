"""Everything ``BENCHMARK.json`` names loads; a new configuration, mix and
per-layer metric are picked up from new files alone; and no module of the
benchmark touches an accelerator when it is imported."""
import json
import pathlib
import re
import subprocess
import sys

import numpy as np

from bench import loadgen
from bench.harness import run_cell
from bench.registry import Registry

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_everything_named_loads_and_fits_the_spec():
    reg = Registry(ROOT)
    spec = reg.spec
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        cfg = reg.config(c["name"])
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert {"generator", "capacity", "load_batch", "server",
                "guarantees", "assumed"} <= set(cfg)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for w in spec["workloads"]:
        mix = reg.traffic(w["traffic"])
        loadgen.OpStream(mix, 64, list(range(8)), [(0, 1), (1, 2)],
                         np.random.default_rng(0))
        reports = {m["name"] for m in reg.end_to_end(w["name"])}
        assert "setup_s" in reports and len(reports) >= 2
        layer = reg.per_layer(w["name"])
        assert layer and all(m["moves"] in reports for m in layer)
        for m in layer:
            assert callable(reg.reader(m["name"]))


def test_a_new_config_mix_and_metric_need_only_new_files(tiny_root,
                                                         tmp_path):
    from bench.tests.conftest import make_root

    root = make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/g500-s16.json").read_text())
    cfg["generator"]["scale"] = 7
    (root / "bench/configs/g500-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/update-heavy.json").read_text())
    mix.update(clients=2, templates_per_batch=4)
    mix["templates"] = mix["templates"][3:]
    (root / "bench/traffic/edges-only.json").write_text(json.dumps(mix))
    (root / "bench/metrics/batches_seen.extra.py").write_text(
        "def read(run):\n    return float(len(run.batches))\n")
    spec["configs"].append({"name": "g500-tiny", "source": "test",
                            "file": "bench/configs/g500-tiny.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "g500-tiny.edges-only",
                              "config": "g500-tiny",
                              "traffic": "edges-only", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("g500-tiny.edges-only")
    spec["per_layer"].append({"name": "batches_seen.extra",
                              "unit": "batches", "better": "higher",
                              "source": "host_clock", "layer": "test",
                              "moves": "mut_ops_per_s",
                              "workloads": ["g500-tiny.edges-only"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_cell(root, "g500-tiny.edges-only", 9, 1.0, True,
                   require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["batches_seen.extra"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_no_module_touches_an_accelerator_on_import():
    mods = [p.stem for p in (ROOT / "bench").glob("*.py")]
    code = (
        "import sys, runpy, pathlib\n"
        f"sys.path[:0] = [{str(ROOT / 'src')!r}, {str(ROOT)!r}]\n"
        f"for m in {mods!r}:\n"
        "    __import__('bench.' + m)\n"
        f"for p in pathlib.Path({str(ROOT / 'bench/metrics')!r}).glob('*.py'):\n"
        "    runpy.run_path(str(p))\n"
        "from jax._src import xla_bridge\n"
        "print(xla_bridge.backends_are_initialized())\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True)
    assert out.stdout.strip().splitlines()[-1] == "False"
