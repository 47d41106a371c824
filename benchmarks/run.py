"""Benchmark driver — one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract.
  python -m benchmarks.run [--quick] [--json PATH] [--smoke]

``--json`` additionally writes the sweep figures' rows as one uniform
long-format record list — every registered figure emits records with the
same required keys ({figure, q, engine, seconds, steps, steps_per_s,
speedup_vs_baseline}, figure-specific extras allowed), so downstream
plotting aggregates them without per-figure cases — and, on FULL runs
only, drops one ``BENCH_<figure>.json`` per figure at the repo root,
recording the perf trajectory PR over PR (quick/smoke numbers are not
comparable and never touch those records).

``--smoke`` is the CI gate: quick mode, every registered sweep figure must
run and emit schema-valid JSON (kernel/roofline sections are skipped —
they are not sweep figures).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

# Registered sweep figures: (figure-name prefix emitted in records,
# module name, banner). --smoke asserts each emits >= 1 schema-valid row.
FIGURES = (
    ("fig9_throughput", "fig9_throughput",
     "Fig. 9 analogue — throughput vs lanes, 3 mixes, no GetPath"),
    ("fig10_getpath", "fig10_getpath",
     "Fig. 10 analogue — mixes + 2% GetPath (double-collect sessions)"),
    ("multiquery", "fig_multiquery",
     "Multi-query analogue — fused multi-source BFS vs vmap, Q sweep"),
    ("sharded", "fig_sharded",
     "Sharded analogue — mesh-partitioned engines vs dense (DESIGN.md §8)"),
    ("index", "fig_index",
     "Reachability index — 2-hop label fast path vs fused BFS (DESIGN.md §9)"),
    ("serving", "fig_serving",
     "Serving admission — coalesced multi-tenant ingest vs serial baseline "
     "(DESIGN.md §12)"),
    ("snapshot", "fig_snapshot",
     "Wait-free snapshot — epoch-ring resolution vs retry loop under a "
     "100%-mutation adversary (DESIGN.md §13)"),
    ("recovery", "fig_recovery",
     "Durable ingest — WAL append overhead + recovery wall-time vs "
     "checkpoint cadence (DESIGN.md §16)"),
)

REQUIRED_KEYS = {
    "figure": str,
    "q": (int,),
    "engine": str,
    "seconds": (int, float),
    "steps": (int, float),
    "steps_per_s": (int, float),
    "speedup_vs_baseline": (int, float),
}


def validate_records(records: list[dict], expect_figures) -> list[str]:
    """Schema check for the uniform long format; returns human-readable
    failures (empty = valid)."""
    errors = []
    seen = set()
    for i, rec in enumerate(records):
        for key, types in REQUIRED_KEYS.items():
            if key not in rec:
                errors.append(f"record {i}: missing key {key!r} ({rec})")
            elif not isinstance(rec[key], types):
                errors.append(f"record {i}: {key}={rec[key]!r} is not {types}")
        if isinstance(rec.get("figure"), str):
            seen.add(rec["figure"])
    for name in expect_figures:
        if not any(fig == name or fig.startswith(name + "_") for fig in seen):
            errors.append(f"registered figure {name!r} emitted no records "
                          f"(saw {sorted(seen)})")
    return errors


def write_bench_files(records: list[dict], root: pathlib.Path = ROOT) -> list[str]:
    """One BENCH_<figure>.json per figure at the repo root — the
    longitudinal perf record the ROADMAP's trajectory is judged by."""
    by_fig: dict[str, list[dict]] = {}
    for rec in records:
        by_fig.setdefault(rec["figure"], []).append(rec)
    written = []
    for fig, rows in sorted(by_fig.items()):
        path = root / f"BENCH_{fig}.json"
        with open(path, "w", encoding="utf-8") as f:
            json.dump(rows, f, indent=1)
        written.append(str(path))
    return written


def check_committed_records(figures=None, root: pathlib.Path = ROOT
                            ) -> tuple[list[str], list[str]]:
    """Validate the COMMITTED BENCH_<figure>.json records for the registered
    figures. Returns (errors, notes).

    A figure with no committed record yet is a NOTE, never an error: a
    fresh clone (or a newly registered figure whose first full ``--json``
    run hasn't landed) must not abort ``--quick``/``--smoke`` — only a
    record that EXISTS but is unreadable or schema-invalid fails the gate.
    """
    errors: list[str] = []
    notes: list[str] = []
    for name in (figures if figures is not None else [f[0] for f in FIGURES]):
        # a registered name is a record-figure PREFIX: fig_sharded emits
        # sharded_apply + sharded_bfs, each with its own BENCH file
        paths = sorted(root.glob(f"BENCH_{name}.json")) \
            + sorted(root.glob(f"BENCH_{name}_*.json"))
        if not paths:
            notes.append(f"no committed BENCH_{name}*.json yet "
                         f"(fresh clone / new figure) — a full --json run "
                         f"will create it")
            continue
        for path in paths:
            fig = path.stem[len("BENCH_"):]
            try:
                with open(path, encoding="utf-8") as f:
                    rows = json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                errors.append(f"{path.name}: unreadable ({e})")
                continue
            if not isinstance(rows, list) or not rows:
                errors.append(f"{path.name}: expected a non-empty record "
                              f"list, got {type(rows).__name__}")
                continue
            errors += [f"{path.name}: {e}"
                       for e in validate_records(rows, [fig])]
    return errors, notes


def preflight(root: pathlib.Path = ROOT) -> list[str]:
    """--smoke import-and-registry preflight (DESIGN.md §15): every
    registered figure module must exist under benchmarks/, import
    cleanly, and expose the ``main`` entry the driver is about to call —
    so a broken import or a FIGURES typo fails the gate in milliseconds
    instead of mid-sweep. Built on repro.analysis.modwalk, the analysis
    framework's module-walking helper."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.analysis.modwalk import iter_package_modules, preflight_imports

    on_disk = {name for name, _ in
               iter_package_modules(root / "benchmarks", "benchmarks")}
    registered = [f"benchmarks.{module}" for _, module, _ in FIGURES]
    errors = [f"{mod}: registered in FIGURES but no such module under "
              f"benchmarks/" for mod in registered if mod not in on_disk]
    errors += preflight_imports([m for m in registered if m in on_disk],
                                require_attr="main")
    return errors


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write sweep rows as uniform JSON records")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: quick sweeps only, assert every figure "
                         "emits schema-valid JSON")
    args = ap.parse_args()
    quick = args.quick or args.smoke
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.smoke:
        failures = preflight()
        if failures:
            print("\n".join(failures), file=sys.stderr)
            sys.exit(1)
        print(f"preflight: {len(FIGURES)} registered figure modules "
              f"import cleanly and expose main()")

    csv: list[str] = []
    json_records: list[dict] = []

    import importlib

    for _name, module, banner in FIGURES:
        print("=" * 72)
        print(banner)
        print("=" * 72)
        mod = importlib.import_module(f"benchmarks.{module}")
        csv += mod.main(quick=quick, rows_out=json_records)
        print()

    if not args.smoke:
        print("=" * 72)
        print("BFS kernel — structural intensity + jnp-path wall time")
        print("=" * 72)
        from benchmarks import kernel_bench
        csv += kernel_bench.main(quick=quick)

        print("\n" + "=" * 72)
        print("Roofline — per (arch x shape), single-pod 256 chips "
              "(see EXPERIMENTS.md)")
        print("=" * 72)
        from benchmarks import roofline
        rows = roofline.build_table()
        print(roofline.format_table(rows))
        # roofline rides the same long-format record stream (and hence the
        # committed BENCH_roofline.json on full --json runs, DESIGN.md §14)
        json_records += roofline.records(rows)
        for r in rows:
            if not r.get("skipped"):
                csv.append(f'roofline/{r["arch"]}/{r["shape"]},'
                           f'{r["compute_s"]*1e6:.1f},'
                           f'dominant={r["dominant"]};frac={r["roofline_fraction"]:.3f}')

        print("\n" + "=" * 72)
        print("CSV (name,us_per_call,derived)")
        print("=" * 72)
        for line in csv:
            print(line)

    if args.smoke or (args.json and not quick):
        # one schema gate guards both the CI smoke check and the committed
        # longitudinal BENCH records a full --json run is about to write
        errors = validate_records(json_records, [f[0] for f in FIGURES])
        if errors:
            print("\n".join(errors), file=sys.stderr)
            sys.exit(1)
        print(f"{len(json_records)} records from {len(FIGURES)} figures "
              f"— schema valid")
        # committed-record audit: schema-check the BENCH files that exist;
        # a missing record (fresh clone / newly registered figure) is only
        # a note — quick/smoke must never abort on it
        cerrors, notes = check_committed_records()
        for note in notes:
            print(f"note: {note}")
        if cerrors:
            print("\n".join(cerrors), file=sys.stderr)
            sys.exit(1)

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(json_records, f, indent=1)
        print(f"\nwrote {len(json_records)} sweep records to {args.json}")
        if quick:
            # quick/smoke numbers are not comparable run-to-run: never let
            # them clobber the committed longitudinal BENCH records
            print("quick/smoke run: BENCH_<figure>.json records not updated")
        else:
            for path in write_bench_files(json_records):
                print(f"wrote {path}")


if __name__ == "__main__":
    main()
