"""The benchmark's one command:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip this process finds and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, traced, a
``breakdown``; the numbers compared for ``correct`` come last, under
``checks``, and again as the last lines of standard error. Without a TPU,
or with fewer chips than the cell asks for, it exits with code 3 and prints
no result. JAX's compilation cache is ``.jax_cache/`` in this checkout;
glibc keeps freed memory in the heap (``bench/hostmem.py``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve()
        != ROOT / "bench"]
    from bench.hostmem import keep_freed_memory

    heap = keep_freed_memory()
    from bench.harness import NoChip, log, run_cell, use_checkout_cache

    log(f"host allocator: freed memory kept in the heap: {heap}")
    use_checkout_cache(ROOT)

    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
