"""The control and the planted faults that ``correct`` must catch.

    python bench/controls.py --workload <cell> --fault <name> --seconds <s> --seeds 1 2 3

runs the cell once per seed with the fault switched on from the window's
opening to the end of the check, in one process, and prints one JSON line
per run with ``correct`` and the numbers compared. The benchmark's own runs
never switch one on. Faults:

``control``          the durability guarantee broken: WAL appends are written
                     and flushed but not fsynced, so acks run ahead of what a
                     power cut keeps (what a group commit done wrong does);
``state_unchanged``  every fused apply returns its input state (with the
                     result codes the real apply gave);
``half_batch``       every other lane of each fused apply is dropped;
``code_altered``     the first lane's result code of each apply is altered;
``path_altered``     the first answer of each ``get_paths`` call is altered.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _patch_apply(wrap):
    import repro.runtime.ingest as ingest

    real = ingest.apply_ops_fast
    ingest.apply_ops_fast = wrap(real)

    def undo():
        ingest.apply_ops_fast = real
    return undo


def control(srv):
    wal = srv.pool.wal

    def append(record):
        t0 = time.perf_counter()
        frame = wal._frame(record.to_payload())
        wal._f.write(frame)
        wal._f.flush()
        wal._n_records += 1
        wal.stats.records += 1
        wal.stats.bytes += len(frame)
        wal.stats.append_s += time.perf_counter() - t0

    wal.append = append

    def undo():
        del wal.append
    return undo


def state_unchanged(srv):
    return _patch_apply(lambda real: lambda state, batch:
                        (state, real(state, batch)[1]))


def half_batch(srv):
    import jax.numpy as jnp

    def wrap(real):
        def apply(state, batch):
            keep = jnp.arange(batch.opcode.shape[0]) % 2 == 0
            return real(state, batch._replace(
                opcode=jnp.where(keep, batch.opcode, 0)))
        return apply
    return _patch_apply(wrap)


def code_altered(srv):
    import jax.numpy as jnp

    def wrap(real):
        def apply(state, batch):
            new, res = real(state, batch)
            # FALSE <-> TRUE-like flip; never TABLE FULL, which would make
            # the pool grow the table and replay, forever
            return new, res.at[0].set(jnp.where(res[0] == 0, 1, 0))
        return apply
    return _patch_apply(wrap)


def path_altered(srv):
    real = srv.get_paths

    def get_paths(pairs, **kw):
        out, rounds = real(pairs, **kw)
        found, keys = out[0]
        out[0] = (found, keys[:-1]) if found and len(keys) > 1 \
            else (not found, [])
        return out, rounds

    srv.get_paths = get_paths

    def undo():
        del srv.get_paths
    return undo


FAULTS = {f.__name__: f for f in (control, state_unchanged, half_batch,
                                  code_altered, path_altered)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.hostmem import keep_freed_memory

    keep_freed_memory()
    from bench.harness import run_cell, use_checkout_cache

    use_checkout_cache(ROOT)

    for seed in args.seeds:
        out = run_cell(ROOT, args.workload, seed, args.seconds, False,
                       on_window=FAULTS[args.fault])
        print(json.dumps({"fault": args.fault, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
