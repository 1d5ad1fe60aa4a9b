#!/usr/bin/env python3
"""Smoke test of the store on one TPU, through ``repro.api`` only.

Phase 1 loads a bare Parallax engine (YCSB load A, SD value mix, default
``StoreConfig`` with 10-bit bloom filters) and runs YCSB-B ops against it.
Phase 2 loads a 4-shard hash engine with async execution, whose executor
threads drive the device merge concurrently, then runs a YCSB-A update phase
and a batch of deletes.  Every get in the op streams, one 1,000-row scan and
one scan of the whole store per phase are checked against a plain ``dict``
built from the same ops, and every level's device key column is decoded and
checked against the level's keys.

Scale: ``--keys`` (default 1,000,000, about 250 MB of logical KV data) is cut
from the paper's 100M keys because each index entry is still a Python object
on the host; only the compaction merge and each level's key column live on
the device.

Run from the repository root::

    python3 chip_smoke.py [--keys N]

It exits non-zero, without the result line, when JAX finds no TPU or any
check fails.  The last line of its output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

PAPER_KEYS = 100_000_000
SEED = 7


def _log(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _reference(ops, ref: dict) -> list[bytes]:
    """Apply write ops to ``ref``; return the keys the stream reads."""
    from repro.core.ycsb import payload

    reads = []
    for op in ops:
        if op.kind in ("insert", "update"):
            ref[op.key] = payload(op.value_size)
        elif op.kind == "read":
            reads.append(op.key)
    return reads


def _check_answers(eng, ref: dict, reads: list[bytes], scan_start: bytes) -> int:
    """Compare every read key, one 1,000-row scan and a full scan with ``ref``."""
    for k in reads:
        _check(eng.get(k) == ref.get(k), f"get({k!r}) disagrees with the reference")
    keys = sorted(ref)
    i = bisect.bisect_left(keys, scan_start)
    want = [(k, ref[k]) for k in keys[i : i + 1000]]
    got = eng.scan(scan_start, 1000)
    _check(len(want) == 1000, "scan window shorter than 1,000 rows")
    _check(got == want, f"scan({scan_start!r}, 1000) disagrees with the reference")
    _check(eng.scan(b"", len(ref) + 1) == [(k, ref[k]) for k in keys],
           "the full scan disagrees with the reference")
    return len(reads)


def _check_key_columns(stores) -> int:
    """Decode every level's device key column and compare it with the level's
    keys and tombstones; return the entries checked."""
    from repro.kernels.merge_runs.ops import unpack_keys

    checked = 0
    for s in stores:
        for lvl in s.levels:
            if not lvl.entries:
                _check(lvl.key_column is None, f"empty L{lvl.index} keeps a key column")
                continue
            want = [(e.key, e.tombstone) for e in lvl.entries]
            _check(unpack_keys(lvl.key_column, len(want)) == want,
                   f"L{lvl.index}'s device key column disagrees with its keys")
            checked += len(want)
    return checked


def _device_report(stores) -> dict:
    """Where the merged levels' key columns live, and the merge's compile count."""
    import jax

    from repro.kernels.merge_runs.ops import compiled_merge_programs

    platforms = {
        d.platform
        for s in stores for lvl in s.levels if lvl.key_column is not None
        for d in lvl.key_column.devices()
    }
    stats = jax.devices()[0].memory_stats() or {}
    return {
        "merge_output_platforms": ",".join(sorted(platforms)) or "none",
        "merge_programs_compiled": compiled_merge_programs(),
        "peak_device_bytes": stats.get("peak_bytes_in_use", "not reported"),
    }


def phase_bare(keys: int, run_ops: int) -> dict:
    """Load a bare engine, run YCSB-B, check gets and a scan."""
    import repro.api as api
    from repro.core import StoreConfig
    from repro.core.ycsb import Workload, make_key

    ref: dict = {}
    load = list(Workload("load_a", "SD", num_keys=keys, num_ops=0, seed=SEED).load_ops())
    _reference(load, ref)
    run = list(Workload("run_b", "SD", num_keys=keys, num_ops=run_ops, seed=SEED).run_ops())
    with api.open(api.EngineConfig(store=StoreConfig(bloom_bits_per_key=10))) as eng:
        t0 = time.perf_counter()
        api.execute(eng, load)
        t1 = time.perf_counter()
        api.execute(eng, run)
        t2 = time.perf_counter()
        reads = _reference(run, ref)
        checked = _check_answers(eng, ref, reads + [make_key(keys + 1)], make_key(keys // 3))
        columns = _check_key_columns([eng.store])
        t3 = time.perf_counter()
        st = eng.stats()["store"]
        report = _device_report([eng.store])
    _check(st["compactions"] > 0, "the load ran no compaction")
    return dict(phase="bare", keys_loaded=keys, compactions=st["compactions"],
                entries_merged=st["entries_merged"], load_s=t1 - t0,
                run_b_s=t2 - t1, check_s=t3 - t2, gets_checked=checked,
                column_entries_checked=columns, **report)


def phase_hash_async(keys: int, run_ops: int, deletes: int) -> dict:
    """Load a hash:4 async engine, delete, update, check gets and a scan."""
    import repro.api as api
    from repro.core import StoreConfig
    from repro.core.ycsb import Workload, make_key

    ref: dict = {}
    load = list(Workload("load_a", "SD", num_keys=keys, num_ops=0, seed=SEED).load_ops())
    _reference(load, ref)
    run = list(Workload("run_a", "SD", num_keys=keys, num_ops=run_ops, seed=SEED).run_ops())
    doomed = [make_key(i) for i in range(0, keys, max(1, keys // deletes))][:deletes]
    cfg = api.EngineConfig(store=StoreConfig(bloom_bits_per_key=10),
                           partitioning="hash:4", execution="async")
    with api.open(cfg) as eng:
        t0 = time.perf_counter()
        api.execute(eng, load)
        t1 = time.perf_counter()
        for k in doomed:
            eng.delete(k)
        api.execute(eng, run)   # its flushes carry the tombstones into the levels
        t2 = time.perf_counter()
        for k in doomed:
            del ref[k]
        reads = _reference(run, ref)
        checked = _check_answers(eng, ref, reads + doomed, make_key(keys // 2))
        columns = _check_key_columns(eng.store.shards)
        t3 = time.perf_counter()
        st = eng.stats()["store"]
        report = _device_report(eng.store.shards)
    _check(st["compactions"] > 0, "the load ran no compaction")
    return dict(phase="hash4_async", keys_loaded=keys, compactions=st["compactions"],
                entries_merged=st["entries_merged"], deletes=len(doomed), load_s=t1 - t0,
                update_delete_s=t2 - t1, check_s=t3 - t2, gets_checked=checked,
                column_entries_checked=columns, **report)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--keys", type=int, default=1_000_000,
                        help="keys loaded in phase 1 (default 1,000,000)")
    args = parser.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    _log(device=dev.platform, kind=repr(dev.device_kind), count=len(jax.devices()))
    _log(scale=f"{args.keys}_of_{PAPER_KEYS}_paper_keys",
         cut="index entries are host Python objects (ROADMAP Reach 1)")
    for phase in (lambda: phase_bare(args.keys, run_ops=20_000),
                  lambda: phase_hash_async(50_000, run_ops=20_000, deletes=2_000)):
        r = phase()
        _log(**r)
        _check(r["merge_output_platforms"] == "tpu",
               f"merged key columns live on {r['merge_output_platforms']}, not the TPU")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
