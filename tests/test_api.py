"""Unified engine API: config validation, lifecycle, WriteBatch, Iterator.

The byte-identity of `repro.api.Engine` against the legacy front-ends is the
differential oracle's job (tests/test_differential.py, tests/test_exec.py);
this module covers the *new* surface itself: the declarative config tree's
error contract (`ConfigError` with actionable messages), engine lifecycle
(`close`/context manager/`ClosedError`), buffered write batches, the lazy
RocksDB-style iterator (including its edge cases: empty store, seek past the
max key, iteration across a shard boundary with a migration in flight), and
the namespaced stats/device-time surface.
"""
import itertools

import pytest

import repro.api as api
from repro.core import ParallaxStore, RangeShardedStore, ShardedStore, StoreConfig
from repro.core.ycsb import Workload, make_key, payload


def small_config(**kw) -> StoreConfig:
    defaults = dict(l0_capacity=1 << 12, cache_bytes=1 << 15,
                    segment_bytes=1 << 14, chunk_bytes=1 << 11)
    defaults.update(kw)
    return StoreConfig(**defaults)


ALL_COMBOS = [(p, e) for p in ("none", "hash:3", "range:3") for e in ("serial", "async")]


def open_engine(partitioning="none", execution="serial", **kw) -> api.Engine:
    return api.open(api.EngineConfig(store=small_config(**kw.pop("store_kw", {})),
                                     partitioning=partitioning, execution=execution, **kw))


# ------------------------------------------------------------- config errors
@pytest.mark.parametrize("bad,frag", [
    (dict(partitioning="hash:-2"), "positive shard count"),
    (dict(partitioning="range:0"), "positive shard count"),
    (dict(partitioning="zebra:3"), "unknown partitioning"),
    (dict(partitioning="hash"), "missing its shard count"),
    (dict(partitioning="hash:four"), "non-integer shard count"),
    (dict(execution="warp"), "unknown execution mode"),
    (dict(execution=api.ExecutionConfig(mode="serial", pace=0.5)), "requires mode 'async'"),
    (dict(execution=api.ExecutionConfig(workers=0)), "workers must be >= 1"),
    (dict(execution=api.ExecutionConfig(overlap="channels:0")), "overlap"),
    (dict(execution=api.ExecutionConfig(overlap="warp")), "overlap"),
    (dict(execution="async", batch_size=0), "batch_size >= 1"),
    (dict(gc_every=-1), "gc_every"),
    (dict(partitioning=api.PartitioningConfig(scheme="range", boundaries=(b"a",))), "b''"),
    (dict(partitioning=api.PartitioningConfig(scheme="range", boundaries=(b"", b"b", b"b"))),
     "strictly increasing"),
    (dict(partitioning=api.PartitioningConfig(scheme="hash", shards=2, boundaries=(b"",))),
     "only apply to range"),
    (dict(partitioning=api.PartitioningConfig(scheme="none", shards=3)), "single store"),
    (dict(partitioning=api.PartitioningConfig(scheme="range", shards=2, migration_batch_keys=0)),
     "migration_batch_keys"),
])
def test_config_errors_are_actionable(bad, frag):
    with pytest.raises(api.ConfigError) as err:
        api.open(api.EngineConfig(store=small_config(), **bad))
    assert frag in str(err.value), str(err.value)


def test_config_error_is_engine_error_and_value_error():
    assert issubclass(api.ConfigError, api.EngineError)
    assert issubclass(api.ConfigError, ValueError)
    assert issubclass(api.ClosedError, api.EngineError)


def test_shorthand_strings_coerce_and_tag():
    cfg = api.EngineConfig(partitioning="hash:4", execution="async")
    assert isinstance(cfg.partitioning, api.PartitioningConfig)
    assert isinstance(cfg.execution, api.ExecutionConfig)
    assert cfg.tag() == "hash4+async4"
    assert api.EngineConfig().tag() == "none+serial"
    assert api.EngineConfig(partitioning="range:8").tag() == "range8+serial"
    bounded = api.PartitioningConfig.range_for_keys([make_key(i) for i in range(100)], 4)
    assert bounded.scheme == "range" and len(bounded.boundaries) == 4
    assert api.EngineConfig(partitioning=bounded).tag() == "range4+serial"


def test_open_builds_the_right_backend():
    with open_engine("none", "serial") as db:
        assert isinstance(db.store, ParallaxStore)
    with open_engine("none", "async") as db:  # 1-shard hash wrapper (see docs)
        assert isinstance(db.store, ShardedStore) and db.store.num_shards == 1
    with open_engine("hash:3", "serial") as db:
        assert isinstance(db.store, ShardedStore) and db.store.num_shards == 3
    with open_engine("range:3", "async") as db:
        assert isinstance(db.store, RangeShardedStore) and db.store.num_shards == 3


# --------------------------------------------------------------- lifecycle
@pytest.mark.parametrize("partitioning,execution", ALL_COMBOS)
def test_lifecycle_and_closed_error(partitioning, execution):
    db = open_engine(partitioning, execution)
    db.put(make_key(1), payload(104))
    assert db.get(make_key(1)) == payload(104)
    db.close()
    db.close()  # idempotent
    assert db.closed
    for fn in (lambda: db.put(b"k", b"v"), lambda: db.get(b"k"),
               lambda: db.delete(b"k"), lambda: db.scan(b"", 1),
               lambda: db.iterator(), lambda: db.write_batch(),
               lambda: db.crash(), lambda: api.execute(db, iter([]))):
        with pytest.raises(api.ClosedError):
            fn()
    # stats stay readable after close (post-run reporting)
    assert db.stats()["engine"]["closed"] is True
    assert db.stats()["store"]["inserts"] == 1


def test_crash_recover_round_trip():
    with open_engine("range:3", "async") as db:
        api.execute(db, Workload("load_a", "SD", num_keys=300, num_ops=0, seed=5).load_ops())
        db.flush_all()
        db.crash()
        db.recover()
        got = [db.get(make_key(i)) for i in range(300)]
        assert all(v is not None for v in got)


# -------------------------------------------------------------- write batch
@pytest.mark.parametrize("partitioning,execution", ALL_COMBOS)
def test_write_batch_matches_singles(partitioning, execution):
    with open_engine(partitioning, execution) as batched, \
         open_engine(partitioning, execution) as singles:
        wb = batched.write_batch()
        for i in range(50):
            wb.put(make_key(i), payload(104))
        wb.update(make_key(10), payload(9)).delete(make_key(20))
        assert len(wb) == 52
        batched.write(wb)
        assert len(wb) == 0  # committed batches clear
        for i in range(50):
            singles.put(make_key(i), payload(104))
        singles.update(make_key(10), payload(9))
        singles.delete(make_key(20))
        probe = [make_key(i) for i in range(55)]
        assert [batched.get(k) for k in probe] == [singles.get(k) for k in probe]
        assert batched.get(make_key(10)) == payload(9)
        assert batched.get(make_key(20)) is None


def test_write_batch_context_manager_commits_on_clean_exit_only():
    with open_engine("hash:2", "serial") as db:
        with db.write_batch() as wb:
            wb.put(make_key(1), b"v" * 30)
        assert db.get(make_key(1)) == b"v" * 30
        with pytest.raises(RuntimeError, match="boom"):
            with db.write_batch() as wb:
                wb.put(make_key(2), b"x" * 30)
                raise RuntimeError("boom")
        assert db.get(make_key(2)) is None  # discarded, not applied
        assert len(wb) == 0  # ...and emptied: reusing the batch can't replay it
        with wb:
            wb.put(make_key(3), b"y" * 30)
        assert db.get(make_key(3)) == b"y" * 30
        assert db.get(make_key(2)) is None


# ----------------------------------------------------------------- iterator
def load_keys(db, n, size=104):
    with db.write_batch() as wb:
        for i in range(n):
            wb.put(make_key(i), payload(size))


@pytest.mark.parametrize("partitioning,execution", ALL_COMBOS)
def test_iterator_matches_eager_scan(partitioning, execution):
    with open_engine(partitioning, execution) as db:
        load_keys(db, 300)
        it = db.iterator()
        rows = list(it)
        assert rows == db.scan(b"", 400)
        assert len(rows) == 300
        # mid-keyspace seek, manual cursor protocol
        it.seek(make_key(250))
        got = []
        while it.valid():
            got.append((it.key(), it.value()))
            it.next()
        assert got == db.scan(make_key(250), 100)


def test_iterator_empty_store():
    for part in ("none", "hash:3", "range:3"):
        with open_engine(part) as db:
            it = db.iterator()
            assert not it.valid()
            assert list(it) == []
            with pytest.raises(api.EngineError, match="not positioned"):
                it.key()
            with pytest.raises(api.EngineError, match="not positioned"):
                it.next()


def test_iterator_seek_past_max_key():
    for part in ("none", "hash:3", "range:3"):
        with open_engine(part) as db:
            load_keys(db, 100)
            it = db.iterator(make_key(100))  # first absent key
            assert not it.valid()
            it.seek(b"\xff" * 24)  # past every representable key
            assert not it.valid()
            with pytest.raises(api.EngineError):
                it.value()
            # re-seek recovers the cursor
            it.seek(make_key(99))
            assert it.valid() and it.key() == make_key(99)


def test_iterator_is_lazy_on_hash_backend():
    """Pulling k rows must not pay the eager path's count-per-shard reads."""
    with open_engine("hash:4", store_kw=dict(cache_bytes=0)) as lazy, \
         open_engine("hash:4", store_kw=dict(cache_bytes=0)) as eager:
        load_keys(lazy, 400)
        load_keys(eager, 400)
        before = lazy.stats()["device"]["bytes_read"]
        it = lazy.iterator()
        first = list(itertools.islice(iter(it), 10))
        lazy_read = lazy.stats()["device"]["bytes_read"] - before
        before = eager.stats()["device"]["bytes_read"]
        assert eager.scan(b"", 10) == first
        eager_read = eager.stats()["device"]["bytes_read"] - before
        assert lazy_read < eager_read, (lazy_read, eager_read)


def test_iterator_across_shard_boundary_mid_migration():
    """A split's migration left in flight: the cursor must cross the moving
    boundary and agree with the eager scan's double-routed merged view."""
    nk = 400
    keys = [make_key(i) for i in range(nk)]
    cfg = api.EngineConfig(
        store=small_config(),
        partitioning=api.PartitioningConfig.range_for_keys(
            keys, 3, auto_rebalance=False, migration_batch_keys=4),
    )
    with api.open(cfg) as db:
        load_keys(db, nk)
        # delete a stripe so tombstone suppression is exercised across the move
        with db.write_batch() as wb:
            for i in range(150, 250, 3):
                wb.delete(make_key(i))
        store = db.store
        store.flush_all()
        hot = max(range(store.num_shards),
                  key=lambda i: len(store.shards[i].live_keys_in(*store.bounds(i))))
        assert store._split(hot, background=True)
        db.migration_tick()  # move a few keys; leave the migration pending
        assert store.migration is not None
        full = db.scan(b"", nk + 50)
        assert list(db.iterator()) == full
        # start inside the migrating range, cross the new boundary
        lo = store.migration.lo
        assert list(db.iterator(lo)) == db.scan(lo, nk)
        assert store.migration is not None  # iteration never ticks the policy
        store.drain_migration()
        assert list(db.iterator()) == full  # drained world agrees too


# -------------------------------------------------------------------- stats
def test_stats_namespaces_by_backend():
    with open_engine("none") as db:
        db.put(make_key(1), payload(104))
        s = db.stats()
        assert set(s) == {"engine", "store", "device"}
        assert s["store"]["inserts"] == 1
    with open_engine("hash:2") as db:
        db.put(make_key(1), payload(104))
        assert db.get(make_key(1)) == payload(104)
        s = db.stats()
        assert set(s) == {"engine", "store", "device", "frontend"}
        assert s["engine"]["num_shards"] == 2
        assert s["frontend"]["gets"] == 1
    with open_engine("range:2") as db:
        load_keys(db, 100)
        s = db.stats()
        assert set(s) == {"engine", "store", "device", "frontend", "topology"}
        assert s["topology"]["meta_records"] >= 1
        assert len(s["topology"]["boundaries"]) == 2


def test_device_time_uses_config_overlap_policy():
    cfg = api.EngineConfig(
        store=small_config(), partitioning="hash:4",
        execution=api.ExecutionConfig(mode="serial", overlap="serial"),
    )
    with api.open(cfg) as db:
        load_keys(db, 300)
        per_shard = db.store.device_times()
        assert db.device_time() == pytest.approx(sum(per_shard))       # config default
        assert db.device_time("ideal") == pytest.approx(max(per_shard))


def test_execute_rejects_raw_stores():
    with pytest.raises(TypeError, match="drives an Engine"):
        api.execute(ParallaxStore(small_config()), iter([]))


# -------------------------------------------------------------- compile cache
@pytest.mark.parametrize("preset", [None, "elsewhere"])
def test_open_keeps_compile_cache_in_checkout_unless_configured(preset, tmp_path):
    import jax

    from repro.kernels import CACHE_DIR

    was = jax.config.jax_compilation_cache_dir
    want = str(tmp_path / preset) if preset else str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / preset) if preset else None)
    try:
        api.open().close()
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert CACHE_DIR.name == ".jax_cache" and (CACHE_DIR.parent / "src" / "repro").is_dir()
