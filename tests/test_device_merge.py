"""Differential tests: the device compaction merge against ``lsm.merge_runs``.

``merge_on_device`` must return the same ``merged`` and ``dead`` lists as the
plain reference, entry for entry (the same objects, in the same order), and
hand back a key column that decodes to the merged run's keys.  Keys are
variable-length bytes, including prefix-equal pairs (``b"ab"`` /
``b"ab\\x00"``) and lengths on both sides of a word-bucket boundary.
"""
from __future__ import annotations

import pytest

from repro.core.lsm import IndexEntry, Level, merge_on_device, merge_runs, pack_column
from repro.kernels.merge_runs.ops import MAX_KEY_BYTES, pack_keys, unpack_keys

hypothesis = pytest.importorskip("hypothesis")
st = hypothesis.strategies
given, settings = hypothesis.given, hypothesis.settings

# a tiny alphabet makes shared prefixes, zero bytes and 0xff words common
_KEYS = st.binary(max_size=13).map(lambda b: bytes(c % 5 * 63 for c in b))


def _run(draw, lsn0: int) -> list[IndexEntry]:
    keys = sorted(draw(st.sets(_KEYS, max_size=40)))
    tombs = draw(st.lists(st.booleans(), min_size=len(keys), max_size=len(keys)))
    return [IndexEntry(key=k, lsn=lsn0 + i, category=0, tombstone=t)
            for i, (k, t) in enumerate(zip(keys, tombs))]


@st.composite
def _runs(draw, count: int):
    return [_run(draw, 1000 * (count - i)) for i in range(count)]


def _device(newer, older, older_column, drop):
    return merge_on_device(newer, pack_column(newer), older, older_column,
                           drop_tombstones=drop)


@settings(max_examples=60, deadline=None)
@given(runs=_runs(2), drop=st.booleans())
def test_device_merge_matches_reference(runs, drop):
    newer, older = runs
    ref_merged, ref_dead = merge_runs(newer, older, drop_tombstones=drop)
    older_column = pack_column(older) if older else None
    merged, dead, column = _device(newer, older, older_column, drop)
    assert [id(e) for e in merged] == [id(e) for e in ref_merged]
    assert [id(e) for e in dead] == [id(e) for e in ref_dead]
    assert unpack_keys(column, len(merged)) == [(e.key, e.tombstone) for e in merged]


@settings(max_examples=30, deadline=None)
@given(runs=_runs(3), drops=st.tuples(st.booleans(), st.booleans()))
def test_device_merge_chains_columns(runs, drops):
    """A merged column feeds the next merge, as a level's column does."""
    newest, middle, oldest = runs
    ref, _ = merge_runs(middle, oldest, drop_tombstones=drops[0])
    ref, ref_dead = merge_runs(newest, ref, drop_tombstones=drops[1])
    mid, _, mid_column = _device(middle, oldest, pack_column(oldest) if oldest else None, drops[0])
    merged, dead, column = _device(newest, mid, mid_column if mid else None, drops[1])
    assert [id(e) for e in merged] == [id(e) for e in ref]
    assert [id(e) for e in dead] == [id(e) for e in ref_dead]
    assert unpack_keys(column, len(merged)) == [(e.key, e.tombstone) for e in merged]


@pytest.mark.parametrize("pair", [
    (b"ab", b"ab\x00"),
    (b"", b"\x00"),
    (b"abcd", b"abcd\x00"),            # 1 word vs 2 words
    (b"abcdefgh", b"abcdefgh\x00"),    # 2 words vs bucket of 4
    (b"\xff\xff\xff\xff", b"\xff\xff\xff\xff\x00"),
])
def test_device_merge_prefix_pairs(pair):
    short, long_ = (IndexEntry(key=k, lsn=1, category=0) for k in pair)
    for newer, older in (([short], [long_]), ([long_], [short])):
        merged, dead, column = _device(newer, older, pack_column(older), False)
        assert [e.key for e in merged] == list(pair) and dead == []
        assert unpack_keys(column, 2) == [(k, False) for k in pair]


def test_pack_rejects_oversized_key():
    with pytest.raises(ValueError, match="exceeds"):
        pack_keys([b"k" * (MAX_KEY_BYTES + 1)], [False])


def _entries(keys, tombstone=False):
    return [IndexEntry(key=k, lsn=i, category=0, tombstone=tombstone) for i, k in enumerate(keys)]


def test_rebuild_repacks_a_run_given_no_column():
    lvl = Level(1)
    run = _entries([b"a", b"b\x00", b"small-key-0001"], tombstone=True)
    lvl.rebuild(run, None)
    assert unpack_keys(lvl.key_column, 3) == [(e.key, True) for e in run]
    lvl.clear()
    assert lvl.key_column is None


@pytest.mark.parametrize("side", ["newer", "older"])
def test_merge_rejects_column_narrower_than_run(side):
    short, full = _entries([b"k%04d" % i for i in range(2000)]), _entries([b"x"])
    runs = {"newer": (short, full), "older": (full, short)}[side]
    cols = [pack_column(run[:1000]) if run is short else pack_column(run) for run in runs]
    with pytest.raises(ValueError, match="cannot hold"):
        merge_on_device(runs[0], cols[0], runs[1], cols[1], drop_tombstones=False)


def test_unpack_rejects_entries_past_the_count():
    col = pack_keys([b"a", b"b"], [False, False])
    assert unpack_keys(col, 2) == [(b"a", False), (b"b", False)]
    with pytest.raises(ValueError, match="past its 1"):
        unpack_keys(col, 1)
