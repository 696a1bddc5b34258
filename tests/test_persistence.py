"""Durable storage: snapshot round-trips, WAL crash recovery, layout safety.

The acceptance contract of ``repro.checkpointing``'s index persistence:

- ``save_index``/``load_index`` round-trip a sharded index *bit-identically*
  — counts and row ids against the live index and brute force, across shard
  count x summary policy x staged-overlay state x mixed bounds epochs.
- A crash at any injected drain point (pre journal append, post-append
  pre-swap, post-swap pre-truncate) recovers via ``QueryEngine.recover`` —
  last committed snapshot + journal replay — to exactly the acknowledged
  state: no acknowledged write lost, no record double-applied, and an
  uncommitted partial snapshot directory is never loaded.
- The binary section container refuses corruption (truncation, version
  bumps, flipped payload bytes) with ``CorruptSnapshotError`` instead of
  constructing arrays from garbage; arbitrary dtypes/shapes round-trip
  byte-exactly (the hypothesis twin lives in
  ``tests/test_persistence_property.py``).
- ``checkpointing.save_checkpoint`` publishes its ``COMMITTED`` sentinel
  only after every payload file is fsynced, via fsync-then-atomic-rename.

Crash simulation note: the writer's in-memory rollback never touches disk,
so raising from an injected hook and then recovering *from disk alone*
(fresh objects, nothing reused) faithfully models a kill -9 at that point.
"""
import struct

import numpy as np
import pytest

import repro.runtime.writer as writer_mod
from repro.checkpointing.checkpoint import restore_checkpoint, save_checkpoint
from repro.checkpointing.layout import (CorruptSnapshotError, pack_sections,
                                        read_section_file, unpack_sections,
                                        write_section_file)
import repro.checkpointing.snapshot as snap_mod
from repro.checkpointing.snapshot import (delta_chain, disk_usage,
                                          latest_delta_seq, latest_epoch,
                                          load_index, recover_index,
                                          save_delta, save_index)
from repro.checkpointing.wal import Journal
from repro.core import bitmap as bm
from repro.core.partition import ShardedHippoIndex
from repro.core.predicate import Predicate
from repro.runtime.engine import QueryEngine
from repro.runtime.writer import MaintenanceWriter
from repro.storage.table import PagedTable

pytestmark = pytest.mark.persist


def make_sidx(values, num_shards=4, page_card=8, resolution=32, density=0.25,
              spare_pages=256, **kw):
    table = PagedTable.from_values(np.asarray(values).copy(),
                                   page_card=page_card,
                                   spare_pages=spare_pages)
    return ShardedHippoIndex.create(table, num_shards=num_shards,
                                    resolution=resolution, density=density,
                                    **kw)


def preds():
    """Empty, point, narrow, drifted-region, spanning, and full-table."""
    return [
        Predicate(lo=5.0, hi=1.0),
        Predicate.equality(50.0),
        Predicate.between(20.0, 24.0),
        Predicate.between(108.0, 114.0),
        Predicate.between(80.0, 125.0),
        Predicate.between(-1e30, 1e30),
    ]


def value_brute(values, ps) -> np.ndarray:
    """Counts straight off the acknowledged value multiset — independent of
    the table/staging split, so it checks recovered engines in any drain
    state."""
    v = np.asarray(values, np.float32)
    return np.asarray([((v >= p.lo) & (v <= p.hi)).sum() for p in ps],
                      np.int64)


def engine_counts_and_rows(index, writer, ps, top_k=16):
    """Counts + row ids through a compact engine over ``index``."""
    eng = QueryEngine(index, batch=8, drain_policy="manual",
                      auto_resummarize=False, top_k=top_k, writer=writer)
    tickets = [eng.submit(p) for p in ps]
    eng.drain()
    counts = np.asarray([t.count for t in tickets], np.int64)
    rows = [np.asarray(t.row_ids) for t in tickets]
    return counts, rows


# ---------------------------------------------------------------------------
# Satellite 1: save/load round-trip equivalence sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_shards", [1, 4])
@pytest.mark.parametrize("summary", ["equal_mass", "learned"])
@pytest.mark.parametrize("staged", [False, True])
def test_round_trip_counts_and_rows_bit_identical(tmp_path, num_shards,
                                                  summary, staged):
    """The full sweep: a recovered index answers every predicate with
    counts and row ids bit-identical to the live index it was saved from,
    under mixed bounds epochs and (optionally) a staged overlay."""
    rng = np.random.default_rng(7 * num_shards + staged)
    base = np.sort(rng.uniform(0, 100, 300))
    idx = make_sidx(base, num_shards=num_shards, summary=summary)
    writer = MaintenanceWriter(idx)
    drained = rng.uniform(100, 130, 48)
    for v in drained:
        writer.write(float(v))
    writer.flush()
    # mixed bounds epochs: schedule a remap of every shard but drain only
    # half the units — the snapshot must carry both the bumped and the
    # unbumped epochs plus the still-pending remap
    writer.schedule_resummarize()
    writer.drain(max_units=max(1, num_shards // 2))
    pending = rng.uniform(125, 140, 12) if staged else np.zeros(0)
    for v in pending:
        writer.write(float(v))

    live = np.concatenate([base, drained, pending]).astype(np.float32)
    ps = preds()
    want_counts, want_rows = engine_counts_and_rows(idx, writer, ps)
    np.testing.assert_array_equal(want_counts, value_brute(live, ps))

    idx.save(tmp_path)
    idx2, writer2, _ = recover_index(tmp_path, wal_sync=False)
    got_counts, got_rows = engine_counts_and_rows(idx2, writer2, ps)
    np.testing.assert_array_equal(got_counts, want_counts)
    for g, w in zip(got_rows, want_rows):
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(idx2.bounds_epochs, idx.bounds_epochs)
    if num_shards > 1:
        assert len(set(idx.bounds_epochs.tolist())) > 1, \
            "sweep lost its mixed-epoch shape (test setup rot)"
    assert idx2.summary == idx.summary
    assert (writer2.queue_depth, writer2.staged_rows) == \
        (writer.queue_depth, writer.staged_rows)
    assert writer2.pending_resummarize_shards() == \
        writer.pending_resummarize_shards()
    # the pending remap drains identically on the recovered side
    writer.flush()
    writer2.flush()
    np.testing.assert_array_equal(idx2.bounds_epochs, idx.bounds_epochs)
    g2, _ = engine_counts_and_rows(idx2, writer2, ps)
    np.testing.assert_array_equal(g2, value_brute(live, ps))


def test_writerless_load_matches_saved_counts(tmp_path):
    """``ShardedHippoIndex.load`` (no journal, no writer) round-trips a
    drained index exactly, counters and config included."""
    rng = np.random.default_rng(11)
    idx = make_sidx(np.sort(rng.uniform(0, 100, 240)))
    ps = preds()
    want = np.asarray(idx.search_batch(ps).counts)
    idx.save(tmp_path)
    idx2 = ShardedHippoIndex.load(tmp_path)
    np.testing.assert_array_equal(np.asarray(idx2.search_batch(ps).counts),
                                  want)
    assert idx2.cfg == idx.cfg
    assert idx2.counters == idx.counters
    assert idx2.nbytes() == idx.nbytes()


# ---------------------------------------------------------------------------
# Satellite 2: crash-injection recovery (snapshot + journal replay)
# ---------------------------------------------------------------------------

def _durable_engine(root, base):
    idx = make_sidx(base, num_shards=4)
    return QueryEngine(idx, batch=8, drain_policy="manual",
                       auto_resummarize=False, storage_dir=root)


def _recover(root):
    return QueryEngine.recover(root, drain_policy="manual",
                               auto_resummarize=False)


class _Boom(RuntimeError):
    pass


def test_crash_pre_append_loses_only_the_unacknowledged_write(
        tmp_path, monkeypatch):
    """A journal append that dies leaves the write unacknowledged and
    unstaged; recovery serves exactly the writes acknowledged before it."""
    rng = np.random.default_rng(0)
    base = np.sort(rng.uniform(0, 100, 200))
    root = tmp_path / "dur"
    eng = _durable_engine(root, base)
    acked = [float(v) for v in rng.uniform(100, 130, 20)]
    for v in acked:
        eng.write(v)

    def boom(self, shard, value):
        raise _Boom("torn journal append")
    monkeypatch.setattr(Journal, "append_insert", boom)
    with pytest.raises(_Boom):
        eng.write(999.0)
    assert eng.writer.queue_depth == len(acked), \
        "a failed append must stage nothing"
    monkeypatch.undo()

    del eng   # kill -9: disk is all that survives
    eng2 = _recover(root)
    eng2.flush()
    ps = preds()
    np.testing.assert_array_equal(
        eng2.run_all(ps), value_brute(np.concatenate([base, acked]), ps))


def test_crash_mid_drain_pre_swap_recovers_every_acknowledged_write(
        tmp_path, monkeypatch):
    """Dying at the swap (post-append, pre-publish) rolls nothing onto disk;
    recovery replays the journal suffix over the last committed snapshot and
    no acknowledged write is lost."""
    rng = np.random.default_rng(1)
    base = np.sort(rng.uniform(0, 100, 200))
    root = tmp_path / "dur"
    eng = _durable_engine(root, base)
    first = [float(v) for v in rng.uniform(100, 115, 16)]
    for v in first:
        eng.write(v)
    eng.flush()            # drained + snapshotted: the committed base
    second = [float(v) for v in rng.uniform(115, 130, 16)]
    for v in second:
        eng.write(v)
    eng.delete(10.0, 12.0)  # journaled delete rides the same recovery

    def boom(shards, s, st):
        raise _Boom("killed at the swap")
    monkeypatch.setattr(writer_mod, "set_shard", boom)
    with pytest.raises(_Boom):
        eng.flush()
    monkeypatch.undo()

    survivors = np.concatenate([base[(base < 10.0) | (base > 12.0)],
                                first, second])
    del eng
    eng2 = _recover(root)
    eng2.flush()
    ps = preds()
    np.testing.assert_array_equal(eng2.run_all(ps),
                                  value_brute(survivors, ps))


def test_crash_post_swap_pre_truncate_never_double_applies(
        tmp_path, monkeypatch):
    """Dying between the post-drain snapshot commit and the journal
    truncation leaves every drained record still in the journal; the
    snapshot's wal watermark must keep replay from applying them twice."""
    rng = np.random.default_rng(2)
    base = np.sort(rng.uniform(0, 100, 200))
    root = tmp_path / "dur"
    eng = _durable_engine(root, base)
    writes = [float(v) for v in rng.uniform(100, 130, 24)]
    for v in writes:
        eng.write(v)

    def boom(self):
        raise _Boom("killed before journal truncation")
    monkeypatch.setattr(Journal, "reset", boom)
    with pytest.raises(_Boom):
        eng.flush()        # drain + snapshot commit succeed, truncate dies
    monkeypatch.undo()
    assert Journal(root, 4, sync=False).replay(), \
        "setup rot: the journal should still hold the drained records"

    expected = np.concatenate([base, writes])
    del eng
    eng2 = _recover(root)
    eng2.flush()
    ps = preds()
    np.testing.assert_array_equal(eng2.run_all(ps),
                                  value_brute(expected, ps))
    full = Predicate.between(-1e30, 1e30)
    assert eng2.run_all([full])[0] == expected.size, \
        "double-applied journal records inflated the full-table count"


def test_partial_uncommitted_snapshot_is_never_loaded(tmp_path):
    """A snapshot directory without the COMMITTED sentinel — a crash
    mid-save — must be invisible to epoch listing, load, and recovery,
    whatever garbage it holds."""
    rng = np.random.default_rng(3)
    base = np.sort(rng.uniform(0, 100, 200))
    root = tmp_path / "dur"
    eng = _durable_engine(root, base)
    for v in rng.uniform(100, 120, 8):
        eng.write(float(v))
    eng.flush()
    committed = latest_epoch(root)
    ps = preds()
    want = eng.run_all(ps)

    partial = root / f"snap_{committed + 5}"
    partial.mkdir()
    (partial / "index.bin").write_bytes(b"\x00garbage, never to be read")
    assert latest_epoch(root) == committed
    del eng
    eng2 = _recover(root)
    np.testing.assert_array_equal(eng2.run_all(ps), want)


def test_fresh_dir_guard_refuses_existing_durable_state(tmp_path):
    """A new engine pointed at a directory that already holds durable state
    must refuse — adopting it silently would shadow the acknowledged
    history that only recover() replays."""
    rng = np.random.default_rng(4)
    base = np.sort(rng.uniform(0, 100, 160))
    root = tmp_path / "dur"
    eng = _durable_engine(root, base)
    eng.write(105.0)
    del eng
    with pytest.raises(ValueError, match="recover"):
        _durable_engine(root, base)


# ---------------------------------------------------------------------------
# Satellite 3 (seeded half): binary layout round-trip + corruption refusal
# ---------------------------------------------------------------------------

_DTYPES = ["float32", "float64", "int32", "int64", "uint8", "uint32", "bool"]


def _arbitrary_sections(rng, n):
    out = {}
    for i in range(n):
        dt = np.dtype(_DTYPES[int(rng.integers(len(_DTYPES)))])
        ndim = int(rng.integers(0, 4))
        shape = tuple(int(rng.integers(0, 5)) for _ in range(ndim))
        raw = rng.integers(0, 256, size=(int(np.prod(shape, dtype=np.int64))
                                         * max(dt.itemsize, 1),),
                           dtype=np.uint8)
        out[f"sec_{i}/d{dt.name}"] = raw.view(np.uint8)[
            : int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        ].copy().view(dt).reshape(shape)
    return out


def test_layout_round_trips_arbitrary_dtypes_byte_exactly(tmp_path):
    rng = np.random.default_rng(5)
    for trial in range(20):
        sections = _arbitrary_sections(rng, int(rng.integers(1, 8)))
        back = unpack_sections(pack_sections(sections), origin="test")
        assert set(back) == set(sections)
        for name, arr in sections.items():
            got = back[name]
            assert got.dtype == arr.dtype and got.shape == arr.shape
            assert got.tobytes() == arr.tobytes(), \
                f"trial {trial}: section {name} not byte-exact"
        path = tmp_path / f"t{trial}.bin"
        write_section_file(path, sections)
        back2 = read_section_file(path)
        for name, arr in sections.items():
            assert back2[name].tobytes() == arr.tobytes()


def test_layout_refuses_truncation_everywhere(tmp_path):
    rng = np.random.default_rng(6)
    data = pack_sections({"a": rng.standard_normal(64).astype(np.float32),
                          "b": rng.integers(0, 9, 33).astype(np.int64)})
    for cut in (0, 7, 32, 63, 64, len(data) // 2, len(data) - 1):
        with pytest.raises(CorruptSnapshotError):
            unpack_sections(data[:cut], origin=f"cut@{cut}")


def test_layout_refuses_version_bump_and_bad_magic(tmp_path):
    data = bytearray(pack_sections({"a": np.arange(8, dtype=np.float32)}))
    bumped = bytearray(data)
    bumped[8:12] = struct.pack("<I", 2)    # version field of the header
    with pytest.raises(CorruptSnapshotError, match="version"):
        unpack_sections(bytes(bumped), origin="version-bump")
    nomagic = bytearray(data)
    nomagic[0] ^= 0xFF
    with pytest.raises(CorruptSnapshotError):
        unpack_sections(bytes(nomagic), origin="bad-magic")


def test_layout_refuses_flipped_payload_byte(tmp_path):
    data = bytearray(pack_sections({"a": np.arange(64, dtype=np.float32)}))
    data[-5] ^= 0x40                        # deep in the last payload
    with pytest.raises(CorruptSnapshotError, match="crc|checksum|CRC"):
        unpack_sections(bytes(data), origin="bitflip")


def _encode_bitmaps_per_entry(rows):
    """Reference for the vectorized encoder: one ``rle_compress`` per entry,
    kept when shorter than the raw words."""
    flags = np.zeros((rows.shape[0],), np.uint8)
    lens = np.zeros((rows.shape[0],), np.uint32)
    chunks = [np.zeros((0,), np.uint32)]
    for i, row in enumerate(rows):
        rle = bm.rle_compress(row)
        use = rle.size < row.size
        flags[i], lens[i] = use, rle.size if use else row.size
        chunks.append(rle if use else row.astype(np.uint32))
    return flags, lens, np.concatenate(chunks)


@pytest.mark.parametrize("kind", ["random", "few_words", "one_word", "empty"])
def test_entry_bitmap_encoding_matches_per_entry_rle(kind):
    """The vectorized snapshot encoder equals per-entry RLE byte for byte,
    and decoding gives the bitmaps back."""
    rng = np.random.default_rng(11)
    n, w = (0, 13) if kind == "empty" else (257, 13)
    if kind == "random":
        rows = rng.integers(0, 2**32, (n, w), dtype=np.uint32)
    elif kind == "few_words":
        rows = rng.choice(np.asarray([0, 1, 0xFFFFFFFF], np.uint32), (n, w))
    else:
        rows = np.zeros((n, w), np.uint32)
        rows[np.arange(n), rng.integers(0, w, n)] = 5
    got = snap_mod._encode_bitmaps(rows)
    for a, b in zip(got, _encode_bitmaps_per_entry(rows)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert np.array_equal(snap_mod._decode_bitmaps(*got, w), rows)


def test_entry_bitmap_decoding_refuses_inconsistent_lengths():
    rows = np.zeros((4, 13), np.uint32)
    rows[:, 3] = 7
    flags, lens, data = snap_mod._encode_bitmaps(rows)
    assert flags.all()
    with pytest.raises(CorruptSnapshotError, match="shorter"):
        snap_mod._decode_bitmaps(flags, lens, data[:-1], 13)
    odd = lens.copy()
    odd[0] += 1                       # not whole (count, word) pairs
    with pytest.raises(CorruptSnapshotError, match="decodes to"):
        snap_mod._decode_bitmaps(flags, odd, np.concatenate([data, data]), 13)
    with pytest.raises(CorruptSnapshotError, match="decodes to"):
        snap_mod._decode_bitmaps(flags, lens, data, 12)


def test_load_index_surfaces_corruption_cleanly(tmp_path):
    """A committed snapshot whose payload rotted on disk must raise
    CorruptSnapshotError from load, not construct a wrong index."""
    rng = np.random.default_rng(8)
    idx = make_sidx(np.sort(rng.uniform(0, 100, 160)))
    snap = idx.save(tmp_path)
    f = snap / "index.bin"
    blob = bytearray(f.read_bytes())
    blob[len(blob) // 2] ^= 0x01
    f.write_bytes(bytes(blob))
    with pytest.raises(CorruptSnapshotError):
        load_index(tmp_path)


def test_disk_usage_splits_table_from_index(tmp_path):
    rng = np.random.default_rng(9)
    idx = make_sidx(np.sort(rng.uniform(0, 100, 160)))
    u = disk_usage(save_index(tmp_path, idx))
    assert u["table"] > 0 and u["index"] > 0
    assert u["table"] + u["index"] == u["total"]


# ---------------------------------------------------------------------------
# Satellite 4: checkpoint sentinel durability regression
# ---------------------------------------------------------------------------

def test_save_checkpoint_fsyncs_payload_before_sentinel(tmp_path, monkeypatch):
    """The async-writer commit protocol: every leaf and the manifest are
    fsynced strictly before the COMMITTED sentinel is published, and the
    sentinel lands via the fsync-then-atomic-rename helper (a bare touch()
    could surface after a crash with torn leaves behind it)."""
    import repro.checkpointing.checkpoint as ckpt_mod
    events = []
    real_fsync, real_commit = ckpt_mod.fsync_file, ckpt_mod.commit_sentinel
    monkeypatch.setattr(ckpt_mod, "fsync_file",
                        lambda p: (events.append(("fsync", p.name)),
                                   real_fsync(p))[1])
    monkeypatch.setattr(ckpt_mod, "commit_sentinel",
                        lambda d: (events.append(("commit", d.name)),
                                   real_commit(d))[1])
    tree = {"w": np.arange(6, dtype=np.float32),
            "b": np.zeros((2, 3), np.float32)}
    t = save_checkpoint(tmp_path, 3, tree, async_write=True)
    t.join()
    kinds = [k for k, _ in events]
    assert kinds[-1] == "commit" and kinds.count("commit") == 1
    synced = {n for k, n in events if k == "fsync"}
    assert {"leaf_0.npy", "leaf_1.npy", "manifest.json"} <= synced
    assert (tmp_path / "step_3" / "COMMITTED").exists()
    step, back = restore_checkpoint(tmp_path, treedef_like=tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(back["w"]), tree["w"])


# ---------------------------------------------------------------------------
# WAL unit coverage: framing, torn tails, watermarks
# ---------------------------------------------------------------------------

def test_journal_replay_is_exact_and_ordered(tmp_path):
    j = Journal(tmp_path, 4, sync=False)
    j.append_insert(1, 10.5)
    j.append_delete(3.0, 4.0)
    j.append_insert(0, -2.0)
    bounds = np.linspace(0.0, 1.0, 9).astype(np.float32)
    j.append_resummarize(bounds, "learned")
    recs = j.replay()
    assert [r.kind for r in recs] == [1, 2, 1, 3]
    assert [r.seqno for r in recs] == [1, 2, 3, 4]
    assert (recs[0].shard, recs[0].value) == (1, 10.5)
    assert (recs[1].lo, recs[1].hi) == (3.0, 4.0)
    assert recs[3].policy == "learned"
    np.testing.assert_array_equal(recs[3].bounds, bounds)
    assert [r.seqno for r in j.replay(after=2)] == [3, 4]


def test_journal_ignores_torn_tail_and_keeps_seqnos_monotonic(tmp_path):
    j = Journal(tmp_path, 2, sync=False)
    for i in range(5):
        j.append_insert(i % 2, float(i))
    log = tmp_path / "wal" / "shard_1.log"
    log.write_bytes(log.read_bytes()[:-3])      # torn final record
    j2 = Journal(tmp_path, 2, sync=False)
    survivors = j2.replay()
    assert len(survivors) == 4, "only the torn record may be dropped"
    j2.reset()
    j2.append_insert(0, 9.0)
    assert j2.replay()[0].seqno > 5, \
        "seqnos must keep increasing across reset() or watermarks break"


def test_truncate_through_drops_only_at_or_below_watermark(tmp_path):
    """The background persister's watermark-aware journal GC: records past
    the watermark survive byte-identically (a fresh Journal re-reads them
    and resumes seqnos after them); records at or below it are gone."""
    j = Journal(tmp_path, 2, sync=False)
    for i in range(6):
        j.append_insert(i % 2, float(i))
    j.append_delete(1.0, 2.0)                                   # seqno 7
    bounds = np.linspace(0.0, 1.0, 9).astype(np.float32)
    j.append_resummarize(bounds, "learned")                     # seqno 8
    j.truncate_through(5)
    assert [r.seqno for r in j.replay()] == [6, 7, 8]
    j2 = Journal(tmp_path, 2, sync=False)       # fresh scan of the rewrite
    recs = j2.replay()
    assert [r.seqno for r in recs] == [6, 7, 8]
    assert j2.last_seqno == 8, "seqno allocation must resume after survivors"
    assert (recs[1].lo, recs[1].hi) == (1.0, 2.0)
    assert recs[2].policy == "learned"
    np.testing.assert_array_equal(recs[2].bounds, bounds)
    j2.truncate_through(100)
    assert j2.replay() == [], "a watermark past everything empties the logs"


# ---------------------------------------------------------------------------
# Incremental snapshots: delta chains, compaction, tombstone pruning
# ---------------------------------------------------------------------------

def test_delta_round_trip_counts_and_rows_bit_identical(tmp_path):
    """A full snapshot + one delta capturing the drained/vacuumed shards
    loads to exactly the live index's counts and row ids — and to brute
    force over the surviving value multiset."""
    rng = np.random.default_rng(21)
    base = np.sort(rng.uniform(0, 100, 300))
    idx = make_sidx(base)
    w = MaintenanceWriter(idx)
    save_index(tmp_path, idx, wal_seqno=0)
    vals = [float(v) for v in base]
    for v in rng.uniform(100.0, 128.0, 40):
        w.write(float(v))
        vals.append(float(v))
    w.flush()
    w.delete(10.0, 14.0)       # validity flips outside the drained shards
    vals = [v for v in vals if not 10.0 <= v <= 14.0]
    w.flush()
    shards = w.dirty_checkpoint_shards()
    assert shards, "drains and deletes must mark their shards dirty"
    save_delta(tmp_path, idx, shards=shards)
    assert latest_delta_seq(tmp_path, latest_epoch(tmp_path)) == 1

    idx2, meta = load_index(tmp_path)
    assert meta["deltas"] == 1
    ps = preds()
    counts1, rows1 = engine_counts_and_rows(idx, w, ps)
    counts2, rows2 = engine_counts_and_rows(idx2, None, ps)
    np.testing.assert_array_equal(counts2, counts1)
    for a, b in zip(rows1, rows2):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(counts2, value_brute(vals, ps))


def test_delta_chain_gap_is_refused(tmp_path):
    """A committed delta k without every committed delta below it means a
    skipped commit; replaying across the hole would silently lose shards —
    loading must refuse."""
    import shutil
    rng = np.random.default_rng(22)
    idx = make_sidx(np.sort(rng.uniform(0, 100, 200)))
    w = MaintenanceWriter(idx)
    save_index(tmp_path, idx, wal_seqno=0)
    for k in range(2):
        for v in rng.uniform(100.0, 120.0, 8):
            w.write(float(v))
        w.flush()
        save_delta(tmp_path, idx, shards=w.dirty_checkpoint_shards())
        w.clear_checkpoint_dirty()
    assert latest_delta_seq(tmp_path, 1) == 2
    shutil.rmtree(tmp_path / "delta_1_1")
    with pytest.raises(CorruptSnapshotError, match="delta chain"):
        load_index(tmp_path)
    with pytest.raises(CorruptSnapshotError, match="delta chain"):
        delta_chain(tmp_path, 1)


def test_prune_renames_to_tombstone_before_rmtree(tmp_path, monkeypatch):
    """Satellite regression: pruning must rename a doomed snapshot to
    ``*.tombstone`` *before* deleting it, so a crash mid-prune (simulated
    by an rmtree that never runs) leaves no discoverable directory that
    still carries a COMMITTED sentinel — and the next save sweeps the
    leftover tombstone."""
    rng = np.random.default_rng(23)
    idx = make_sidx(np.sort(rng.uniform(0, 100, 160)))
    save_index(tmp_path, idx, keep=1)          # snap_1
    monkeypatch.setattr(snap_mod.shutil, "rmtree",
                        lambda *a, **k: None)   # crash: delete never lands
    save_index(tmp_path, idx, keep=1)          # snap_2 prunes snap_1
    monkeypatch.undo()

    tomb = tmp_path / "snap_1.tombstone"
    assert tomb.exists(), "prune must rename before any rmtree"
    assert (tomb / "COMMITTED").exists(), \
        "setup rot: the crash should leave the sentinel inside the tombstone"
    assert not (tmp_path / "snap_1").exists()
    assert latest_epoch(tmp_path) == 2, \
        "a tombstoned COMMITTED sentinel must be invisible to discovery"
    idx2, _ = load_index(tmp_path)             # loads snap_2, not the tomb
    ps = preds()
    c1, _ = engine_counts_and_rows(idx, None, ps)
    c2, _ = engine_counts_and_rows(idx2, None, ps)
    np.testing.assert_array_equal(c2, c1)

    save_index(tmp_path, idx, keep=1)          # snap_3: sweeps the leftover
    assert not tomb.exists(), "the next save must sweep crash tombstones"


def test_prune_drops_a_superseded_base_with_its_delta_chain(tmp_path):
    """Compaction hygiene: when an old full base falls out of ``keep``,
    its deltas go with it — they are unreadable without their base."""
    rng = np.random.default_rng(24)
    idx = make_sidx(np.sort(rng.uniform(0, 100, 200)))
    w = MaintenanceWriter(idx)
    save_index(tmp_path, idx, keep=1)          # snap_1
    for v in rng.uniform(100.0, 120.0, 8):
        w.write(float(v))
    w.flush()
    save_delta(tmp_path, idx, shards=w.dirty_checkpoint_shards())
    w.clear_checkpoint_dirty()
    save_index(tmp_path, idx, keep=1, compact=True)   # snap_2 folds chain
    names = {p.name for p in tmp_path.iterdir()}
    assert "snap_2" in names
    assert "snap_1" not in names and "delta_1_1" not in names, \
        "a pruned base must take its delta chain with it"


def test_incremental_engine_builds_chain_then_compacts(tmp_path):
    """Engine e2e on the default incremental mode: each drain commits a
    delta ≪ the full base, the K policy folds the chain into a fresh full
    snapshot, and recovery off the chain is bit-identical to brute force."""
    rng = np.random.default_rng(25)
    base = np.sort(rng.uniform(0, 100, 200))
    root = tmp_path / "dur"
    idx = make_sidx(base)
    eng = QueryEngine(idx, batch=8, drain_policy="manual",
                      auto_resummarize=False, storage_dir=root,
                      compact_every=3, compact_ratio=1e9)  # isolate K policy
    vals = [float(v) for v in base]
    for step in range(4):
        for v in rng.uniform(100.0, 130.0, 8):
            eng.write(float(v))
            vals.append(float(v))
        eng.flush()
    names = {p.name for p in root.iterdir() if p.is_dir()}
    assert {"snap_1", "delta_1_1", "delta_1_2", "delta_1_3",
            "snap_2"} <= names, f"unexpected chain layout: {sorted(names)}"
    full = (root / "snap_1" / "index.bin").stat().st_size
    for k in range(1, 4):
        d = (root / f"delta_1_{k}" / "index.bin").stat().st_size
        assert d < full, \
            f"delta_{k} ({d}B) should be smaller than its base ({full}B)"
    assert eng.stats.persists == 5          # initial full + 3 deltas + fold
    assert eng.stats.persist_lag == 0

    del eng
    eng2 = _recover(root)
    eng2.flush()
    ps = preds()
    np.testing.assert_array_equal(eng2.run_all(ps), value_brute(vals, ps))


def test_background_save_poison_falls_back_to_sync_full(tmp_path,
                                                        monkeypatch):
    """A failed background commit poisons the persister (queued commits
    must not leapfrog a hole in the chain); flush_durable surfaces it, and
    the next drain commit self-heals through a synchronous full snapshot
    that supersedes the broken chain and re-enables background saves."""
    from repro.runtime.persister import PersisterPoisoned
    rng = np.random.default_rng(26)
    base = np.sort(rng.uniform(0, 100, 200))
    root = tmp_path / "dur"
    eng = QueryEngine(make_sidx(base), batch=8, drain_policy="manual",
                      auto_resummarize=False, storage_dir=root,
                      background_save=True)
    vals = [float(v) for v in base]

    def boom(*a, **k):
        raise _Boom("disk full")
    monkeypatch.setattr(snap_mod, "write_delta_snapshot", boom)
    for v in rng.uniform(100.0, 120.0, 8):
        eng.write(float(v))
        vals.append(float(v))
    eng.flush()                      # delta job fails on the worker thread
    with pytest.raises(PersisterPoisoned):
        eng.flush_durable()
    assert eng._persister.stats_snapshot().failed == 1
    monkeypatch.undo()

    for v in rng.uniform(120.0, 130.0, 8):
        eng.write(float(v))
        vals.append(float(v))
    eng.flush()                      # poisoned submit -> sync full fallback
    eng.flush_durable()              # clean: the chain was superseded
    assert not eng._persister.poisoned

    eng.close()
    eng2 = _recover(root)
    eng2.flush()
    ps = preds()
    np.testing.assert_array_equal(eng2.run_all(ps), value_brute(vals, ps))


# ---------------------------------------------------------------------------
# hippolint regressions: journal-before-admission in schedule_resummarize,
# and the durable watermark crossing the persister/foreground thread line
# ---------------------------------------------------------------------------

def test_resummarize_journals_before_admission(tmp_path):
    """Regression for the crash-pass finding in schedule_resummarize: the
    learned model, fallback/refit counters, and pending bounds were
    admitted *before* the WAL append. A crash at the append (kill -9
    stand-in) must leave the writer exactly as it was — the operation was
    never acknowledged, so no trace of it may survive."""
    from repro.runtime.faultinject import InjectedCrash, crash_points
    rng = np.random.default_rng(31)
    base = np.sort(rng.uniform(0, 100, 200))
    idx = make_sidx(base, summary="learned")
    writer = MaintenanceWriter(idx)
    writer.journal = Journal(tmp_path, idx.spec.num_shards, sync=False)
    for v in rng.uniform(0, 100, 64):
        writer.write(float(v))
    writer.flush()

    def state():
        return (writer._pending_model, writer._pending_bounds,
                writer.stats.learned_refits, writer.stats.learned_fallbacks,
                writer.pending_resummarize_shards())

    before = state()
    wm = writer.journal.last_seqno
    crash_points.arm("wal.pre_append", times=1)
    try:
        with pytest.raises(InjectedCrash):
            writer.schedule_resummarize()
    finally:
        crash_points.reset()
    assert state() == before, \
        "a crashed (unacknowledged) resummarize left writer state behind"
    assert writer.journal.last_seqno == wm, "nothing may have been appended"
    # and with the crash gone, the same call goes through whole
    writer.schedule_resummarize()
    assert writer.journal.last_seqno == wm + 1
    assert writer.pending_resummarize_shards()


def test_background_watermark_advances_under_lock(tmp_path):
    """Regression for the locks-pass finding on _durable_watermark: the
    persister's commit callback advances it on the worker thread while
    the foreground derives persist_lag from it. After the flush barrier
    the locked read must equal the journal watermark exactly."""
    rng = np.random.default_rng(33)
    base = np.sort(rng.uniform(0, 100, 200))
    root = tmp_path / "dur"
    eng = QueryEngine(make_sidx(base), batch=8, drain_policy="manual",
                      auto_resummarize=False, storage_dir=root,
                      background_save=True)
    for v in rng.uniform(100, 120, 8):
        eng.write(float(v))
    eng.flush()                       # drain -> background delta commit
    eng.flush_durable()
    with eng._durable_lock:
        wm = eng._durable_watermark
    assert wm == eng.journal.last_seqno > 0
    eng._sync_writer_stats()
    assert eng.stats.persist_lag == 0
    assert eng.stats.persist_pending == 0
    eng.close()
