"""Crash-consistency harness: simulated power-loss crashes over the
storage plane (tpuraft/storage/fault.py).

Three generational harnesses — FileLogStorage + MetaJournal under live
``ChaosDir`` interposition, the native multilog under
``NativeJournalTracker`` tail imaging — each runs dozens of seeded
power-loss crashes (>= 220 in total across the module) and checks the
recovery invariants after EVERY one:

  - recovery never raises (a torn/bit-flipped unsynced tail is
    truncated at the last CRC-valid record, not crashed on);
  - log prefix property: recovered entries byte-match what was staged;
  - acked floor: nothing proven durable by a completed fsync is lost
    (last_recovered >= last_acked, {term, votedFor} never regresses
    below an acked save);
  - staged ceiling: recovery never invents entries beyond what was
    staged;
  - no orphaned gids: an acked registration keeps its gid across
    crashes; journal records whose registration was lost are truncated,
    never adopted or shadowed.

Bit rot of the DURABLE region is the opposite contract — fail loudly,
never truncate silently — and is covered by the explicit tests at the
bottom.
"""

from __future__ import annotations

import os
import random
import struct

from tpuraft.entity import EMPTY_PEER, EntryType, LogEntry, LogId, PeerId
from tpuraft.storage.fault import (
    ChaosDir,
    NativeJournalTracker,
)
from tpuraft.storage.log_storage import CorruptLogError, FileLogStorage
from tpuraft.storage.meta_multilog import MetaJournal
from tpuraft.storage.multilog import MultiLogStorage


def _entry(index: int, gen: int, term: int = 1) -> LogEntry:
    return LogEntry(type=EntryType.DATA, id=LogId(index, term),
                    data=b"g%03d-i%06d" % (gen, index))


# ---------------------------------------------------------------------------
# FileLogStorage under ChaosDir
# ---------------------------------------------------------------------------


def _filelog_lifetime(root: str, rng: random.Random, gens: int) -> int:
    """One directory, ``gens`` crash generations; returns crash count."""
    first, entries, acked_last = 1, {}, 0

    def staged_last():
        return max(entries) if entries else first - 1

    with ChaosDir(root) as chaos:
        for gen in range(gens):
            st = FileLogStorage(os.path.join(root, "log"),
                                segment_max_bytes=200)
            st.init()  # must tolerate whatever the crash left
            rf, rl = st.first_log_index(), st.last_log_index()
            assert rf == first, f"gen {gen}: first {rf} != {first}"
            assert acked_last <= rl <= staged_last(), \
                f"gen {gen}: last {rl} not in [{acked_last}, {staged_last()}]"
            for i in range(rf, rl + 1):
                e = st.get_entry(i)
                assert e is not None and e.data == entries[i], \
                    f"gen {gen}: entry {i} mismatch"
            # recovered state is durable (init re-fsyncs + watermarks)
            for i in list(entries):
                if i > rl:
                    del entries[i]
            acked_last = rl

            for _ in range(rng.randrange(1, 5)):
                op = rng.random()
                if op < 0.70 or not entries:
                    n = rng.randrange(1, 6)
                    batch = [_entry(staged_last() + 1 + k, gen)
                             for k in range(n)]
                    st.append_entries(batch, sync=True)  # fsynced => acked
                    for e in batch:
                        entries[e.id.index] = e.data
                    acked_last = staged_last()
                elif op < 0.85 and acked_last >= first:
                    keep = rng.randrange(first - 1, staged_last() + 1)
                    st.truncate_suffix(keep)  # fsynced by contract
                    for i in list(entries):
                        if i > keep:
                            del entries[i]
                    acked_last = min(acked_last, keep)
                elif op < 0.95 and staged_last() > first:
                    cut = rng.randrange(first, staged_last() + 1)
                    st.truncate_prefix(cut)  # meta fsynced by contract
                    first = max(first, cut)
                    for i in list(entries):
                        if i < first:
                            del entries[i]
                    acked_last = max(acked_last, first - 1)
                else:
                    nxt = staged_last() + rng.randrange(1, 10)
                    st.reset(nxt)
                    first, entries, acked_last = nxt, {}, nxt - 1

            if rng.random() < 0.7:
                # the in-flight append the power interrupts: staged
                # bytes on disk, fsync never completed — on-disk
                # identical to a crash mid sync=True append
                n = rng.randrange(1, 5)
                batch = [_entry(staged_last() + 1 + k, gen, term=2)
                         for k in range(n)]
                st.append_entries(batch, sync=False)
                for e in batch:
                    entries[e.id.index] = e.data

            plan = chaos.capture_crash(rng)   # power dies here
            st.shutdown()                     # in-proc cleanup only...
            chaos.apply_crash(plan)           # ...discarded by the image
        return chaos.crash_count


def test_filelog_power_loss_recovery():
    import tempfile

    crashes = 0
    for seed in range(3):
        with tempfile.TemporaryDirectory() as tmp:
            crashes += _filelog_lifetime(
                os.path.join(tmp, f"flog{seed}"),
                random.Random(1000 + seed), gens=20)
    assert crashes >= 60


# ---------------------------------------------------------------------------
# MetaJournal under ChaosDir
# ---------------------------------------------------------------------------


def _meta_lifetime(root: str, rng: random.Random, gens: int) -> int:
    groups = [f"r{i}" for i in range(4)]
    history = {g: [(0, "")] for g in groups}   # staged (term, voted) per group
    acked = {g: 0 for g in groups}             # index into history[g]
    term = {g: 0 for g in groups}

    with ChaosDir(root) as chaos:
        for gen in range(gens):
            j = MetaJournal(root)
            j.COMPACT_MIN_BYTES = 512  # force compaction under chaos
            for g in groups:
                t, voted = j.get(g)
                v = "" if voted.is_empty() else str(voted)
                hist = history[g]
                assert (t, v) in hist, f"gen {gen}: {g} has unknown {t}/{v}"
                pos = hist.index((t, v))
                assert pos >= acked[g], \
                    f"gen {gen}: {g} regressed below acked " \
                    f"({t} < {hist[acked[g]][0]})"
                # recovered value is durable now (reopen fsync + wm)
                history[g] = [(t, v)]
                acked[g] = 0
                term[g] = max(term[g], t)

            for _ in range(rng.randrange(2, 8)):
                g = rng.choice(groups)
                term[g] += rng.randrange(1, 3)
                voted = PeerId.parse(f"10.0.0.{rng.randrange(1, 5)}:80") \
                    if rng.random() < 0.8 else EMPTY_PEER
                j.stage(g, term[g], voted)
                history[g].append(
                    (term[g], "" if voted.is_empty() else str(voted)))
                if rng.random() < 0.4:
                    j.sync()  # group-commit round: everything staged acks
                    for gg in groups:
                        acked[gg] = len(history[gg]) - 1

            plan = chaos.capture_crash(rng)
            j.close()
            chaos.apply_crash(plan)
        return chaos.crash_count


def test_meta_journal_power_loss_recovery():
    import tempfile

    crashes = 0
    for seed in range(4):
        with tempfile.TemporaryDirectory() as tmp:
            crashes += _meta_lifetime(
                os.path.join(tmp, f"meta{seed}"),
                random.Random(2000 + seed), gens=20)
    assert crashes >= 80


# ---------------------------------------------------------------------------
# native multilog under tail imaging
# ---------------------------------------------------------------------------


class _GroupModel:
    def __init__(self) -> None:
        self.first = 1
        self.acked_first = 1
        self.entries: dict[int, bytes] = {}
        self.acked_last = 0

    def staged_last(self) -> int:
        return max(self.entries) if self.entries else self.first - 1


def _native_lifetime(base: str, rng: random.Random, gens: int) -> int:
    names = [f"g{i}" for i in range(3)]
    model = {n: _GroupModel() for n in names}
    gids: dict[str, int] = {}
    live = os.path.join(base, "gen0")
    crashes = 0

    for gen in range(gens):
        stores = {n: MultiLogStorage(live, n) for n in names}
        for n in names:
            stores[n].init()  # shared engine; recovery scan runs once
        eng = stores[names[0]].engine
        eng.sync()  # registrations of any new names ack immediately
        for n in names:
            if n in gids:
                assert stores[n]._gid == gids[n], \
                    f"gen {gen}: acked group {n} changed gid " \
                    f"{gids[n]} -> {stores[n]._gid} (orphan/shadow)"
            else:
                gids[n] = stores[n]._gid

        tracker = NativeJournalTracker(live)
        tracker.note_sync()  # the recovered image IS the durable state

        for n in names:
            m, s = model[n], stores[n]
            rf, rl = s.first_log_index(), s.last_log_index()
            assert m.acked_first <= rf, \
                f"gen {gen}: {n} first {rf} below acked {m.acked_first}"
            assert rf <= max(m.first, m.acked_first), \
                f"gen {gen}: {n} first {rf} beyond staged {m.first}"
            assert m.acked_last <= rl, \
                f"gen {gen}: {n} last {rl} below acked {m.acked_last}"
            assert rl <= m.staged_last() or not m.entries, \
                f"gen {gen}: {n} last {rl} beyond staged {m.staged_last()}"
            for i in range(rf, rl + 1):
                e = s.get_entry(i)
                assert e is not None and e.data == m.entries[i], \
                    f"gen {gen}: {n} entry {i} mismatch"
            m.first = rf
            m.acked_first = rf
            for i in list(m.entries):
                if i < rf or i > rl:
                    del m.entries[i]
            m.acked_last = rl

        synced = False
        for _ in range(rng.randrange(2, 6)):
            n = rng.choice(names)
            m, s = model[n], stores[n]
            op = rng.random()
            if op < 0.60 or not m.entries:
                cnt = rng.randrange(1, 5)
                batch = [_entry(m.staged_last() + 1 + k, gen)
                         for k in range(cnt)]
                s.append_entries(batch, sync=False)  # staged, not acked
                for e in batch:
                    m.entries[e.id.index] = e.data
            elif op < 0.75:
                eng.sync()
                tracker.note_sync()
                for mm in model.values():
                    mm.acked_last = mm.staged_last()
                    mm.acked_first = mm.first
                synced = True
            elif op < 0.85 and m.acked_last >= m.first:
                keep = rng.randrange(m.first - 1, m.staged_last() + 1)
                s.truncate_suffix(keep)  # fsyncs everything staged
                tracker.note_sync()
                for i in list(m.entries):
                    if i > keep:
                        del m.entries[i]
                for mm in model.values():
                    mm.acked_last = mm.staged_last()
                    mm.acked_first = mm.first
            elif op < 0.95 and m.staged_last() > m.first:
                cut = rng.randrange(m.first, m.staged_last() + 1)
                s.truncate_prefix(cut)  # lazily durable control record
                m.first = max(m.first, cut)
                # keep entries down to acked_first: a crash can lose the
                # staged trunc record and legitimately revive them
                for i in list(m.entries):
                    if i < m.acked_first:
                        del m.entries[i]
            else:
                nxt = m.staged_last() + rng.randrange(1, 8)
                s.reset(nxt)  # fsyncs everything staged
                tracker.note_sync()
                m.first = m.acked_first = nxt
                m.entries = {}
                m.acked_last = nxt - 1
                for mm in model.values():
                    mm.acked_last = mm.staged_last()
        del synced

        nxt_dir = os.path.join(base, f"gen{gen + 1}")
        tracker.crash_image(nxt_dir, rng)  # power dies here
        for s in stores.values():
            s.shutdown()  # releases/closes the live engine afterwards
        live = nxt_dir
        crashes += 1
    return crashes


def test_native_multilog_power_loss_recovery(tmp_path):
    crashes = 0
    for seed in range(4):
        crashes += _native_lifetime(
            str(tmp_path / f"nat{seed}"), random.Random(3000 + seed),
            gens=30)
    assert crashes >= 120


# ---------------------------------------------------------------------------
# explicit contract tests
# ---------------------------------------------------------------------------


def test_torn_tail_truncated_at_last_crc_valid_record(tmp_path):
    """A torn unsynced tail recovers by CRC truncation — acked prefix
    intact, no exception, no garbage read."""
    root = str(tmp_path / "torn")
    rng = random.Random(7)
    with ChaosDir(root, modes=(("torn-write", 1.0),)) as chaos:
        st = FileLogStorage(os.path.join(root, "log"))
        st.init()
        st.append_entries([_entry(i, 0) for i in range(1, 6)], sync=True)
        st.append_entries([_entry(i, 0) for i in range(6, 9)], sync=False)
        plan = chaos.capture_crash(rng)
        st.shutdown()
        chaos.apply_crash(plan)
        st2 = FileLogStorage(os.path.join(root, "log"))
        st2.init()
        assert 5 <= st2.last_log_index() <= 8
        for i in range(1, st2.last_log_index() + 1):
            assert st2.get_entry(i).data == _entry(i, 0).data
        st2.shutdown()


def test_bit_flip_in_unsynced_tail_is_truncated(tmp_path):
    root = str(tmp_path / "flip")
    rng = random.Random(11)
    with ChaosDir(root, modes=(("bit-flip", 1.0),)) as chaos:
        st = FileLogStorage(os.path.join(root, "log"))
        st.init()
        st.append_entries([_entry(i, 0) for i in range(1, 4)], sync=True)
        st.append_entries([_entry(i, 0) for i in range(4, 9)], sync=False)
        plan = chaos.capture_crash(rng)
        st.shutdown()
        chaos.apply_crash(plan)
        st2 = FileLogStorage(os.path.join(root, "log"))
        st2.init()  # must not raise: flip is in the unsynced region
        assert st2.last_log_index() >= 3
        for i in range(1, st2.last_log_index() + 1):
            assert st2.get_entry(i).data == _entry(i, 0).data
        st2.shutdown()


def test_durable_bit_rot_fails_loudly_filelog(tmp_path):
    """Corruption BELOW the durability watermark is not a torn tail:
    startup must refuse to truncate acked entries."""
    d = str(tmp_path / "rot")
    st = FileLogStorage(d)
    st.init()
    st.append_entries([_entry(i, 0) for i in range(1, 6)], sync=True)
    st.shutdown()  # advances the watermark over everything
    seg = next(n for n in os.listdir(d) if n.startswith("seg_"))
    p = os.path.join(d, seg)
    blob = bytearray(open(p, "rb").read())
    blob[len(blob) // 2] ^= 0x40
    open(p, "wb").write(bytes(blob))
    st2 = FileLogStorage(d)
    try:
        st2.init()
        raise AssertionError("durable-region rot went undetected")
    except CorruptLogError:
        pass


def test_multilog_get_crc_guards_read_path(tmp_path):
    """Bit rot in a live, indexed record: tlm_get must fail loudly
    (CorruptLogError), not hand garbage (or a silent hole) upward."""
    d = str(tmp_path / "mrot")
    s = MultiLogStorage(d, "g")
    s.init()
    s.append_entries([_entry(i, 0) for i in range(1, 4)], sync=True)
    jnl = next(n for n in sorted(os.listdir(d))
               if n.startswith("journal_"))
    p = os.path.join(d, jnl)
    blob = bytearray(open(p, "rb").read())
    blob[30] ^= 0x10  # inside the first record's payload
    open(p, "wb").write(bytes(blob))
    try:
        s.get_entry(1)
        raise AssertionError("rotted record served without complaint")
    except CorruptLogError:
        pass
    finally:
        s.shutdown()


def test_multilog_len_rot_on_live_record_fails_loudly(tmp_path):
    """A len field rotted HIGH on a live, indexed record must surface
    as corruption (CorruptLogError), not read as a missing-entry hole
    via a short payload read."""
    d = str(tmp_path / "lenrot")
    s = MultiLogStorage(d, "g")
    s.init()
    s.append_entries([_entry(i, 0) for i in range(1, 3)], sync=True)
    jnl = next(n for n in sorted(os.listdir(d))
               if n.startswith("journal_"))
    p = os.path.join(d, jnl)
    blob = bytearray(open(p, "rb").read())
    blob[3] |= 0x40  # inflate the first record's len field past the file
    open(p, "wb").write(bytes(blob))
    try:
        s.get_entry(1)
        raise AssertionError("len-rotted record read as a hole")
    except CorruptLogError:
        pass
    finally:
        s.shutdown()


def test_multilog_unreadable_registry_fails_open_not_truncates(tmp_path):
    """A registry that cannot be READ must fail the engine open loudly
    (retryable) — scanning journals against a partial registry would
    read every acked record as orphan garbage and truncate them."""
    d = str(tmp_path / "regdead")
    s = MultiLogStorage(d, "g")
    s.init()
    s.append_entries([_entry(1, 0)], sync=True)
    s.shutdown()
    jsize = os.path.getsize(os.path.join(d, next(
        n for n in sorted(os.listdir(d)) if n.startswith("journal_"))))
    reg = os.path.join(d, "groups")
    os.remove(reg)
    os.mkdir(reg)  # open(O_RDWR) now fails EISDIR: unreadable registry
    s2 = MultiLogStorage(d, "g")
    try:
        s2.init()
        raise AssertionError("open succeeded against unreadable registry")
    except IOError:
        pass
    # the acked journal bytes must be untouched by the failed open
    jnl = next(n for n in sorted(os.listdir(d))
               if n.startswith("journal_"))
    assert os.path.getsize(os.path.join(d, jnl)) == jsize
    os.rmdir(reg)


def test_multilog_registry_gid_alias_is_truncated(tmp_path):
    """A flipped gid in the registry's unsynced tail must not alias an
    acked gid (shadowing another group's log): the sequential-gid scan
    truncates the tail at the deviation."""
    d = str(tmp_path / "reg")
    sa, sb = MultiLogStorage(d, "a"), MultiLogStorage(d, "b")
    sa.init(), sb.init()
    sa.engine.sync()  # both registrations acked
    gid_a, gid_b = sa._gid, sb._gid
    sa.shutdown(), sb.shutdown()
    # forge a tail record claiming gid_a for a different name (what a
    # partial-page writeback bit flip can leave behind)
    with open(os.path.join(d, "groups"), "ab") as f:
        f.write(struct.pack("<II", gid_a, 1) + b"z")
    sa2, sz = MultiLogStorage(d, "a"), MultiLogStorage(d, "z")
    sa2.init(), sz.init()
    try:
        assert sa2._gid == gid_a
        assert sz._gid not in (gid_a, gid_b), "alias adopted: shadowing"
    finally:
        sa2.shutdown(), sz.shutdown()


def test_multilog_registry_tolerates_legacy_gid_gaps(tmp_path):
    """Registries written before register_group rolled next_gid back on
    a failed append can hold gid GAPS in their durable region; the
    alias guard must accept those (strictly increasing), not truncate
    acked registrations on upgrade."""
    d = str(tmp_path / "gap")
    sa, sb = MultiLogStorage(d, "a"), MultiLogStorage(d, "b")
    sa.init(), sb.init()
    gid_a, gid_b = sa._gid, sb._gid
    sa.engine.sync()
    sa.shutdown(), sb.shutdown()
    # legacy gap: a registration that consumed gid_b+1 without a record,
    # then a later group registered at gid_b+2
    with open(os.path.join(d, "groups"), "ab") as f:
        f.write(struct.pack("<II", gid_b + 2, 1) + b"c")
    sa2 = MultiLogStorage(d, "a")
    sb2 = MultiLogStorage(d, "b")
    sc2 = MultiLogStorage(d, "c")
    sd2 = MultiLogStorage(d, "dnew")
    for s in (sa2, sb2, sc2, sd2):
        s.init()
    try:
        assert sa2._gid == gid_a and sb2._gid == gid_b
        assert sc2._gid == gid_b + 2, "gap-following record truncated"
        assert sd2._gid == gid_b + 3  # next_gid resumed past the gap
    finally:
        for s in (sa2, sb2, sc2, sd2):
            s.shutdown()


def test_multilog_orphan_journal_records_are_torn(tmp_path):
    """Journal records whose registration never became durable are an
    unsynced tail by construction: recovery truncates them instead of
    adopting records for an unregistered gid."""
    import shutil

    d = str(tmp_path / "orph")
    sa = MultiLogStorage(d, "a")
    sa.init()
    sa.append_entries([_entry(1, 0)], sync=True)   # a: acked
    reg_durable = os.path.getsize(os.path.join(d, "groups"))
    sb = MultiLogStorage(d, "b")
    sb.init()                                       # b: registration staged
    sb.append_entries([_entry(1, 0), _entry(2, 0)], sync=False)
    # power loss: journal pages survived writeback, registry tail didn't
    img = str(tmp_path / "orph_img")
    shutil.copytree(d, img)
    with open(os.path.join(img, "groups"), "r+b") as f:
        f.truncate(reg_durable)
    sa.shutdown(), sb.shutdown()
    ra, rb = MultiLogStorage(img, "a"), MultiLogStorage(img, "b")
    ra.init(), rb.init()
    try:
        assert ra.last_log_index() == 1
        assert ra.get_entry(1).data == _entry(1, 0).data
        # b's staged-only records were truncated with its registration;
        # the re-registered b starts empty (no adopted orphan records)
        assert rb.last_log_index() == 0
        assert rb.get_entry(1) is None
    finally:
        ra.shutdown(), rb.shutdown()


async def test_reboot_after_compaction_keeps_acked_suffix(tmp_path):
    """Regression for the amnesiac-reboot bug the power-loss soak found:
    after snapshot compaction prunes the entry AT the snapshot index
    (margin 0, first == S+1), the next boot's set_snapshot saw term 0
    there, called it divergence, and RESET the log — silently dropping
    the whole acked suffix.  Two stores rebooting in one fault window
    then break quorum intersection and un-commit acked writes."""
    from tpuraft.conf import Configuration, ConfigurationEntry
    from tpuraft.storage.log_manager import LogManager

    d = str(tmp_path / "lm")
    conf = ConfigurationEntry(
        LogId(0, 0), Configuration.parse("1.1.1.1:1,1.1.1.2:1,1.1.1.3:1"))

    st = FileLogStorage(d)
    lm = LogManager(st)
    await lm.init()
    await lm.append_entries_follower(
        0, 0, [_entry(i, 0, term=3) for i in range(1, 11)])
    # snapshot at 5 (margin 0): prunes entries <= 5, first becomes 6
    await lm.set_snapshot(LogId(5, 3), conf)
    assert lm.first_log_index() == 6 and lm.last_log_index() == 10
    await lm.shutdown()

    # reboot: snapshot load replays set_snapshot on the compacted log
    st2 = FileLogStorage(d)
    lm2 = LogManager(st2)
    await lm2.init()
    await lm2.set_snapshot(LogId(5, 3), conf)
    assert lm2.last_log_index() == 10, \
        "acked suffix dropped on reboot after compaction"
    for i in range(6, 11):
        assert lm2.get_term(i) == 3
    assert lm2.check_consistency().is_ok()
    await lm2.shutdown()

    # the true-divergence case still resets: entry AT the snapshot index
    # present with a DIFFERENT term (install-snapshot over a stale log)
    st3 = FileLogStorage(str(tmp_path / "lm3"))
    lm3 = LogManager(st3)
    await lm3.init()
    await lm3.append_entries_follower(
        0, 0, [_entry(i, 0, term=2) for i in range(1, 11)])
    await lm3.set_snapshot(LogId(7, 5), conf)   # term 5 != stored term 2
    assert lm3.last_log_index() == 7            # stale tail dropped
    assert lm3.first_log_index() == 8
    await lm3.shutdown()


def test_chaosdir_lost_fsync_and_survival(tmp_path):
    """Sanity of the model itself: unsynced bytes vanish under
    lost-fsync; fsynced bytes always survive."""
    root = str(tmp_path / "model")
    rng = random.Random(5)
    with ChaosDir(root, modes=(("lost-fsync", 1.0),)) as chaos:
        p = os.path.join(root, "f.bin")
        f = open(p, "wb")
        f.write(b"durable")
        f.flush()
        os.fsync(f.fileno())
        f.write(b"+volatile")
        f.flush()
        f.close()
        assert open(p, "rb").read() == b"durable+volatile"
        chaos.crash(rng)
        assert open(p, "rb").read() == b"durable"


# ---------------------------------------------------------------------------
# disk-pressure fault plane: quota / ENOSPC (ISSUE 17)
# ---------------------------------------------------------------------------


def test_chaosdir_quota_partial_write_then_enospc(tmp_path):
    """The capacity fault plane itself: a write crossing the budget
    commits the fitting prefix (short write) then fails ENOSPC; deletes
    refund the budget."""
    import errno as _errno

    root = str(tmp_path / "quota")
    with ChaosDir(root) as chaos:
        p = os.path.join(root, "f.bin")
        with open(p, "wb") as f:
            f.write(b"x" * 60)
        chaos.set_quota(100)
        try:
            with open(p, "ab") as f:
                f.write(b"y" * 80)
            raise AssertionError("over-budget write admitted whole")
        except OSError as e:
            assert e.errno == _errno.ENOSPC
        # the fitting 40-byte prefix landed before the error
        assert os.path.getsize(p) == 100
        assert chaos.enospc_counts.get("write", 0) == 1
        limit, used = chaos.quota_state()
        assert limit == 100 and used >= 100
        # refund on remove: the budget frees and writes admit again
        os.remove(p)
        with open(os.path.join(root, "g.bin"), "wb") as f:
            f.write(b"z" * 50)
        assert os.path.getsize(os.path.join(root, "g.bin")) == 50


def test_chaosdir_quota_shrink_and_burst(tmp_path):
    """quota-shrink-over-time tightens the wall; seeded bursts fail
    writes wholesale regardless of budget and heal at rate 0."""
    root = str(tmp_path / "sq")
    with ChaosDir(root) as chaos:
        chaos.set_quota(1000)
        assert chaos.shrink_quota(400) == 600
        p = os.path.join(root, "f.bin")
        with open(p, "wb") as f:
            f.write(b"a" * 500)
        try:
            with open(p, "ab") as f:
                f.write(b"b" * 200)
            raise AssertionError("shrunk quota not enforced")
        except OSError:
            pass
        chaos.set_enospc_burst(1.0, seed=9)
        try:
            with open(os.path.join(root, "h.bin"), "wb") as f:
                f.write(b"c")
            raise AssertionError("burst rate 1.0 admitted a write")
        except OSError:
            pass
        assert chaos.enospc_counts.get("burst", 0) >= 1
        chaos.set_enospc_burst(0.0)
        chaos.clear_quota()
        with open(os.path.join(root, "h.bin"), "wb") as f:
            f.write(b"c" * 300)  # healed


def test_chaosdir_quota_rename_enospc(tmp_path):
    """Creating a fresh directory entry on a full tree fails ENOSPC
    (the path snapshot commit / meta compaction renames exercise)."""
    root = str(tmp_path / "rq")
    with ChaosDir(root) as chaos:
        src = os.path.join(root, "src.bin")
        with open(src, "wb") as f:
            f.write(b"x" * 100)
        chaos.set_quota(100)  # exactly full
        try:
            os.rename(src, os.path.join(root, "dst.bin"))
            raise AssertionError("rename to fresh entry on full tree")
        except OSError:
            pass
        assert chaos.enospc_counts.get("rename", 0) == 1
        # replacing an EXISTING entry stays allowed (no new inode)
        dst = os.path.join(root, "src.bin")  # self-replace: dst exists
        os.replace(src, dst)


def test_filelog_enospc_append_fails_clean_and_retries(tmp_path):
    """An append that dies ENOSPC leaves the storage view unchanged
    (no phantom index advance) and the SAME batch retries cleanly after
    space frees — partial garbage at the tail is overwritten, never
    served."""
    root = str(tmp_path / "flq")
    with ChaosDir(root) as chaos:
        st = FileLogStorage(os.path.join(root, "log"))
        st.init()
        st.append_entries([_entry(i, 0) for i in range(1, 6)], sync=True)
        base = st.last_log_index()
        chaos.set_quota(chaos.quota_state()[1] + 20)  # ~half an entry
        batch = [_entry(base + 1, 1), _entry(base + 2, 1)]
        try:
            st.append_entries(batch, sync=True)
            raise AssertionError("ENOSPC append reported success")
        except OSError:
            pass
        assert st.last_log_index() == base
        for i in range(1, base + 1):
            assert st.get_entry(i).data == _entry(i, 0).data
        chaos.clear_quota()
        st.append_entries(batch, sync=True)  # same batch, now fits
        assert st.last_log_index() == base + 2
        for e in batch:
            assert st.get_entry(e.id.index).data == e.data
        st.shutdown()
        # and the healed tail survives a reopen (no torn garbage kept)
        st2 = FileLogStorage(os.path.join(root, "log"))
        st2.init()
        assert st2.last_log_index() == base + 2
        st2.shutdown()


def test_filelog_shutdown_and_reopen_on_full_disk(tmp_path):
    """A store must SHUT DOWN and BOOT on a genuinely full disk: the
    non-sync watermark saves (init scan, clean shutdown) only advance a
    stale-LOW-safe optimization, so ENOSPC on ``synced.tmp`` must not
    propagate.  Caught by the 300s --disk-pressure --power-loss soak:
    the power-loss kill's graceful stop died mid-shutdown on the
    watermark write and the store never came back."""
    root = str(tmp_path / "flfull")
    with ChaosDir(root) as chaos:
        st = FileLogStorage(os.path.join(root, "log"))
        st.init()
        st.append_entries([_entry(i, 0) for i in range(1, 8)], sync=True)
        chaos.set_quota(chaos.quota_state()[1])  # zero headroom
        st.shutdown()                            # must not raise
        # boot on the still-full disk: init's watermark refresh is also
        # best-effort; the log itself is read back intact
        st2 = FileLogStorage(os.path.join(root, "log"))
        st2.init()
        assert st2.last_log_index() == 7
        for i in range(1, 8):
            assert st2.get_entry(i).data == _entry(i, 0).data
        st2.shutdown()
    # and with the quota lifted the watermark heals on the next cycle
    st3 = FileLogStorage(os.path.join(root, "log"))
    st3.init()
    assert st3.last_log_index() == 7
    st3.shutdown()


def test_meta_journal_close_on_full_disk(tmp_path):
    """MetaJournal.close() on a full disk: the fsync lands (durability
    holds), the watermark save is best-effort, close does not raise,
    and the values replay on reopen."""
    root = str(tmp_path / "mjfull")
    with ChaosDir(root) as chaos:
        j = MetaJournal(root)
        j.stage("g1", 7, PeerId.parse("127.0.0.1:1"))
        j.sync()
        chaos.set_quota(chaos.quota_state()[1])  # zero headroom
        try:
            # the staged append itself fails ENOSPC (the vote-save
            # handler surfaces that as a refused grant) — the landed
            # prefix is torn-tail garbage the replay discards
            j.stage("g2", 9, PeerId.parse("127.0.0.1:2"))
        except OSError:
            pass
        j.close()     # must not raise (watermark tmp hits ENOSPC)
    j2 = MetaJournal(root)
    term, voted = j2.get("g1")
    assert term == 7 and str(voted) == "127.0.0.1:1"
    j2.close()


def test_native_quota_mirror_enospc(tmp_path):
    """The native multilog's quota mirror: attach_quota installs the
    engine fault gate; appends past the journal budget fail ENOSPC,
    acked entries stay readable, clear_quota heals."""
    d = str(tmp_path / "natq")
    s = MultiLogStorage(d, "g")
    s.init()
    s.append_entries([_entry(i, 0) for i in range(1, 4)], sync=True)
    tracker = NativeJournalTracker(d)
    tracker.attach_quota(s.engine, limit_bytes=tracker._dir_usage() + 16)
    try:
        s.append_entries([_entry(4, 0)], sync=True)
        raise AssertionError("native append past journal budget")
    except OSError:
        pass
    assert s.last_log_index() == 3
    for i in range(1, 4):
        assert s.get_entry(i).data == _entry(i, 0).data
    tracker.clear_quota()
    s.append_entries([_entry(4, 0)], sync=True)
    assert s.get_entry(4).data == _entry(4, 0).data
    # burst mirror: whole-op seeded failures, rate 0 heals
    tracker.attach_quota(s.engine, burst_rate=1.0, seed=3)
    try:
        s.append_entries([_entry(5, 0)], sync=True)
        raise AssertionError("burst rate 1.0 admitted a native append")
    except OSError:
        pass
    tracker.attach_quota(s.engine, burst_rate=0.0)
    s.append_entries([_entry(5, 0)], sync=True)
    s.shutdown()


def test_snapshot_save_enospc_keeps_old_snapshot(tmp_path):
    """ENOSPC mid snapshot save: the previous snapshot stays loadable,
    the aborted temp is swept, and the save succeeds once space frees."""
    from tpuraft.rpc.messages import SnapshotMeta
    from tpuraft.storage.snapshot import LocalSnapshotStorage

    root = str(tmp_path / "snapq")
    with ChaosDir(root) as chaos:
        stor = LocalSnapshotStorage(os.path.join(root, "snap"))
        stor.init()
        w = stor.create()
        w.write_file("kv", b"gen1" * 50)
        stor.commit(w, SnapshotMeta(last_included_index=10,
                                    last_included_term=1))
        assert stor.open().load_meta().last_included_index == 10

        chaos.set_quota(chaos.quota_state()[1] + 30)
        w2 = stor.create()
        try:
            w2.write_file("kv", b"gen2" * 200)
            raise AssertionError("over-budget snapshot write admitted")
        except OSError:
            pass
        # old snapshot intact, correct bytes
        r = stor.open()
        assert r.load_meta().last_included_index == 10
        assert r.read_file("kv") == b"gen1" * 50

        chaos.clear_quota()
        stor.init()  # sweeps the aborted temp dir
        w3 = stor.create()
        w3.write_file("kv", b"gen2" * 200)
        stor.commit(w3, SnapshotMeta(last_included_index=20,
                                     last_included_term=1))
        assert stor.open().load_meta().last_included_index == 20


def test_snapshot_storage_init_sweeps_orphans(tmp_path):
    """init() removes crash-orphaned snapshot_<N> dirs: stale older
    dirs the post-commit prune never got to, and unreadable newer dirs
    whose manifest never became durable."""
    from tpuraft.rpc.messages import SnapshotMeta
    from tpuraft.storage.snapshot import LocalSnapshotStorage

    root = str(tmp_path / "sweep")
    stor = LocalSnapshotStorage(root)
    stor.init()
    w = stor.create()
    w.write_file("kv", b"live")
    stor.commit(w, SnapshotMeta(last_included_index=10,
                                last_included_term=1))
    # stale older dir (prune-after-replace never ran) + manifestless
    # newer dir (replace landed, manifest lost to the crash)
    os.makedirs(os.path.join(root, "snapshot_5"))
    with open(os.path.join(root, "snapshot_5", "kv"), "wb") as f:
        f.write(b"stale")
    os.makedirs(os.path.join(root, "snapshot_20"))

    stor2 = LocalSnapshotStorage(root)
    stor2.init()
    names = sorted(n for n in os.listdir(root) if n.startswith("snapshot_"))
    assert names == ["snapshot_10"], names
    assert stor2.open().read_file("kv") == b"live"

    # nothing loadable at all -> keep everything for forensics
    root2 = str(tmp_path / "sweep2")
    os.makedirs(os.path.join(root2, "snapshot_7"))
    s3 = LocalSnapshotStorage(root2)
    s3.init()
    assert os.path.isdir(os.path.join(root2, "snapshot_7"))


def test_meta_journal_enospc_mid_compaction(tmp_path):
    """ENOSPC during the journal's compaction rewrite must not fail the
    sync round or hurt the journal: values stay readable, the partial
    tmp is dropped, and compaction succeeds after space frees."""
    root = str(tmp_path / "mjq")
    with ChaosDir(root) as chaos:
        j = MetaJournal(root)
        j.COMPACT_MIN_BYTES = 512
        peer = PeerId.parse("10.0.0.1:80")
        # pile up garbage records well past the compaction threshold
        for t in range(1, 120):
            j.stage("g0", t, peer)
            j.stage("g1", t, peer)
        chaos.set_quota(chaos.quota_state()[1])  # zero headroom
        j.sync()   # fsync ok (bytes already staged); compaction dies
        assert j.get("g0") == (119, peer)
        assert j.get("g1") == (119, peer)
        assert not os.path.exists(os.path.join(root, "meta.jnl.tmp"))
        # journal still ACCEPTS overwrites of staged bytes... heal and
        # prove full service: stage + sync + eventual compaction
        chaos.clear_quota()
        j.stage("g0", 200, peer)
        j.sync()
        assert j.get("g0") == (200, peer)
        j.close()
        j2 = MetaJournal(root)
        assert j2.get("g0") == (200, peer)
        assert j2.get("g1") == (119, peer)
        j2.close()


def test_disk_budget_thresholds_hysteresis_resume():
    from tpuraft.util.health import (
        PRESSURE_FULL,
        PRESSURE_NEAR_FULL,
        PRESSURE_OK,
        DiskBudget,
        DiskBudgetOptions,
    )

    b = DiskBudget(DiskBudgetOptions(budget_bytes=1000, worsen_after=1,
                                     recover_after=2))
    b.note_append(500)
    assert b.evaluate() == PRESSURE_OK
    b.note_append(350)            # 850/1000 >= 0.80
    assert b.evaluate() == PRESSURE_NEAR_FULL
    b.note_append(100)            # 950/1000 >= 0.92
    assert b.evaluate() == PRESSURE_FULL
    # recovery is hysteretic: reclaim must PROVE space recover_after
    # consecutive rounds before pressure relaxes (then: one resume)
    b.note_reclaimed(500)         # 450/1000
    assert b.evaluate() == PRESSURE_FULL
    assert b.evaluate() == PRESSURE_OK
    c = b.counters()
    assert c["disk_pressure_resumes"] == 1
    assert c["disk_reclaimed_bytes"] == 500
    # reconcile re-bases the estimate (rmtree deletes the hot path
    # never saw), and set_budget adopts an operator resize
    b.reconcile(900)
    assert b.used_bytes() == 900
    b.set_budget(2000)
    assert b.evaluate() == PRESSURE_OK   # 900/2000: headroom again
    assert b.capacity_bytes() == 2000


def test_disk_budget_enospc_latch_pins_full():
    """An observed ENOSPC pins raw FULL for enospc_latch_rounds no
    matter what the byte estimate says — the errno outranks it."""
    from tpuraft.util.health import (
        PRESSURE_FULL,
        PRESSURE_OK,
        DiskBudget,
        DiskBudgetOptions,
    )

    b = DiskBudget(DiskBudgetOptions(budget_bytes=1000, worsen_after=1,
                                     recover_after=1, enospc_latch_rounds=2))
    b.note_append(10)             # estimate says: nearly empty
    assert b.evaluate() == PRESSURE_OK
    b.note_enospc()
    assert b.evaluate() == PRESSURE_FULL
    assert b.evaluate() == PRESSURE_FULL     # latch round 2
    assert b.evaluate() == PRESSURE_OK       # latch expired, estimate rules
    assert b.counters()["disk_enospc_events"] == 1
    assert b.counters()["disk_pressure_resumes"] == 1


def test_disk_budget_statvfs_view_counts_only_reachable_blocks():
    """Whole-filesystem mode (no explicit budget): a volume whose free
    blocks are mostly out of this process's reach (252 GiB of blocks,
    241 free, 19.4 available, 11 used) is 36% full as the store can use
    it — not "92% full", which shed every write on such a host.  A disk
    the process really has almost no room left on still reads FULL."""
    from types import SimpleNamespace

    from tpuraft.util.health import (
        PRESSURE_FULL,
        PRESSURE_OK,
        DiskBudget,
        DiskBudgetOptions,
        statvfs_usage,
    )

    gib = (1 << 30) // 4096

    def level(blocks, bfree, bavail):
        sv = SimpleNamespace(f_frsize=4096, f_blocks=int(blocks * gib),
                             f_bfree=int(bfree * gib),
                             f_bavail=int(bavail * gib))
        b = DiskBudget(DiskBudgetOptions(worsen_after=1))
        b.reconcile(*statvfs_usage(sv))
        return b.evaluate()

    assert level(252, 241, 19.4) == PRESSURE_OK
    assert level(252, 241, 0.5) == PRESSURE_FULL    # 11 used, 0.5 left
    assert level(252, 2, 2) == PRESSURE_FULL        # plainly full
    assert level(252, 200, 200) == PRESSURE_OK      # plainly empty


async def test_log_manager_enospc_flush_rolls_back_frontier(tmp_path):
    """Regression for the non-contiguous-append wedge the disk-pressure
    soak found: a flush that dies ENOSPC must fail its waiters AND roll
    the in-memory frontier back to what storage holds — otherwise the
    next append passes the in-memory contiguity check, trips storage's
    gap check, and the node is wedged in ERROR forever."""
    from tpuraft.errors import RaftException
    from tpuraft.storage.log_manager import LogManager

    root = str(tmp_path / "lmq")
    with ChaosDir(root) as chaos:
        st = FileLogStorage(os.path.join(root, "log"))
        lm = LogManager(st)
        await lm.init()
        await lm.append_entries_follower(
            0, 0, [_entry(i, 0, term=2) for i in range(1, 6)])
        assert lm.last_log_index() == 5
        chaos.set_quota(chaos.quota_state()[1] + 10)
        try:
            await lm.append_entries_follower(
                5, 2, [_entry(i, 0, term=2) for i in range(6, 9)])
            raise AssertionError("ENOSPC flush reported success")
        except RaftException:
            pass
        # frontier converged back onto storage: no phantom suffix
        assert lm.last_log_index() == st.last_log_index()
        # heal -> the SAME entries re-append cleanly (leader retry)
        chaos.clear_quota()
        base = lm.last_log_index()
        ok = await lm.append_entries_follower(
            base, 2, [_entry(i, 0, term=2) for i in range(base + 1, 9)])
        assert ok and lm.last_log_index() == 8
        assert lm.check_consistency().is_ok()
        for i in range(1, 9):
            assert lm.get_term(i) == 2
        await lm.shutdown()


def _filelog_quota_crash_lifetime(root: str, rng: random.Random,
                                  gens: int) -> int:
    """Seeded-crash matrix with quota faults layered in: every
    generation runs under a shifting byte budget (including seeded
    ENOSPC bursts), appends tolerate ENOSPC without model drift, and a
    power-loss crash ends the generation.  Invariants are the usual
    acked floor / staged ceiling / byte-match set."""
    first, entries, acked_last = 1, {}, 0

    def staged_last():
        return max(entries) if entries else first - 1

    with ChaosDir(root) as chaos:
        for gen in range(gens):
            chaos.clear_quota()
            chaos.set_enospc_burst(0.0)
            st = FileLogStorage(os.path.join(root, "log"),
                                segment_max_bytes=200)
            st.init()
            rf, rl = st.first_log_index(), st.last_log_index()
            assert rf == first, f"gen {gen}: first {rf} != {first}"
            assert acked_last <= rl <= staged_last(), \
                f"gen {gen}: last {rl} not in [{acked_last}, {staged_last()}]"
            for i in range(rf, rl + 1):
                e = st.get_entry(i)
                assert e is not None and e.data == entries[i], \
                    f"gen {gen}: entry {i} mismatch"
            for i in list(entries):
                if i > rl:
                    del entries[i]
            acked_last = rl

            # quota fault for this generation: tight budget, seeded
            # burst, or free-running (the original matrix)
            mode = rng.random()
            if mode < 0.4:
                chaos.set_quota(chaos.quota_state()[1]
                                + rng.randrange(0, 400))
            elif mode < 0.6:
                chaos.set_enospc_burst(0.3, seed=rng.randrange(1 << 30))

            for _ in range(rng.randrange(1, 5)):
                n = rng.randrange(1, 6)
                batch = [_entry(staged_last() + 1 + k, gen)
                         for k in range(n)]
                try:
                    st.append_entries(batch, sync=True)
                except OSError:
                    # ENOSPC: storage contract says the view advanced
                    # only to what landed whole — adopt ITS frontier
                    # (landed entries are staged, NOT acked: the batch
                    # fsync never ran)
                    landed = st.last_log_index()
                    for e in batch:
                        if e.id.index <= landed:
                            entries[e.id.index] = e.data
                    if rng.random() < 0.5:
                        chaos.clear_quota()
                        chaos.set_enospc_burst(0.0)
                    continue
                for e in batch:
                    entries[e.id.index] = e.data
                acked_last = staged_last()

            if rng.random() < 0.5:
                batch = [_entry(staged_last() + 1 + k, gen, term=2)
                         for k in range(rng.randrange(1, 4))]
                try:
                    st.append_entries(batch, sync=False)
                    for e in batch:
                        entries[e.id.index] = e.data
                except OSError:
                    landed = st.last_log_index()
                    for e in batch:
                        if e.id.index <= landed:
                            entries[e.id.index] = e.data

            plan = chaos.capture_crash(rng)   # power dies (quota live)
            # the faults die with the power: shutdown's own writes are
            # discarded by the image anyway, but they must not blow up
            # the harness on the still-armed quota
            chaos.clear_quota()
            chaos.set_enospc_burst(0.0)
            st.shutdown()
            chaos.apply_crash(plan)
        return chaos.crash_count


def test_filelog_quota_crash_matrix():
    import tempfile

    crashes = 0
    for seed in range(3):
        with tempfile.TemporaryDirectory() as tmp:
            crashes += _filelog_quota_crash_lifetime(
                os.path.join(tmp, f"qlog{seed}"),
                random.Random(4000 + seed), gens=20)
    assert crashes >= 60
