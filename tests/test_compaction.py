"""Journal at production scale (ISSUE 7): segment rotation, checkpoint
compaction, chain-aware resume/merge — proven by a property-based
crash-fuzzer and a mid-compaction chaos matrix.

The contract under test: however the journal is sliced (rotated segments,
checkpoints, zone-runner segment files) and wherever the process dies (torn
tail in any file, kill at any compaction stage), three views of history
agree bit-for-bit — the live registry, the chain replay
(``Workspace.from_journal`` = best checkpoint + tail), and the uncompacted
oracle (``replay_files`` over every archived segment + live tail).
"""

import json
import os
import tempfile

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.provenance import ProvenanceRegistry
from repro.provenance import (
    Journal,
    discover_chain,
    merge_segments,
    read_chain,
    read_records,
    replay_files,
    replay_journal,
    replay_segments,
)
from repro.runtime import ZonedProcessExecutor, fork_context
from repro.topology import Topology
from repro.workspace import Workspace

needs_fork = pytest.mark.skipif(
    fork_context() is None, reason="fork start method unavailable"
)

# scheduled CI runs raise this for a deeper fuzz (see .github/workflows)
FUZZ_EXAMPLES = int(os.environ.get("KOALJA_FUZZ_EXAMPLES", "20"))

STAGES = ("fold", "pre-rename", "post-rename", "mid-gc", "post-gc")


class _Kill(RuntimeError):
    """Simulated process death inside Journal.compact."""


def _kill_at(stage):
    def fault(s):
        if s == stage:
            raise _Kill(stage)

    return fault


# ---------------------------------------------------------------------------
# circuits + fingerprints
# ---------------------------------------------------------------------------


def _chain_ws(journal_path, topology=False, cache=False, **kw):
    """source -> normalize -> score, journaling (with rotation) to path."""
    ws = Workspace(
        "compacted",
        journal_path=str(journal_path),
        topology=topology,
        cache=cache,
        **kw,
    )
    norm = ws.task(
        lambda x: {"y": x / (np.linalg.norm(x) + 1e-9)},
        name="normalize", inputs=["x"], outputs=["y"],
    )
    score = ws.task(
        lambda y: {"s": float(y.sum())},
        name="score", inputs=["y"], outputs=["s"],
    )
    norm["y"] >> score["y"]
    return ws, norm, score


def _fp(registry, ledger=None, cache=None, docs=True):
    """Byte-identical equality oracle over the forensic stories: the full
    registry snapshot (AVs canonicalized by uid; visits already seq-sorted),
    optionally ledger totals and the memo table. ``next_seq`` is excluded —
    it is a counter watermark, not a story, and retirement legitimately
    leaves the live counter above a replayed one. ``docs=False`` strips
    travel documents: the journal restores them as of registration time
    (stamps added later are link-side mutations it does not track), so
    live-vs-replay comparisons must not require them; replay-vs-oracle
    comparisons keep them (both views are journal-derived)."""
    state = registry.snapshot_state()
    state.pop("next_seq", None)
    state["avs"] = sorted(state["avs"], key=lambda a: a["av"]["uid"])
    if not docs:
        for item in state["avs"]:
            item["av"] = {
                k: v for k, v in item["av"].items() if k != "travel_document"
            }
    blob = {"registry": state}
    if ledger is not None:
        blob["ledger"] = ledger.snapshot_state()
    if cache is not None:
        snap = cache.snapshot_state()
        snap["entries"] = sorted(snap["entries"], key=lambda e: e["key"])
        blob["cache"] = snap
    return json.dumps(blob, sort_keys=True, default=repr)


def _oracle_files(base, archive_dir):
    """The uncompacted oracle's inputs: every segment compaction archived,
    plus whatever is still on disk in the chain (rotated segments + live
    tail) — full history, no checkpoint."""
    files = []
    if os.path.isdir(archive_dir):
        files += sorted(
            os.path.join(archive_dir, n) for n in os.listdir(archive_dir)
        )
    chain = discover_chain(base)
    files += chain["segments"]
    if chain["live"]:
        files.append(chain["live"])
    return files


# ---------------------------------------------------------------------------
# rotation
# ---------------------------------------------------------------------------


class TestRotation:
    def test_rotates_to_numbered_segments_preserving_seq(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1, rotate_records=4)
        seqs = [j.append("anomaly", {"task": "t", "note": str(i)}) for i in range(14)]
        j.close()
        chain = discover_chain(str(tmp_path / "j.jsonl"))
        assert len(chain["segments"]) >= 2
        assert chain["live"] is not None
        for p in chain["segments"]:
            assert p.endswith(tuple(f".{i:04d}" for i in chain["segment_indices"]))
        # the chain read restores one gapless, sorted stream
        records, truncated, info = read_chain(str(tmp_path / "j.jsonl"))
        assert truncated == 0
        got = [r["seq"] for r in records]
        assert got == sorted(got) and len(set(got)) == len(got)
        notes = [r["data"]["note"] for r in records if r["kind"] == "anomaly"]
        assert notes == [str(i) for i in range(14)]
        assert seqs == sorted(seqs)

    def test_rotate_by_bytes(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1, rotate_bytes=400)
        for i in range(30):
            j.append("anomaly", {"task": "t", "note": f"pad-{i:03d}" * 4})
        j.close()
        chain = discover_chain(j.path)
        assert len(chain["segments"]) >= 2
        # every sealed segment respects the threshold order-of-magnitude
        for p in chain["segments"]:
            assert os.path.getsize(p) >= 400

    def test_rotation_never_spins_empty_segments(self, tmp_path):
        # a threshold smaller than one record must still make progress:
        # each sealed segment carries at least one non-header record
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1, rotate_bytes=1)
        for i in range(6):
            j.append("anomaly", {"task": "t", "note": str(i)})
        j.close()
        for p in discover_chain(j.path)["segments"]:
            rs, _ = read_records(p)
            assert any(r["kind"] != "meta" for r in rs)

    def test_env_knob_enables_rotation(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KOALJA_JOURNAL_ROTATE", "256")
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        assert j.rotate_bytes == 256
        for i in range(20):
            j.append("anomaly", {"task": "t", "note": f"row-{i}" * 4})
        j.close()
        assert len(discover_chain(j.path)["segments"]) >= 1

    def test_env_knob_rejects_garbage(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KOALJA_JOURNAL_ROTATE", "plenty")
        with pytest.raises(ValueError, match="KOALJA_JOURNAL_ROTATE"):
            Journal(tmp_path / "j.jsonl")

    def test_from_journal_discovers_rotated_chain(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, journal_rotate_records=6,
                                journal_flush_every_n=1)
        for i in range(4):
            ws.push(norm, x=np.arange(5.0) + i)
        ws.journal.flush()
        assert discover_chain(str(base))["segments"], "expected a rotation"
        ws2 = Workspace.from_journal(str(base))
        assert _fp(ws2.registry, docs=False) == _fp(ws.registry, docs=False)
        js = ws2.stats()["journal"]
        assert js["rehydrated"] and js["segments"] >= 2
        assert js["checkpoints"] == 0 and js["records_compacted"] == 0


# ---------------------------------------------------------------------------
# satellite 1: resume scans the whole chain
# ---------------------------------------------------------------------------


class TestResumeAfterRotation:
    def test_reopen_seeds_seq_from_rotated_segments(self, tmp_path):
        """Regression: the highest seq lives in a rotated segment when the
        live tail is young; resume must scan the chain, not just the tail."""
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1, rotate_records=3)
        last = 0
        for i in range(7):
            last = j.append("anomaly", {"task": "t", "note": str(i)})
        j.rotate()  # live tail now holds only the continuation header
        header_seq = last + 1
        j.close()
        j2 = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        assert j2.append("anomaly", {"task": "t", "note": "post"}) == header_seq + 1
        j2.close()
        records, truncated, _ = read_chain(j2.path)
        seqs = [r["seq"] for r in records]
        assert truncated == 0 and seqs == sorted(seqs) == list(range(header_seq + 2))

    def test_reopen_seeds_visit_seq_from_rotated_segments(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1, rotate_records=3)
        for i in range(5):
            j.append("visit", {"task": "t", "av_uid": f"a{i}", "event": "executed",
                               "timestamp": 1.0, "software_version": "v",
                               "note": "", "seq": 40 + i})
        j.rotate()
        j.close()
        j2 = Journal(tmp_path / "j.jsonl")
        assert j2.resumed_visit_seq == 44
        reg = ProvenanceRegistry()
        reg.bind_journal(j2)
        reg.log_visit("t", "a9", "executed", "v")
        assert reg.visitor_log("t")[-1]["seq"] == 45
        j2.close()

    def test_reopen_seeds_visit_seq_from_checkpoint(self, tmp_path):
        """After compaction the folded visits exist only inside the
        checkpoint; the restored registry counter is the high-water mark."""
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, journal_flush_every_n=1)
        ws.push(norm, x=np.arange(3.0))
        high = max(e["seq"] for t in ws.tasks() for e in ws.visitor_log(t))
        ws.compact_journal()
        ws.journal.close()
        j2 = Journal(str(base))
        assert j2.resumed_visit_seq >= high
        j2.close()

    def test_workspace_resume_after_rotation_keeps_orders(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, journal_rotate_records=5,
                                journal_flush_every_n=1)
        ws.push(norm, x=np.arange(4.0))
        ws.journal.close()
        ws2, norm2, _ = _chain_ws(base, journal_rotate_records=5,
                                  journal_flush_every_n=1)
        ws2.push(norm2, x=np.arange(4.0) + 1)
        ws2.journal.flush()
        replayed = replay_journal(str(base))
        # both processes' visits replay with a gapless total order per task
        for t in ("normalize", "score"):
            seqs = [e["seq"] for e in replayed.registry.visitor_log(t)]
            assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        # both identical runs journaled their visits; the second process's
        # live log holds only its own half
        own = sum(len(ws2.visitor_log(t)) for t in ("normalize", "score"))
        assert replayed.counts["visit"] == 2 * own


# ---------------------------------------------------------------------------
# satellite 2: stats over the whole chain
# ---------------------------------------------------------------------------


class TestJournalStats:
    def test_bytes_on_disk_sums_all_live_segments(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1, rotate_records=4)
        for i in range(12):
            j.append("anomaly", {"task": "t", "note": str(i)})
        s = j.stats()
        chain = discover_chain(j.path)
        expect = sum(
            os.path.getsize(p)
            for p in chain["segments"] + [chain["live"]]
        )
        assert s["bytes_on_disk"] == expect
        assert s["segments"] == len(chain["segments"]) + 1
        assert s["rotations"] == len(chain["segments"])
        assert s["bytes_reclaimed"] == 0 and s["checkpoints"] == 0
        j.close()

    def test_compaction_reports_reclaimed_bytes(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, journal_rotate_records=6,
                                journal_flush_every_n=1)
        for i in range(5):
            ws.push(norm, x=np.arange(4.0) + i)
        before = ws.journal.stats()["bytes_on_disk"]
        report = ws.compact_journal()
        s = ws.journal.stats()
        assert report["bytes_reclaimed"] > 0
        assert s["bytes_reclaimed"] == report["bytes_reclaimed"]
        assert s["checkpoints"] == 1 and s["compactions"] == 1
        assert s["records_compacted"] == report["records_folded"]
        assert s["segments"] == 1  # only the live tail survives
        # workspace stats surface the same numbers
        js = ws.stats()["journal"]
        assert js["checkpoints"] == 1 and js["records_compacted"] > 0
        assert js["bytes_on_disk"] < before + s["bytes_reclaimed"]
        ws.journal.close()


# ---------------------------------------------------------------------------
# compaction semantics
# ---------------------------------------------------------------------------


class TestCompaction:
    def test_checkpoint_plus_tail_equals_history(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        archive = str(tmp_path / "archive")
        ws, norm, _ = _chain_ws(base, journal_rotate_records=8,
                                journal_flush_every_n=1)
        for i in range(3):
            ws.push(norm, x=np.arange(4.0) + i)
        ws.compact_journal(archive_dir=archive)
        ws.push(norm, x=np.arange(4.0) + 99)  # tail records after the fold
        ws.journal.flush()
        live = _fp(ws.registry, docs=False)
        replayed = replay_journal(str(base))
        assert _fp(replayed.registry, docs=False) == live
        assert replayed.checkpoints == 1 and replayed.records_compacted > 0
        # the uncompacted oracle and the checkpointed replay agree on the
        # FULL state, travel documents included — byte-identical
        oracle = replay_files(_oracle_files(str(base), archive))
        assert _fp(oracle.registry) == _fp(replayed.registry)
        assert _fp(oracle.registry, docs=False) == live

    def test_ledger_and_topology_fold_into_checkpoint(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, topology=Topology.three_zone(),
                                journal_flush_every_n=1)
        for i in range(3):
            ws.push(norm, x=np.arange(6.0) + i, region="edge")
        ws.compact_journal()
        ws.push(norm, x=np.arange(6.0) + 50, region="edge")
        ws.journal.flush()
        replayed = replay_journal(str(base))
        assert replayed.ledger is not None
        assert _fp(replayed.registry, replayed.ledger, docs=False) == _fp(
            ws.registry, ws.ledger, docs=False
        )
        assert replayed.ledger.stats() == ws.ledger.stats()

    def test_memo_table_folds_with_overwrites_deduped(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        from repro.cache import MemoCache

        cache = MemoCache()
        cache.bind_journal(j)
        cache.insert("k1", {"software_version": "v1", "out_nbytes": {}})
        cache.insert("k1", {"software_version": "v2", "out_nbytes": {}})  # overwrite
        cache.insert("k2", {"software_version": "v1", "out_nbytes": {}})
        j.compact()
        ck = read_chain(j.path)[2]["checkpoint_data"]
        # superseded k1 record folded away: one entry per key survives
        assert sorted(e["key"] for e in ck["cache"]["entries"]) == ["k1", "k2"]
        replayed = replay_journal(j.path)
        assert replayed.cache is not None
        assert replayed.cache.lookup("k1")["software_version"] == "v2"
        assert _fp(ProvenanceRegistry(), cache=replayed.cache) == _fp(
            ProvenanceRegistry(), cache=cache
        )
        j.close()

    def test_memo_hits_survive_compaction_end_to_end(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, cache=None, journal_flush_every_n=1)
        x = np.arange(5.0)
        ws.push(norm, x=x)
        ws.push(norm, x=x)  # memo hit
        assert ws.stats()["sustainability"]["executions_avoided"] > 0
        ws.compact_journal()
        ws.journal.flush()
        replayed = replay_journal(str(base))
        assert _fp(replayed.registry, docs=False) == _fp(ws.registry, docs=False)
        assert replayed.cache is not None and len(
            replayed.cache.snapshot_state()["entries"]
        ) == len(ws.manager.cache.snapshot_state()["entries"])

    def test_retirement_bounds_state_and_all_views_agree(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        archive = str(tmp_path / "archive")
        ws, norm, _ = _chain_ws(base, journal_rotate_records=10,
                                journal_flush_every_n=1)
        for i in range(4):
            ws.push(norm, x=np.arange(4.0) + i)
        # evict the oldest normalize output: its payload is gone for good
        victim = ws.registry.all_avs()[0]
        ws.store.evict_local(ws.registry.get_av(victim).uri)
        report = ws.compact_journal(retire_evicted=True, archive_dir=archive)
        assert victim not in ws.registry.all_avs()
        assert victim not in [
            a["av"]["uid"]
            for a in read_chain(str(base))[2]["checkpoint_data"]["registry"]["avs"]
        ]
        live = _fp(ws.registry, docs=False)
        replayed = replay_journal(str(base))
        assert _fp(replayed.registry, docs=False) == live
        # the full-history oracle applies the journaled `retired` marker and
        # lands on the same story — deliberate forgetting, not divergence
        oracle = replay_files(_oracle_files(str(base), archive))
        assert _fp(oracle.registry) == _fp(replayed.registry)
        assert report["avs_live"] == len(ws.registry.all_avs())

    def test_repeated_rounds_keep_disk_bounded(self, tmp_path):
        """The production-scale claim in miniature: steady push+evict+compact
        rounds must not grow the on-disk chain monotonically."""
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, journal_rotate_records=16,
                                journal_flush_every_n=1)
        sizes = []
        for r in range(6):
            for i in range(4):
                ws.push(norm, x=np.arange(4.0) + 10 * r + i)
            for uid in ws.registry.all_avs()[:-4]:
                av = ws.registry.get_av(uid)
                if not av.uri.startswith("ghost://"):
                    ws.store.evict_local(av.uri)
            ws.compact_journal(retire_evicted=True)
            sizes.append(ws.journal.stats()["bytes_on_disk"])
        assert max(sizes[2:]) <= 2 * sizes[1], f"journal grew unbounded: {sizes}"
        assert _fp(replay_journal(str(base)).registry, docs=False) == _fp(
            ws.registry, docs=False
        )

    def test_archived_rounds_replay_as_the_full_history(self, tmp_path):
        """Eight rounds of 40 fresh pushes, rotating every 256 records,
        each round evicting all but four AVs and compacting into an archive:
        2,907 records fold away, the restart reads a checkpoint and one
        record, and that replay is byte-identical to the 2,908-record
        oracle over the archive; the last four rounds keep at most twice
        round one's bytes on disk."""
        base = str(tmp_path / "ws.jsonl")
        archive = str(tmp_path / "archive")
        ws, norm, _ = _chain_ws(base, journal_rotate_records=256)
        sizes = []
        for r in range(8):
            for i in range(40):
                ws.push(norm, x=np.full(64, float(r * 40 + i), np.float32))
            for uid in ws.registry.all_avs()[:-4]:
                av = ws.registry.get_av(uid)
                if not av.uri.startswith("ghost://"):
                    ws.store.evict_local(av.uri)
            ws.compact_journal(retire_evicted=True, archive_dir=archive)
            sizes.append(ws.journal.stats()["bytes_on_disk"])
        ws.journal.flush()
        oracle = replay_files(_oracle_files(base, archive))
        restored = replay_journal(base)
        assert ws.journal.stats()["records_compacted"] == 2907
        assert (oracle.records, restored.records) == (2908, 2)
        assert _fp(restored.registry) == _fp(oracle.registry)
        assert _fp(restored.registry, docs=False) == _fp(ws.registry, docs=False)
        assert max(sizes[4:]) <= 2 * sizes[0], f"journal grew unbounded: {sizes}"

    def test_zone_segment_journal_refuses_compact(self, tmp_path):
        seg = Journal(tmp_path / "m.jsonl.seg-a", segment="a", flush_every_n=1)
        seg.append("anomaly", {"task": "t", "note": "x"}, seq=5)
        with pytest.raises(ValueError, match="segment"):
            seg.compact()
        seg.close()


# ---------------------------------------------------------------------------
# satellite 4: chaos matrix — die at every compaction stage
# ---------------------------------------------------------------------------


class TestMidCompactionChaos:
    def _grown(self, tmp_path):
        base = tmp_path / "ws.jsonl"
        ws, norm, _ = _chain_ws(base, journal_rotate_records=6,
                                journal_flush_every_n=1)
        for i in range(4):
            ws.push(norm, x=np.arange(4.0) + i)
        ws.journal.flush()
        return ws, str(base)

    @pytest.mark.parametrize("stage", STAGES)
    def test_kill_at_stage_leaves_replayable_chain(self, tmp_path, stage):
        ws, base = self._grown(tmp_path)
        live = _fp(ws.registry, docs=False)
        with pytest.raises(_Kill):
            ws.journal.compact(fault=_kill_at(stage))
        # whatever mix of old segments / tmp file / fresh checkpoint the
        # kill stranded on disk, the chain replays to the same story
        replayed = replay_journal(base)
        assert _fp(replayed.registry, docs=False) == live, \
            f"divergence after {stage} kill"
        # and a restarted journal can resume on top of the debris
        ws.journal.close()
        j2 = Journal(base, flush_every_n=1)
        nxt = j2.append("anomaly", {"task": "t", "note": "post-crash"})
        j2.close()
        records, _, _ = read_chain(base)
        seqs = [r["seq"] for r in records]
        assert nxt == max(seqs) and seqs == sorted(seqs)

    @pytest.mark.parametrize("stage", STAGES)
    def test_compact_retry_after_kill_converges(self, tmp_path, stage):
        ws, base = self._grown(tmp_path)
        live = _fp(ws.registry, docs=False)
        with pytest.raises(_Kill):
            ws.journal.compact(fault=_kill_at(stage))
        report = ws.journal.compact()  # the restarted process tries again
        assert report.get("noop") or report["checkpoint"]
        chain = discover_chain(base)
        assert len(chain["checkpoints"]) <= 1  # older/partial ones GC'd
        assert not chain["segments"]
        assert _fp(replay_journal(base).registry, docs=False) == live
        ws.journal.close()

    def test_abandoned_tmp_checkpoint_is_ignored(self, tmp_path):
        ws, base = self._grown(tmp_path)
        with open(base + ".ckpt-999999.tmp", "w") as fh:
            fh.write('{"seq": 999999, "kind": "checkpoint", "data": {')
        assert _fp(replay_journal(base).registry, docs=False) == _fp(
            ws.registry, docs=False
        )
        ws.journal.close()

    def test_torn_checkpoint_file_falls_back(self, tmp_path):
        """A damaged published checkpoint must not poison the replay: the
        reader skips it and falls back to older checkpoints / raw history."""
        ws, base = self._grown(tmp_path)
        live = _fp(ws.registry, docs=False)
        ws.journal.compact(archive_dir=str(tmp_path / "arch"))
        ck = discover_chain(base)["checkpoints"][0]
        with open(ck, "w") as fh:
            fh.write('{"seq": 1, "kind": "checkpoint", "da')
        replayed = replay_journal(base)
        # the good history was archived, so the fallback view is tail-only —
        # but it must not raise, and a full-file oracle still reconstructs
        oracle = replay_files(
            _oracle_files(base, str(tmp_path / "arch"))
        )
        assert _fp(oracle.registry, docs=False) == live
        assert replayed.truncated >= 0  # replay completed without raising
        ws.journal.close()


# ---------------------------------------------------------------------------
# satellite 3: merge/replay over rotated mains + zone segments
# ---------------------------------------------------------------------------


class TestZonedChainMerge:
    def test_revoked_window_spanning_segment_rotation_boundary(self, tmp_path):
        """A dead runner's reserved window whose records straddle the zone
        segment's own rotation boundary must vanish from the merge whole —
        both the part in the sealed segment and the part in its live tail."""
        base = str(tmp_path / "m.jsonl")
        main = Journal(base, workspace="w", flush_every_n=1, rotate_records=3)
        main.append("task", {"task": "t", "inputs": [], "outputs": [],
                             "version": "v"})
        main.append("edge", {"src": "t", "relation": "precedes", "dst": "u"})
        # main has rotated at least once by now (3-record threshold)
        dead = main.reserve(4)
        good = main.reserve(2)
        seg = Journal(base + ".seg-z", workspace="w", segment="z",
                      flush_every_n=1, rotate_records=3)
        for i in range(4):  # rotates after the 3rd record: window straddles
            seg.append("anomaly", {"task": "t", "note": f"orphan-{i}"},
                       seq=dead + i)
        assert discover_chain(seg.path)["segments"], "expected seg rotation"
        for i in range(2):
            seg.append("anomaly", {"task": "t", "note": f"kept-{i}"},
                       seq=good + i)
        seg.close()
        main.append("revoked", {"task": "t", "start": dead, "count": 4})
        main.close()
        assert discover_chain(base)["segments"], "expected main rotation"
        records, truncated = merge_segments(base, [base + ".seg-z"])
        assert truncated == 0
        notes = [r["data"]["note"] for r in records if r["kind"] == "anomaly"]
        assert notes == ["kept-0", "kept-1"]
        seqs = [r["seq"] for r in records]
        assert seqs == sorted(seqs)

    def test_merge_over_compacted_main_drops_folded_zone_records(self, tmp_path):
        base = str(tmp_path / "m.jsonl")
        main = Journal(base, workspace="w", flush_every_n=1)
        main.append("task", {"task": "t", "inputs": [], "outputs": [],
                             "version": "v"})
        w = main.reserve(3)
        seg = Journal(base + ".seg-a", workspace="w", segment="a",
                      flush_every_n=1)
        for i in range(3):
            seg.append(
                "visit",
                {"task": "t", "av_uid": f"a{i}", "event": "executed",
                 "timestamp": float(i), "software_version": "v", "note": "",
                 "seq": w + i},
                seq=w + i,
            )
        seg.close()
        before = replay_segments(base, [base + ".seg-a"])
        main.compact(segment_paths=[base + ".seg-a"])
        after = replay_segments(base, [base + ".seg-a"])
        assert _fp(after.registry) == _fp(before.registry)
        # the folded zone visits live in the checkpoint now, counted once
        assert after.counts.get("visit") == before.counts.get("visit") == 3
        main.close()

    @needs_fork
    def test_zoned_run_with_rotation_merges_to_live_registry(self, tmp_path):
        """Integration: a real multi-process zoned run with rotation enabled
        on every journal (main + zone segments), including a killed runner's
        revoked window, still merges bit-identically to the live registry."""
        jpath = str(tmp_path / "zp.jsonl")
        topo = Topology.three_zone()
        ex = ZonedProcessExecutor(max_workers=2, retry_budget=2)
        ws = Workspace(
            "zones", executor=ex, cache=False, topology=topo, placement="pin",
            journal_path=jpath, journal_flush_every_n=1,
            journal_rotate_records=8,
        )
        zones = ("edge", "device")
        src = ws.task(lambda x: {"out": x}, name="src", inputs=["x"],
                      outputs=["out"]).place("cloud")
        red = ws.task(
            lambda **kw: {"total": float(sum(np.sum(v) for v in kw.values()))},
            name="reduce", inputs=[f"a_{z}" for z in zones], outputs=["total"],
        ).place("cloud")
        for z in zones:
            t = ws.task(lambda x, z=z: {"out": x * 2.0}, name=f"prod_{z}",
                        inputs=["x"], outputs=["out"]).place(z)
            src["out"] >> t["x"]
            t["out"] >> red[f"a_{z}"]
        rng = np.random.RandomState(3)
        try:
            for _ in range(2):
                ws.push("src", x=rng.randn(16).astype(np.float32))
            ex.kill_runner("edge")
            for _ in range(3):
                ws.push("src", x=rng.randn(16).astype(np.float32))
            ws.journal.flush()
            assert discover_chain(jpath)["segments"], "main never rotated"
            replayed = replay_segments(jpath, ex.segment_paths())
            assert _fp(replayed.registry, replayed.ledger, docs=False) == _fp(
                ws.registry, ws.ledger, docs=False
            )
            # from_journal takes the same [main, *segments] shape
            ws2 = Workspace.from_journal([jpath, *ex.segment_paths()])
            assert _fp(ws2.registry, docs=False) == _fp(ws.registry, docs=False)
        finally:
            ex.shutdown()


# ---------------------------------------------------------------------------
# the headline: property-based crash fuzzer
# ---------------------------------------------------------------------------


class TestCrashFuzzer:
    @settings(max_examples=FUZZ_EXAMPLES, deadline=None)
    @given(st.data())
    def test_any_schedule_any_kill_point_replays_identically(self, data):
        """Random pipeline activity, random rotation thresholds, random
        compaction/retirement schedules, random kill points (a fault at any
        compaction stage, then a torn tail in any chain file): the live
        registry, the chain replay, and the uncompacted oracle must agree
        byte-for-byte."""
        with tempfile.TemporaryDirectory() as tmp:
            base = os.path.join(tmp, "fuzz.jsonl")
            archive = os.path.join(tmp, "archive")
            rotate = data.draw(st.integers(min_value=3, max_value=12))
            ws, norm, _ = _chain_ws(base, journal_rotate_records=rotate,
                                    journal_flush_every_n=1)
            killed = False
            for r in range(data.draw(st.integers(min_value=1, max_value=3))):
                for p in range(data.draw(st.integers(min_value=1, max_value=3))):
                    ws.push(norm, x=np.arange(4.0) + 10 * r + p)
                action = data.draw(st.integers(min_value=0, max_value=3))
                if action == 1:
                    ws.compact_journal(archive_dir=archive)
                elif action == 2:
                    uids = ws.registry.all_avs()
                    victim = uids[
                        data.draw(st.integers(min_value=0, max_value=len(uids) - 1))
                    ]
                    av = ws.registry.get_av(victim)
                    if not av.uri.startswith("ghost://"):
                        ws.store.evict_local(av.uri)
                    ws.compact_journal(retire_evicted=True, archive_dir=archive)
                elif action == 3 and not killed:
                    stage = STAGES[
                        data.draw(st.integers(min_value=0, max_value=len(STAGES) - 1))
                    ]
                    with pytest.raises(_Kill):
                        ws.journal.compact(
                            archive_dir=archive, fault=_kill_at(stage)
                        )
                    killed = True  # the process "died"; later rounds are the restart
            ws.journal.flush()
            # the final kill: a torn tail at a random point in the chain
            chain = discover_chain(base)
            targets = ([chain["live"]] if chain["live"] else []) + chain["segments"]
            if data.draw(st.integers(min_value=0, max_value=2)) and targets:
                idx = data.draw(
                    st.integers(min_value=0, max_value=len(targets) - 1)
                )
                with open(targets[idx], "a", encoding="utf-8") as fh:
                    fh.write('{"seq": 999999, "kind": "vis')
            live = _fp(ws.registry, docs=False)
            replayed = replay_journal(base)
            assert _fp(replayed.registry, docs=False) == live, \
                "chain replay diverged from the live registry"
            oracle = replay_files(_oracle_files(base, archive))
            assert _fp(oracle.registry) == _fp(replayed.registry), \
                "uncompacted oracle diverged from the checkpointed replay"
            assert _fp(oracle.registry, docs=False) == live
            # a restart over the debris must resume, not corrupt: reopening
            # changes nothing about the story
            ws.journal.close()
            j2 = Journal(base, flush_every_n=1)
            j2.close()
            assert _fp(replay_journal(base).registry, docs=False) == live
