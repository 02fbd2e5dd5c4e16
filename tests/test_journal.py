"""Durable provenance journal (ISSUE 5): append-only write-through, seq
ordering, crash-safe `Workspace.from_journal` rehydration (torn final line
included), drop_oldest forensics, and the registry read-path thread-safety
sweep under ConcurrentExecutor."""

import json
import os
import threading

import numpy as np
import pytest

from repro.core.provenance import ProvenanceRegistry
from repro.provenance import (
    Journal,
    JournalCorruptError,
    read_chain,
    read_records,
    replay_journal,
)
from repro.topology import Topology
from repro.workspace import ConcurrentExecutor, Workspace


# ---------------------------------------------------------------------------
# circuits
# ---------------------------------------------------------------------------


def _chain_ws(tmp_path, name="journaled", topology=False, **kw):
    """source -> normalize -> score, journaling to tmp_path/<name>.jsonl."""
    ws = Workspace(
        name,
        journal_path=str(tmp_path / f"{name}.jsonl"),
        topology=topology,
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


def _forensics(ws, av_uid, task="score"):
    """The rehydration equality contract: the three stories + visits_of."""
    return {
        "lineage": ws.registry.lineage(av_uid),
        "visitor_log": ws.visitor_log(task),
        "design_map": ws.design_map(),
        "design_map_text": ws.design_map_text(),
        "visits_of": ws.registry.visits_of(av_uid),
    }


# ---------------------------------------------------------------------------
# the journal file itself
# ---------------------------------------------------------------------------


class TestJournalFile:
    def test_append_assigns_monotonic_seq(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        seqs = [j.append("visit", {"n": i}) for i in range(5)]
        assert seqs == sorted(seqs) and len(set(seqs)) == 5
        j.close()
        records, truncated = read_records(j.path)
        assert truncated == 0
        assert [r["seq"] for r in records] == list(range(len(records)))
        assert records[0]["kind"] == "meta"  # file header

    def test_flush_every_n_batches_fsync(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=10)
        for i in range(25):
            j.append("visit", {"n": i})
        # 26 records incl. the meta header -> 2 full batches of 10
        assert j.flushes == 2
        j.flush()
        assert j.flushes == 3
        s = j.stats()
        assert s["records_written"] == 26
        assert s["bytes_on_disk"] > 0
        assert s["flush_every_n"] == 10
        j.close()

    def test_reopen_resumes_seq(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        last = j.append("visit", {"n": 0})
        j.close()
        j2 = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        assert j2.append("visit", {"n": 1}) == last + 1
        j2.close()

    def test_torn_final_line_is_dropped(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        j.append("visit", {"n": 0})
        j.close()
        with open(j.path, "a") as f:
            f.write('{"seq": 2, "kind": "visit", "da')  # crash mid-write
        records, truncated = read_records(j.path)
        assert truncated == 1
        assert [r["seq"] for r in records] == [0, 1]

    def test_reopen_over_torn_tail_truncates_not_glues(self, tmp_path):
        """Resuming past a crash must drop the torn line before appending:
        'a' mode would glue the next record onto the partial tail, losing it
        (last line) or corrupting the whole journal (mid-file)."""
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        j.append("visit", {"n": 0})
        j.close()
        with open(j.path, "a") as f:
            f.write('{"seq": 2, "kind": "visit", "da')  # crash mid-write
        j2 = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        s1 = j2.append("visit", {"n": 1})
        s2 = j2.append("visit", {"n": 2})
        j2.close()
        records, truncated = read_records(j2.path)
        assert truncated == 0  # the torn tail is gone, nothing glued
        assert [r["seq"] for r in records] == [0, 1, s1, s2]

    def test_mid_file_corruption_raises(self, tmp_path):
        path = tmp_path / "j.jsonl"
        path.write_text('{"seq": 0, "kind": "meta", "data": {}}\nnot json\n'
                        '{"seq": 2, "kind": "visit", "data": {}}\n')
        with pytest.raises(JournalCorruptError):
            read_records(str(path))

    def test_non_json_payloads_degrade_to_repr(self, tmp_path):
        j = Journal(tmp_path / "j.jsonl", flush_every_n=1)
        j.append("av", {"weird": object()})  # default=repr, never raises
        j.close()
        records, _ = read_records(j.path)
        assert "object object" in records[-1]["data"]["weird"]


# ---------------------------------------------------------------------------
# write-through: one typed record per event
# ---------------------------------------------------------------------------


class TestWriteThrough:
    def test_registry_cache_events_journaled(self, tmp_path):
        ws, norm, score = _chain_ws(tmp_path)
        x = np.arange(8.0)
        ws.push(norm, x=x)
        ws.push(norm, x=x)  # memo hits
        ws.registry.record_anomaly("score", "drift detected")
        ws.journal.flush()
        kinds = [r["kind"] for r in read_chain(ws.journal.path)[0]]
        for kind in ("meta", "task", "edge", "av", "visit", "cache_hit", "anomaly"):
            assert kind in kinds, f"missing journal record kind {kind!r}"

    def test_ledger_and_topology_journaled(self, tmp_path):
        ws, norm, score = _chain_ws(
            tmp_path, topology=Topology.three_zone(), placement="pin"
        )
        ws.push(norm, x=np.arange(8.0))
        ws.journal.flush()
        records = read_chain(ws.journal.path)[0]
        kinds = [r["kind"] for r in records]
        assert "topology" in kinds and "ledger" in kinds
        spec = next(r["data"] for r in records if r["kind"] == "topology")
        assert Topology.from_spec(spec).describe() == ws.topology.describe()

    def test_stats_surface(self, tmp_path):
        ws, norm, _ = _chain_ws(tmp_path)
        ws.push(norm, x=np.arange(4.0))
        s = ws.stats()["journal"]
        assert s["records_written"] > 0
        assert s["bytes_on_disk"] > 0
        assert {"flushes", "flush_every_n", "path", "next_seq"} <= set(s)

    def test_env_knob_creates_tempdir_journal(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KOALJA_JOURNAL", str(tmp_path / "envdir"))
        ws = Workspace("envy")
        t = ws.task(lambda x: {"y": x + 1}, name="t", inputs=["x"], outputs=["y"])
        ws.push(t, x=1)
        assert ws.journal is not None
        assert ws.journal.path.startswith(str(tmp_path / "envdir"))
        assert ws.stats()["journal"]["records_written"] > 0

    def test_env_off_and_explicit_false(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KOALJA_JOURNAL", "0")
        assert Workspace("off").journal is None
        monkeypatch.setenv("KOALJA_JOURNAL", "1")
        assert Workspace("forced-off", journal_path=False).journal is None


# ---------------------------------------------------------------------------
# rehydration: Workspace.from_journal
# ---------------------------------------------------------------------------


class TestFromJournal:
    def test_stories_identical_after_restart(self, tmp_path):
        ws, norm, score = _chain_ws(tmp_path)
        x = np.arange(16.0)
        ws.push(norm, x=x)
        av = ws.push(norm, x=x)[score].av("s")  # second push memo-hits
        live = _forensics(ws, av.uid)
        ws.journal.close()

        ws2 = Workspace.from_journal(ws.journal.path)
        assert ws2.name == "journaled"
        assert _forensics(ws2, av.uid) == live
        # the memoized lineage still reconstructs the original run
        lin = ws2.registry.lineage(av.uid)
        assert lin["cache_hit"] is True and lin["memo_of"]["parents"]

    def test_ledger_identical_after_restart(self, tmp_path):
        ws, norm, score = _chain_ws(
            tmp_path, topology=Topology.three_zone(), placement="pin"
        )
        norm.place("edge")
        score.place("cloud")
        ws.push(norm, x=np.arange(64.0))
        live = ws.stats()["topology"]["ledger"]
        assert live["bytes_moved_crosszone"] > 0  # the run must be non-trivial
        ws.journal.close()

        ws2 = Workspace.from_journal(ws.journal.path)
        assert ws2.stats()["topology"]["ledger"] == live
        assert ws2.ledger.stats() == live

    def test_crash_mid_write_keeps_prefix(self, tmp_path):
        """ISSUE 5 acceptance: a partial final JSONL line (killed mid-run)
        must not poison rehydration — the intact prefix answers exactly."""
        ws, norm, score = _chain_ws(tmp_path)
        av = ws.push(norm, x=np.arange(8.0))[score].av("s")
        live = _forensics(ws, av.uid)
        ws.journal.close()
        with open(ws.journal.path, "a") as f:
            f.write('{"seq": 424242, "kind": "visit", "data": {"task": "sco')

        ws2 = Workspace.from_journal(ws.journal.path)
        assert _forensics(ws2, av.uid) == live
        assert ws2.stats()["journal"]["truncated_lines"] == 1

    def test_rehydrated_registry_continues_seq(self, tmp_path):
        ws, norm, score = _chain_ws(tmp_path)
        ws.push(norm, x=np.arange(4.0))
        max_seq = max(e["seq"] for e in ws.visitor_log(score))
        ws.journal.close()
        ws2 = Workspace.from_journal(ws.journal.path)
        ws2.registry.log_visit("score", "-", "anomaly", "v", note="post-restart")
        assert ws2.visitor_log("score")[-1]["seq"] > max_seq

    def test_rehydration_never_rejournals(self, tmp_path, monkeypatch):
        monkeypatch.setenv("KOALJA_JOURNAL", "1")  # even with the env knob on
        ws, norm, _ = _chain_ws(tmp_path)
        ws.push(norm, x=np.arange(4.0))
        ws.journal.close()
        ws2 = Workspace.from_journal(ws.journal.path)
        assert ws2.journal is None
        assert ws2.registry.journal is None

    def test_resumed_run_keeps_visit_seq_total_order(self, tmp_path):
        """A second process journaling to the same path must not restart
        entry seqs at 0 — replayed visits_of would interleave its events
        among the first run's."""
        path = tmp_path / "resume.jsonl"
        for run in range(2):
            ws = Workspace("resumed", journal_path=str(path))
            t = ws.task(
                lambda x: {"y": x + 1}, name="t", inputs=["x"], outputs=["y"]
            )
            ws.push(t, x=float(run))
            ws.journal.close()
        rep = replay_journal(str(path))
        seqs = [e["seq"] for e in rep.registry.visitor_log("t")]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)

    def test_resumed_topology_run_keeps_prior_ledger_charges(self, tmp_path):
        """A resumed run re-announces its topology spec; replay must keep
        the ledger charges accumulated from the pre-restart records."""
        path = tmp_path / "ledger-resume.jsonl"
        per_run = []
        for run in range(2):
            ws, norm, score = _chain_ws(
                tmp_path, name="lr", topology=Topology.three_zone(), placement="pin"
            )
            ws._journal.close()  # _chain_ws made its own; re-point at `path`
            from repro.provenance import Journal

            ws._journal = Journal(str(path), workspace="lr")
            norm.place("edge")
            score.place("cloud")
            ws.push(norm, x=np.arange(64.0) + run)  # fresh content: no memo
            per_run.append(ws.stats()["topology"]["ledger"]["bytes_moved_crosszone"])
            ws.journal.close()
        rep = replay_journal(str(path))
        assert rep.ledger.stats()["bytes_moved_crosszone"] == sum(per_run)

    def test_replay_counts(self, tmp_path):
        ws, norm, _ = _chain_ws(tmp_path)
        x = np.arange(4.0)
        ws.push(norm, x=x)
        ws.push(norm, x=x)
        ws.journal.close()
        rep = replay_journal(ws.journal.path)
        assert rep.counts["task"] == 2 and rep.counts["edge"] == 1
        assert rep.counts["cache_hit"] == 2  # one per memo-hitting task


# ---------------------------------------------------------------------------
# ordering: visits_of by seq, not wall clock
# ---------------------------------------------------------------------------


class TestSeqOrdering:
    def test_visits_of_orders_by_seq_on_tied_clocks(self):
        reg = ProvenanceRegistry()
        for i in range(10):
            reg.log_visit(f"t{i}", "av-x", "arrived", "v")
        # clobber every timestamp to one tick: the old timestamp sort had
        # nothing left to order by
        with reg._lock:
            for entries in reg._visitor_logs.values():
                for e in entries:
                    e.timestamp = 1234.5
        tasks = [v["task"] for v in reg.visits_of("av-x")]
        assert tasks == [f"t{i}" for i in range(10)]
        seqs = [v["seq"] for v in reg.visits_of("av-x")]
        assert seqs == sorted(seqs)

    def test_visitor_entries_carry_monotonic_seq(self):
        ws = Workspace("seq")
        t = ws.task(lambda x: {"y": x}, name="t", inputs=["x"], outputs=["y"])
        for i in range(3):
            ws.push(t, x=i)
        seqs = [e["seq"] for e in ws.visitor_log(t)]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


# ---------------------------------------------------------------------------
# drop_oldest forensics (no more silent disappearance)
# ---------------------------------------------------------------------------


class TestDropForensics:
    def _offer_through(self, ws, n=3):
        from repro.core.av import AnnotatedValue

        mgr = ws.manager
        link = mgr.pipeline.tasks["slow"].in_links["x"]
        avs = [AnnotatedValue.produce(f"h{i}", f"u{i}", "src", "v") for i in range(n)]
        for av in avs:
            mgr.registry.register_av(av)
            link.offer(av, software_version="v")
        return avs

    def _ring_ws(self, **ws_kwargs):
        ws = Workspace("ring", **ws_kwargs)
        src = ws.source(lambda: {"x": 0.0}, name="src", outputs=["x"])
        slow = ws.task(
            lambda x: {"y": x}, name="slow", inputs=["x[8]"], outputs=["y"]
        )
        ws.wire(src["x"], slow["x"], capacity=1, overflow="drop_oldest")
        return ws

    def test_drop_logs_visit_and_stamps_traveller(self):
        ws = self._ring_ws()
        avs = self._offer_through(ws, n=3)
        log = ws.visitor_log("slow")
        dropped = [e for e in log if e["event"] == "dropped"]
        assert [e["av_uid"] for e in dropped] == [avs[0].uid, avs[1].uid]
        assert "drop_oldest" in dropped[0]["note"]
        # the traveller log records the disappearance too
        journey = [(s["task"], s["event"]) for s in ws.traveller_log(avs[0])]
        assert journey[-1][1] == "dropped"
        # and the counter still agrees
        assert ws.manager.pipeline.tasks["slow"].in_links["x"].avs_dropped == 2

    def test_drop_survives_restart_via_journal(self, tmp_path):
        ws = self._ring_ws(journal_path=str(tmp_path / "ring.jsonl"))
        avs = self._offer_through(ws, n=2)
        ws.journal.close()
        ws2 = Workspace.from_journal(ws.journal.path)
        events = [(e["event"], e["av_uid"]) for e in ws2.visitor_log("slow")]
        assert ("dropped", avs[0].uid) in events


# ---------------------------------------------------------------------------
# thread-safety sweep: forensic reads under a concurrent writer
# ---------------------------------------------------------------------------


class TestConcurrentReads:
    def test_lineage_under_concurrent_waves(self):
        """Hammer every read path while an 8-wide ConcurrentExecutor circuit
        registers AVs; the unlocked reads died with 'dictionary changed size
        during iteration' or KeyError mid-lineage."""
        ws = Workspace("stress", executor=ConcurrentExecutor(max_workers=8))
        cam = ws.source(
            lambda: {"x": np.random.randn(32)}, name="cam", outputs=["x"]
        )
        for i in range(8):
            t = ws.task(
                lambda x, i=i: {"y": float(np.sum(x)) + i},
                name=f"t{i}", inputs=["x"], outputs=["y"],
            )
            cam["x"] >> t["x"]

        errors: list = []
        stop = threading.Event()

        def hammer():
            reg = ws.registry
            while not stop.is_set():
                try:
                    for uid in reg.all_avs():
                        reg.lineage(uid)
                        reg.visits_of(uid)
                    reg.overhead_bytes()
                    reg.design_map()
                    ws.design_map_text()
                except Exception as e:  # pragma: no cover - the regression
                    errors.append(e)
                    return

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            for _ in range(40):
                ws.sample(cam)
        finally:
            stop.set()
            for th in threads:
                th.join()
        assert not errors, f"forensic read raced a writer: {errors[:1]}"
        assert len(ws.registry.all_avs()) >= 40 * 9

    def test_concurrent_journal_writes_keep_seq_total_order(self, tmp_path):
        ws = Workspace(
            "conc-journal",
            executor=ConcurrentExecutor(max_workers=8),
            journal_path=str(tmp_path / "conc.jsonl"),
        )
        cam = ws.source(lambda: {"x": np.arange(8.0)}, name="cam", outputs=["x"])
        for i in range(6):
            t = ws.task(
                lambda x, i=i: {"y": float(x.sum()) + i},
                name=f"t{i}", inputs=["x"], outputs=["y"],
            )
            cam["x"] >> t["x"]
        for _ in range(5):
            ws.sample(cam)
        ws.journal.flush()
        records, truncated, _info = read_chain(ws.journal.path)
        assert truncated == 0
        seqs = [r["seq"] for r in records]
        assert seqs == list(range(len(seqs)))  # gapless total order


# ---------------------------------------------------------------------------
# fused batch encode/append (ISSUE 8)
# ---------------------------------------------------------------------------


class TestBatchAppend:
    RECORDS = [
        ("visit", {"task": "score", "av_uid": "av-0001", "event": "executed",
                   "timestamp": 1723100000.123456, "software_version": "v1",
                   "note": "wall=0.000123s", "seq": 7}),
        ("av", {"av": {"uid": "av-0002", "chash": "ab" * 8, "uri": "mem://x",
                       "meta": None}, "parents": ["av-0001"]}),
        ("anomaly", {"task": "t", "note": 'quote " and \\ backslash\nnewline'},),
        ("ledger", {"bytes": 4096, "pair": ["cloud", "edge"], "energy_j": 0.05}),
        ("odd", {"nan": float("nan"), "inf": float("inf"), "neg0": -0.0,
                 "big": 10**40, "uni": "ünïcode ⚙", "obj": object()}),
        ("nest", {"a": [1, [2, {"b": (3, 4)}]], "flags": [True, False, None]}),
    ]

    def test_encode_record_matches_json_dumps(self):
        from repro.provenance.journal import encode_record

        for i, (kind, data) in enumerate(self.RECORDS):
            want = json.dumps(
                {"seq": i, "kind": kind, "data": data},
                default=repr, separators=(",", ":"),
            )
            assert encode_record(i, kind, data) == want

    def test_append_batch_bytes_identical_to_scalar_appends(self, tmp_path):
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        ja = Journal(str(a), flush_every_n=1)
        for kind, data in self.RECORDS:
            ja.append(kind, data)
        ja.close()
        jb = Journal(str(b), flush_every_n=1)
        seqs = jb.append_batch(self.RECORDS)
        jb.close()
        # seq 0 is the journal's own meta header record
        assert seqs == list(range(1, len(self.RECORDS) + 1))
        strip = lambda p: [  # noqa: E731
            l for l in p.read_text().splitlines() if '"kind":"meta"' not in l
        ]
        assert strip(a) == strip(b)

    def test_staging_window_defers_and_flushes(self, tmp_path):
        j = Journal(str(tmp_path / "s.jsonl"), flush_every_n=1)
        with j.staging():
            assert j.append("visit", {"n": 0}) == -1  # deferred
            with j.staging():  # reentrant: joins the outer window
                assert j.append("visit", {"n": 1}) == -1
            assert j.records_written <= 1  # only the journal's own meta
        j.append("visit", {"n": 2})  # post-window: direct append
        j.close()
        records, truncated, _ = read_chain(j.path)
        assert truncated == 0
        body = [r for r in records if r["kind"] != "meta"]
        assert [r["data"]["n"] for r in body] == [0, 1, 2]
        assert [r["seq"] for r in records] == list(range(len(records)))

    def test_staging_window_flushes_on_exception(self, tmp_path):
        j = Journal(str(tmp_path / "exc.jsonl"), flush_every_n=1)
        with pytest.raises(RuntimeError):
            with j.staging():
                j.append("visit", {"n": 0})
                raise RuntimeError("user fn failed")
        j.close()
        records, _, _ = read_chain(j.path)
        assert any(
            r["kind"] == "visit" and r["data"]["n"] == 0 for r in records
        ), "records staged before the failure must still be durable"

    def test_append_batch_rotates(self, tmp_path):
        j = Journal(str(tmp_path / "rot.jsonl"), rotate_records=10)
        j.append_batch([("visit", {"n": i}) for i in range(25)])
        j.close()
        assert j.stats()["rotations"] >= 1
        records, truncated, _ = read_chain(j.path)
        assert truncated == 0
        assert [r["seq"] for r in records] == list(range(len(records)))

    def test_append_batch_span(self, tmp_path):
        import spantrace

        j = Journal(str(tmp_path / "w.jsonl"), flush_every_n=64)
        batch = [("visit", {"n": i}) for i in range(100)]
        _, found = spantrace.record(lambda: j.append_batch(batch), tmp_path / "trace")
        st = j.stats()
        j.close()
        appends = [s for s in found if s.name == "koalja:journal.append"]
        assert [s.args["records"] for s in appends] == [100]
        fsyncs = [s for s in found if s.name == "koalja:journal.fsync"]
        assert len(fsyncs) == 1 and fsyncs[0].within(appends[0])
        assert st["flushes"] == 1 and st["fsync_s"] > 0.0 and "encode_wall_s" not in st
