"""repro.runtime (ISSUE 6): multi-process worker pools and zone runners.

Covers the refs-only pipe protocol (zero payload bytes cross a pipe),
worker-crash robustness (``worker_died`` anomaly, bounded retries, inline
fallback, no lost or duplicated AVs), journal-segment merge back into a
registry identical to the single-process oracle — including torn tails
and revoked seq windows — and construction-time validation of the
``KOALJA_EXECUTOR`` / ``KOALJA_MAX_WORKERS`` / ``KOALJA_PLACEMENT`` knobs.
"""

import os

import numpy as np
import pytest

from repro.core.store import ArtifactStore
from repro.provenance import (
    Journal,
    merge_segments,
    read_records,
    replay_segments,
)
from repro.runtime import ProcessExecutor, ZonedProcessExecutor, fork_context
from repro.topology import Topology
from repro.workspace import (
    ConcurrentExecutor,
    InlineExecutor,
    Workspace,
    default_executor,
)

needs_fork = pytest.mark.skipif(
    fork_context() is None, reason="fork start method unavailable"
)


# ---------------------------------------------------------------------------
# circuits
# ---------------------------------------------------------------------------


def _fan_ws(executor, width=4, topology=False, placement=None, **ws_kwargs):
    """src -> width parallel squarers -> merge reducer. Every push fires one
    multi-task wave (the squarers), which is what exercises the pool."""
    ws = Workspace(
        "fan", executor=executor, cache=False,
        topology=topology, placement=placement, **ws_kwargs,
    )
    src = ws.task(
        lambda x: {"out": x}, name="src", inputs=["x"], outputs=["out"]
    )
    red = ws.task(
        lambda **kw: {"total": [float(np.sum(kw[k])) for k in sorted(kw)]},
        name="reduce", inputs=[f"v{i}" for i in range(width)],
        outputs=["total"],
    )
    for i in range(width):
        sq = ws.task(
            lambda y, i=i: {"sq": y * y + i},
            name=f"sq{i}", inputs=["y"], outputs=["sq"],
        )
        src["out"] >> sq["y"]
        sq["sq"] >> red[f"v{i}"]
    return ws


def _drive_fan(ws, rounds=2, n=32, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(rounds):
        ws.push("src", x=rng.randn(n).astype(np.float32))
    return ws


def _zone_ws(executor, **ws_kwargs):
    """Three-zone circuit for the zoned runners: one producer pinned per
    non-cloud zone, fanned into a cloud reducer."""
    topo = Topology.three_zone()
    ws = Workspace(
        "zones", executor=executor, cache=False, topology=topo,
        placement="pin", **ws_kwargs,
    )
    zones = ("edge", "device")
    src = ws.task(
        lambda x: {"out": x}, name="src", inputs=["x"], outputs=["out"]
    ).place("cloud")
    red = ws.task(
        lambda **kw: {"total": float(sum(np.sum(v) for v in kw.values()))},
        name="reduce", inputs=[f"a_{z}" for z in zones], outputs=["total"],
    ).place("cloud")
    for z in zones:
        # one push -> one wave holding both zone tasks (forks the runners)
        t = ws.task(
            lambda x, z=z: {"out": x * 2.0},
            name=f"prod_{z}", inputs=["x"], outputs=["out"],
        ).place(z)
        src["out"] >> t["x"]
        t["out"] >> red[f"a_{z}"]
    return ws, zones


def _drive_zones(ws, zones, rounds=2, n=16, seed=3):
    rng = np.random.RandomState(seed)
    for _ in range(rounds):
        ws.push("src", x=rng.randn(n).astype(np.float32))
    return ws


def _registry_story(registry):
    """The provenance projection that must survive any process topology:
    per-AV lineage parents, per-AV visit events, anomaly notes."""
    uids = registry.all_avs()
    # the uid counter is process-global: canonicalize to registration order
    # so stories from two workspaces (or a replay) compare by *shape*
    order = {uid: i for i, uid in enumerate(uids)}
    story = {}
    for uid in uids:
        lin = registry.lineage(uid, depth=1)
        story[order[uid]] = {
            "task": lin["source_task"],
            "parents": sorted(order.get(p["uid"], -1) for p in lin["parents"]),
            "visits": [
                (v["task"], v["event"]) for v in registry.visits_of(uid)
            ],
        }
    return story


# ---------------------------------------------------------------------------
# store: the reference-handover primitives
# ---------------------------------------------------------------------------


class TestStoreHandover:
    def test_publish_promotes_local_to_object_tier(self, tmp_path):
        store = ArtifactStore(object_dir=str(tmp_path / "obj"))
        uri, chash = store.put(np.arange(8, dtype=np.float32))
        moved = store.publish(chash)
        assert moved == store.nbytes_of(chash) > 0
        assert store.publish(chash) == 0  # idempotent: already shared
        with pytest.raises(KeyError):
            store.publish("sha256:absent")

    def test_export_then_adopt_round_trip(self, tmp_path):
        giver = ArtifactStore(object_dir=str(tmp_path / "obj"))
        taker = ArtifactStore(object_dir=str(tmp_path / "obj"))
        payload = np.arange(16, dtype=np.float32)
        _, chash, nbytes, existed = giver.export(payload)
        assert not existed
        uri = taker.adopt(chash, nbytes)
        np.testing.assert_array_equal(taker.get(uri), payload)
        # second export of identical content reports existed=True (dedup)
        _, chash2, _, existed2 = giver.export(payload.copy())
        assert chash2 == chash and existed2


# ---------------------------------------------------------------------------
# knob validation (satellite 1)
# ---------------------------------------------------------------------------


class TestKnobValidation:
    def test_executor_env_values(self, monkeypatch):
        monkeypatch.setenv("KOALJA_EXECUTOR", "process")
        assert isinstance(default_executor(), ProcessExecutor)
        monkeypatch.setenv("KOALJA_EXECUTOR", "zoned-process")
        assert isinstance(default_executor(), ZonedProcessExecutor)

    def test_bad_executor_names_choices(self, monkeypatch):
        monkeypatch.setenv("KOALJA_EXECUTOR", "quantum")
        with pytest.raises(ValueError, match="KOALJA_EXECUTOR"):
            Workspace("w", topology=False)
        try:
            default_executor()
        except ValueError as e:
            msg = str(e)
        for choice in ("inline", "concurrent", "process", "zoned-process"):
            assert choice in msg

    def test_bad_max_workers(self, monkeypatch):
        monkeypatch.setenv("KOALJA_EXECUTOR", "process")
        monkeypatch.setenv("KOALJA_MAX_WORKERS", "many")
        with pytest.raises(ValueError, match="KOALJA_MAX_WORKERS"):
            default_executor()
        monkeypatch.setenv("KOALJA_MAX_WORKERS", "0")
        with pytest.raises(ValueError, match=">= 1"):
            default_executor()
        monkeypatch.setenv("KOALJA_MAX_WORKERS", "3")
        ex = default_executor()
        assert ex.max_workers == 3

    def test_bad_placement_fails_at_construction(self, monkeypatch):
        # even on a flat circuit, where placement would never be exercised
        monkeypatch.setenv("KOALJA_PLACEMENT", "gravity_assist")
        with pytest.raises(ValueError, match="KOALJA_PLACEMENT"):
            Workspace("w", topology=False)
        monkeypatch.delenv("KOALJA_PLACEMENT")
        with pytest.raises(ValueError, match="placement="):
            Workspace("w", topology=False, placement="nope")

    def test_bad_topology_env(self, monkeypatch):
        monkeypatch.setenv("KOALJA_TOPOLOGY", "moonbase")
        with pytest.raises(ValueError, match="KOALJA_TOPOLOGY"):
            Workspace("w")


# ---------------------------------------------------------------------------
# ProcessExecutor: the flat pool
# ---------------------------------------------------------------------------


@needs_fork
class TestProcessPool:
    def test_pipe_carries_exact_control_bytes_only(self):
        """An 8-wide fan pushed four times: 32 tasks run in workers, the
        pipes carry 5,952 control bytes out and 5,920 back and no payload
        byte, and the output and visitor events are the thread pool's."""
        runs = {}
        for label, ex in (
            ("threads", ConcurrentExecutor(max_workers=8)),
            ("processes", ProcessExecutor(max_workers=8)),
        ):
            ws = _drive_fan(_fan_ws(ex, width=8, journal_path=False), rounds=4, n=256)
            runs[label] = (
                ws.value_of(ws.pipeline.tasks["reduce"].last_outputs["total"]),
                sorted((t, e["event"]) for t in ws.tasks() for e in ws.visitor_log(t)),
                ex.stats(),
            )
            ex.shutdown()
        st = runs["processes"][2]
        assert st["tasks_remote"] == 32
        assert st["control_bytes_sent"] == 5952
        assert st["control_bytes_received"] == 5920
        assert st["payload_bytes_over_pipe"] == 0
        assert runs["processes"][:2] == runs["threads"][:2]

    def test_matches_inline_and_moves_no_payload_bytes(self):
        base = _drive_fan(_fan_ws(InlineExecutor()))
        ex = ProcessExecutor(max_workers=4)
        ws = _drive_fan(_fan_ws(ex))
        try:
            assert (
                ws.value_of(ws.pipeline.tasks["reduce"].last_outputs["total"])
                == base.value_of(
                    base.pipeline.tasks["reduce"].last_outputs["total"]
                )
            )
            st = ex.stats()
            assert st["tasks_remote"] > 0
            assert st["payload_bytes_over_pipe"] == 0
            assert st["control_bytes_sent"] > 0
            assert st["control_bytes_received"] > 0
            assert _registry_story(ws.registry) == _registry_story(
                base.registry
            )
        finally:
            ex.shutdown()

    def test_single_task_waves_stay_inline(self):
        ex = ProcessExecutor(max_workers=4)
        ws = Workspace("solo", executor=ex, cache=False, topology=False)
        ws.task(lambda x: {"y": x + 1}, name="t", inputs=["x"], outputs=["y"])
        ws.push("t", x=1)
        try:
            st = ex.stats()
            assert st["tasks_remote"] == 0
            assert st["workers_alive"] == 0  # pool never forked
        finally:
            ex.shutdown()

    def test_scheduler_reports_wave_width(self):
        ex = ProcessExecutor(max_workers=4)
        ws = _drive_fan(_fan_ws(ex, width=4), rounds=1)
        try:
            assert ws.stats()["scheduler"]["max_wave_width"] == 4
        finally:
            ex.shutdown()

    @pytest.mark.parametrize("platform", ["tpu", "cpu"])
    def test_refuses_to_fork_while_holding_an_accelerator(self, platform, monkeypatch):
        """A forked child cannot use a device its parent holds: once JAX has
        an accelerator backend, a multi-task wave raises instead of forking.
        The CPU backend forks as before."""
        import jax

        jax.numpy.zeros(1).block_until_ready()  # backends initialised
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        ex = ProcessExecutor(max_workers=2)
        try:
            if platform == "tpu":
                with pytest.raises(RuntimeError, match="cannot fork"):
                    _drive_fan(_fan_ws(ex, width=2), rounds=1)
                assert ex.stats()["workers_alive"] == 0
            else:
                _drive_fan(_fan_ws(ex, width=2), rounds=1)
                assert ex.stats()["tasks_remote"] > 0
        finally:
            ex.shutdown()


@needs_fork
class TestWorkerCrash:
    """Satellite 2: kill a pool worker mid-wave; the wave must retry on a
    fresh worker, journal a ``worker_died`` anomaly, and lose nothing."""

    def _crash_ws(self, ex, parent_pid, crash_flag):
        """4-wide fan-out where sq0 hard-exits the hosting process — but
        only in a *worker* (the parent-pid guard keeps the retry/fallback
        path computing real values)."""
        ws = Workspace("crash", executor=ex, cache=False, topology=False)
        src = ws.task(
            lambda x: {"out": x}, name="src", inputs=["x"], outputs=["out"]
        )
        red = ws.task(
            lambda **kw: {"total": float(sum(np.sum(v) for v in kw.values()))},
            name="reduce", inputs=[f"v{i}" for i in range(4)],
            outputs=["total"],
        )
        def sq0(y):
            if os.getpid() != parent_pid and os.path.exists(crash_flag):
                os.remove(crash_flag)  # one crash, then behave
                os._exit(1)
            return {"sq": y * y}
        tasks = [sq0] + [
            (lambda y, i=i: {"sq": y * y + i}) for i in range(1, 4)
        ]
        for i, fn in enumerate(tasks):
            t = ws.task(fn, name=f"sq{i}", inputs=["y"], outputs=["sq"])
            src["out"] >> t["y"]
            t["sq"] >> red[f"v{i}"]
        return ws

    def test_killed_worker_retries_with_anomaly(self, tmp_path):
        flag = str(tmp_path / "crash-once")
        ex = ProcessExecutor(max_workers=2, retry_budget=2)
        ws = self._crash_ws(ex, os.getpid(), flag)
        open(flag, "w").close()
        ws.push("src", x=np.ones(8, np.float32))
        try:
            st = ex.stats()
            assert st["worker_restarts"] >= 1
            assert st["retries"] >= 1
            notes = [a["note"] for a in ws.registry.anomalies]
            assert any("worker_died" in n for n in notes)
            # the wave completed: reducer saw all four squares exactly once
            total = ws.value_of(
                ws.pipeline.tasks["reduce"].last_outputs["total"]
            )
            assert total == pytest.approx(8 * (1 + 2 + 3) + 4 * 8)
            for i in range(4):
                emits = [
                    v for v in ws.visitor_log(f"sq{i}")
                    if v["event"] == "emitted"
                ]
                assert len(emits) == 1, f"sq{i} emitted {len(emits)} times"
        finally:
            ex.shutdown()

    def test_exhausted_retry_budget_falls_back_inline(self, tmp_path):
        # crash on *every* worker attempt -> the parent runs the task itself
        ex = ProcessExecutor(max_workers=2, retry_budget=1)
        ws = Workspace("fb", executor=ex, cache=False, topology=False)
        parent = os.getpid()
        src = ws.task(
            lambda x: {"out": x}, name="src", inputs=["x"], outputs=["out"]
        )
        def die(y):
            if os.getpid() != parent:
                os._exit(1)
            return {"sq": y * y}
        t0 = ws.task(die, name="sq0", inputs=["y"], outputs=["sq"])
        t1 = ws.task(
            lambda y: {"sq": y + 1}, name="sq1", inputs=["y"], outputs=["sq"]
        )
        src["out"] >> t0["y"]
        src["out"] >> t1["y"]
        ws.push("src", x=np.full(4, 3.0, np.float32))
        try:
            st = ex.stats()
            assert st["inline_fallbacks"] >= 1
            np.testing.assert_array_equal(
                ws.value_of(ws.pipeline.tasks["sq0"].last_outputs["sq"]),
                np.full(4, 9.0, np.float32),
            )
        finally:
            ex.shutdown()

    def test_crash_run_fingerprint_matches_clean_run(self, tmp_path):
        """Modulo the anomaly entries, a run that lost a worker mid-wave
        tells the same provenance story as a crash-free one."""
        flag = str(tmp_path / "crash-once")

        def run(crash):
            ex = ProcessExecutor(max_workers=2, retry_budget=2)
            ws = self._crash_ws(ex, os.getpid(), flag)
            if crash:
                open(flag, "w").close()
            ws.push("src", x=np.full(8, 2.0, np.float32))
            try:
                story = _registry_story(ws.registry)
                total = ws.value_of(
                    ws.pipeline.tasks["reduce"].last_outputs["total"]
                )
            finally:
                ex.shutdown()
            # anomaly visits ride on the task, not the AVs; strip the
            # anomaly *events* from each AV's visit list for comparison
            for s in story.values():
                s["visits"] = [v for v in s["visits"] if v[1] != "anomaly"]
            return story, total

        clean_story, clean_total = run(crash=False)
        crash_story, crash_total = run(crash=True)
        assert crash_total == clean_total
        # uid *values* may differ; compare the per-task story shapes
        def by_task(story):
            out = {}
            for s in story.values():
                out.setdefault(s["task"], []).append(
                    (sorted(v for v in s["visits"]), len(s["parents"]))
                )
            return {k: sorted(v) for k, v in out.items()}
        assert by_task(crash_story) == by_task(clean_story)


# ---------------------------------------------------------------------------
# ZonedProcessExecutor: runners + journal-segment merge (satellite 3)
# ---------------------------------------------------------------------------


@needs_fork
class TestZoneRunnerMerge:
    def _run_zoned(self, tmp_path, name="zp"):
        jpath = str(tmp_path / f"{name}.jsonl")
        ex = ZonedProcessExecutor(max_workers=2)
        ws, zones = _zone_ws(ex, journal_path=jpath)
        _drive_zones(ws, zones)
        return ws, ex, zones, jpath

    def test_segments_merge_to_live_registry(self, tmp_path):
        ws, ex, zones, jpath = self._run_zoned(tmp_path)
        try:
            segs = ex.segment_paths()
            assert len(segs) >= 2, "expected >=2 active zone segments"
            ws.journal.flush()
            replayed = replay_segments(jpath, segs)
            assert _registry_story(replayed.registry) == _registry_story(
                ws.registry
            )
            assert replayed.truncated == 0
        finally:
            ex.shutdown()

    def test_merge_matches_single_process_oracle(self, tmp_path):
        ws, ex, zones, jpath = self._run_zoned(tmp_path)
        oracle, _ = _zone_ws(InlineExecutor())
        _drive_zones(oracle, zones)
        try:
            ws.journal.flush()
            replayed = replay_segments(jpath, ex.segment_paths())

            def project(reg):
                # uid values differ across runs; compare per-task shapes
                out = {}
                for s in _registry_story(reg).values():
                    out.setdefault(s["task"], []).append(
                        (sorted(s["visits"]), len(s["parents"]))
                    )
                return {k: sorted(v) for k, v in out.items()}

            assert project(replayed.registry) == project(oracle.registry)
            # ledger story survives the merge byte-for-byte
            live = ws.ledger.stats()
            assert replayed.ledger is not None
            assert replayed.ledger.stats() == live
        finally:
            ex.shutdown()

    def test_from_journal_accepts_segment_list(self, tmp_path):
        ws, ex, zones, jpath = self._run_zoned(tmp_path)
        try:
            ws.journal.flush()
            ws2 = Workspace.from_journal([jpath, *ex.segment_paths()])
            for t in ws.tasks():
                assert [e["event"] for e in ws2.visitor_log(t)] == [
                    e["event"] for e in ws.visitor_log(t)
                ]
        finally:
            ex.shutdown()

    def test_torn_segment_tail_is_tolerated(self, tmp_path):
        ws, ex, zones, jpath = self._run_zoned(tmp_path)
        try:
            ws.journal.flush()
            segs = ex.segment_paths()
            ex.shutdown()
            intact = replay_segments(jpath, segs)
            # simulate a runner dying mid-append: torn trailing line
            with open(segs[0], "a", encoding="utf-8") as fh:
                fh.write('{"seq": 99999, "kind": "vis')
            torn = replay_segments(jpath, segs)
            assert torn.truncated == 1
            assert _registry_story(torn.registry) == _registry_story(
                intact.registry
            )
        finally:
            ex.shutdown()

    def test_interleaved_seqs_restore_total_order(self, tmp_path):
        """Two hand-built segments with interleaved seq windows merge into
        one stream sorted by the global seq protocol."""
        main = Journal(str(tmp_path / "m.jsonl"), workspace="w")
        main.append("task", {"task": "t", "inputs": [], "outputs": [],
                             "version": "v"})
        s1 = main.reserve(2)
        s2 = main.reserve(2)
        main.append("anomaly", {"task": "t", "note": "tail", "seq": 0,
                                "clock": 0})
        main.close()
        seg_a = Journal(str(tmp_path / "m.jsonl.seg-a"), workspace="w",
                        segment="a", flush_every_n=1)
        # a holds the *second* window: later seqs written first on disk
        seg_a.append("anomaly", {"task": "t", "note": "w2-first",
                                 "seq": 0, "clock": 0}, seq=s2)
        seg_a.append("anomaly", {"task": "t", "note": "w2-second",
                                 "seq": 0, "clock": 0}, seq=s2 + 1)
        seg_a.close()
        seg_b = Journal(str(tmp_path / "m.jsonl.seg-b"), workspace="w",
                        segment="b", flush_every_n=1)
        seg_b.append("anomaly", {"task": "t", "note": "w1-first",
                                 "seq": 0, "clock": 0}, seq=s1)
        seg_b.append("anomaly", {"task": "t", "note": "w1-second",
                                 "seq": 0, "clock": 0}, seq=s1 + 1)
        seg_b.close()
        records, truncated = merge_segments(
            str(tmp_path / "m.jsonl"),
            [str(tmp_path / "m.jsonl.seg-a"), str(tmp_path / "m.jsonl.seg-b")],
        )
        assert truncated == 0
        seqs = [r["seq"] for r in records]
        assert seqs == sorted(seqs)
        notes = [r["data"]["note"] for r in records if r["kind"] == "anomaly"]
        assert notes == ["w1-first", "w1-second", "w2-first", "w2-second",
                         "tail"]

    def test_revoked_window_drops_segment_records(self, tmp_path):
        main = Journal(str(tmp_path / "m.jsonl"), workspace="w")
        start = main.reserve(2)
        main.append("revoked", {"task": "t", "start": start, "count": 2})
        main.close()
        seg = Journal(str(tmp_path / "m.jsonl.seg-z"), workspace="w",
                      segment="z", flush_every_n=1)
        seg.append("anomaly", {"task": "t", "note": "dead-runner-orphan",
                               "seq": 0, "clock": 0}, seq=start)
        seg.close()
        records, _ = merge_segments(
            str(tmp_path / "m.jsonl"), [str(tmp_path / "m.jsonl.seg-z")]
        )
        assert not any(
            r["kind"] == "anomaly"
            and r["data"]["note"] == "dead-runner-orphan"
            for r in records
        )

    def test_killed_runner_revokes_and_merge_still_matches(self, tmp_path):
        """Chaos: kill one zone runner mid-run. The retried firing must not
        duplicate AVs in the merged replay, and the merged registry must
        still match the live one."""
        jpath = str(tmp_path / "chaos.jsonl")
        ex = ZonedProcessExecutor(max_workers=2, retry_budget=2)
        ws, zones = _zone_ws(ex, journal_path=jpath)
        _drive_zones(ws, zones, rounds=1)  # forks the runners
        assert ex.kill_runner("edge")
        _drive_zones(ws, zones, rounds=2, seed=7)
        try:
            st = ex.stats()
            ws.journal.flush()
            replayed = replay_segments(jpath, ex.segment_paths())
            assert _registry_story(replayed.registry) == _registry_story(
                ws.registry
            )
            # every firing emitted exactly once in the merged story too
            for t in ws.tasks():
                live = [e["event"] for e in ws.visitor_log(t)]
                assert [
                    e["event"] for e in replayed.registry.visitor_log(t)
                ] == live
        finally:
            ex.shutdown()

    def test_zoned_stats_surface(self, tmp_path):
        ws, ex, zones, jpath = self._run_zoned(tmp_path)
        try:
            st = ex.stats()
            assert st["payload_bytes_over_pipe"] == 0
            assert st["control_bytes_sent"] > 0
            assert set(st["runners"]) <= set(
                Topology.three_zone().zone_names()
            )
            assert len(st["zones"]) >= 2
            assert st["tasks_remote"] > 0
        finally:
            ex.shutdown()
