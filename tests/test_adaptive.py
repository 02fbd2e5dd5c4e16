"""Adaptive runtime (ISSUE 10): feedback-driven autoscaling, energy-aware
placement wiring, zone-local cache tiers, and the journaled ``scale``
decision history.

The determinism contract under test: pool size never affects merge order,
provenance, or ledgers; resize decisions derive from deterministic wave
widths (not wall clocks), so the journaled scale history reproduces; and a
memo hit served from a same-zone replica credits — never charges — the
transfer ledger.
"""

import os
import tempfile

import numpy as np
import pytest

from repro.topology import Topology
from repro.workspace import (
    AdaptiveExecutor,
    ConcurrentExecutor,
    InlineExecutor,
    Workspace,
    default_executor,
)


def _wan_topology():
    t = Topology("wan")
    t.zone("cloud", tier="cloud")
    t.zone("edge", tier="edge")
    t.zone("device", tier="device")
    t.link("device", "edge", latency_ms=1, bandwidth_mbps=1000,
           energy_j_per_mb=0.01)
    t.link("edge", "cloud", latency_ms=20, bandwidth_mbps=100,
           energy_j_per_mb=0.05)
    t.link("device", "cloud", latency_ms=50, bandwidth_mbps=10,
           energy_j_per_mb=0.5)
    return t


def _fan_ws(widths, executor=None, placement="energy", journal_path=None,
            cache=False):
    """One fan per load level: src_w (device) -> w squarers -> red_w (cloud).
    Pushing ``src_w`` fires exactly one wave of width ``w``."""
    ws = Workspace("adaptive", topology=_wan_topology(), placement=placement,
                   executor=executor, journal_path=journal_path, cache=cache)
    for w in widths:
        src = ws.task(lambda x: {"out": x}, name=f"src{w}",
                      inputs=["x"], outputs=["out"]).place("device")
        red = ws.task(lambda **kw: {"total": sum(kw.values())},
                      name=f"red{w}", inputs=[f"v{i}" for i in range(w)],
                      outputs=["total"]).place("cloud")
        for i in range(w):
            sq = ws.task(lambda y, i=i: {"s": float(np.sum(y)) + i},
                         name=f"sq{w}_{i}", inputs=["y"], outputs=["s"])
            src["out"] >> sq["y"]
            sq["s"] >> red[f"v{i}"]
    return ws


def _drive(ws, schedule, n=256, seed=0):
    rng = np.random.RandomState(seed)
    for w in schedule:
        ws.push(f"src{w}", x=rng.randn(n).astype(np.float32))
    return ws


# ---------------------------------------------------------------------------
# load signals (tentpole layer 1)
# ---------------------------------------------------------------------------


class TestLoadSignals:
    def test_snapshot_shape_and_percentiles(self):
        ws = _drive(_fan_ws([1, 4]), [1, 4, 4, 4, 4, 4, 4, 4, 4, 4])
        load = ws.stats()["scheduler"]["load"]
        assert load["waves_observed"] > 0
        # each push brackets its wide wave with width-1 src/reduce waves,
        # so the median stays 1 while p95 captures the fan width
        assert load["wave_width_p50"] == 1
        assert load["wave_width_p95"] == 4
        assert load["recommended_workers"] == 4
        assert load["queue_depth_high_water_last_drain"] >= 1
        # service EWMAs observed for every task that executed
        assert "red4" in load["service_ewma_s"]
        assert load["service_ewma_max_s"] >= max(load["service_ewma_s"].values())

    def test_percentiles_are_nearest_rank(self):
        from repro.core.scheduler import LoadSignals

        sig = LoadSignals(window=8)
        for w in (1, 1, 1, 1, 1, 1, 1, 8):
            sig.observe_wave(w)
        assert sig.wave_width_p50 == 1
        assert sig.wave_width_p95 == 8  # nearest-rank: the 8th of 8
        assert sig.recommended_workers == 8

    def test_window_slides(self):
        from repro.core.scheduler import LoadSignals

        sig = LoadSignals(window=4)
        for w in (8, 8, 8, 8, 1, 1, 1, 1):
            sig.observe_wave(w)
        assert sig.wave_width_p95 == 1  # the 8s slid out of the window


# ---------------------------------------------------------------------------
# adaptive executor (tentpole layer 3)
# ---------------------------------------------------------------------------


class TestAdaptiveExecutor:
    def test_constructor_validation(self):
        with pytest.raises(ValueError):
            AdaptiveExecutor(min_workers=0)
        with pytest.raises(ValueError):
            AdaptiveExecutor(min_workers=4, max_workers=2)
        with pytest.raises(ValueError):
            AdaptiveExecutor(scale_down_patience=0)
        with pytest.raises(TypeError):
            AdaptiveExecutor(inner=InlineExecutor())  # no resize seam

    def test_scales_up_with_load(self):
        ex = AdaptiveExecutor(min_workers=1, max_workers=8)
        assert ex.current_workers == 1
        _drive(_fan_ws([1, 6], executor=ex), [1, 6, 6])
        assert ex.current_workers == 6
        assert ex.scale_ups >= 1
        ups = [e for e in ex.scale_history if e["direction"] == "up"]
        assert ups and ups[-1]["to"] == 6
        ex.shutdown()

    def test_scales_down_with_hysteresis(self):
        ex = AdaptiveExecutor(min_workers=1, max_workers=8,
                              scale_down_patience=3)
        # ramp up, then a long quiet tail: the pool must not thrash down on
        # the first narrow wave, only after patience expires AND the wide
        # waves leave the percentile window
        schedule = [6] * 4 + [1] * 80
        _drive(_fan_ws([1, 6], executor=ex), schedule)
        assert ex.current_workers == 1
        assert ex.scale_downs >= 1
        ex.shutdown()

    def test_band_is_clamped(self):
        ex = AdaptiveExecutor(min_workers=2, max_workers=4)
        _drive(_fan_ws([1, 6], executor=ex), [1, 6, 6, 6])
        assert 2 <= ex.current_workers <= 4
        ex.shutdown()

    def test_stats_surface(self):
        ex = AdaptiveExecutor(min_workers=1, max_workers=8)
        ws = _drive(_fan_ws([1, 4], executor=ex), [1, 4, 4])
        st = ws.stats()["executor"]
        for key in ("current_workers", "min_workers", "max_workers",
                    "resizes", "scale_ups", "scale_downs", "inner"):
            assert key in st
        assert st["last_scale"] == ex.scale_history[-1]
        ex.shutdown()

    def test_env_knob_resolution(self, monkeypatch):
        from repro.workspace.executors import ZonedExecutor

        monkeypatch.setenv("KOALJA_EXECUTOR", "adaptive")
        monkeypatch.setenv("KOALJA_MAX_WORKERS", "5")
        ex = default_executor()
        assert isinstance(ex, AdaptiveExecutor)
        assert ex.max_workers == 5
        monkeypatch.setenv("KOALJA_EXECUTOR", "zoned-adaptive")
        zex = default_executor()
        assert isinstance(zex, ZonedExecutor)
        assert isinstance(zex.inner, AdaptiveExecutor)

    def test_pool_size_never_affects_results_or_provenance(self):
        """The acceptance clause: same circuit, pool bands 1..1 vs 8..8 —
        identical merge totals, ledger, and visitor events."""
        def run(lo, hi):
            ex = AdaptiveExecutor(min_workers=lo, max_workers=hi)
            ws = _drive(_fan_ws([1, 6], executor=ex), [1, 6, 6, 1])
            stats = ws.stats()
            out = {
                "total": ws.value_of(
                    ws.manager.pipeline.tasks["red6"].last_outputs["total"]),
                "ledger": stats["topology"]["ledger"],
                "events": sorted((t, e["event"]) for t in ws.tasks()
                                 for e in ws.visitor_log(t)),
            }
            ex.shutdown()
            return out

        assert run(1, 1) == run(8, 8)


class TestPoolResize:
    def test_concurrent_resize(self):
        ex = ConcurrentExecutor(max_workers=2)
        ex.resize(6)
        assert ex.max_workers == 6
        with pytest.raises(ValueError):
            ex.resize(0)
        ex.shutdown()

    def test_process_resize_grow_and_shrink(self):
        from repro.runtime import ProcessExecutor

        ex = ProcessExecutor(max_workers=2)
        ex.resize(4)
        assert ex.max_workers == 4 and len(ex._workers) == 4
        ex.resize(1)
        assert ex.max_workers == 1 and len(ex._workers) == 1
        with pytest.raises(ValueError):
            ex.resize(0)
        ex.shutdown()

    def test_adaptive_over_process_pool(self):
        """AdaptiveExecutor composes with the forked pool: same results,
        resizes journal-free here (no journal), pool ends wide."""
        from repro.runtime import ProcessExecutor
        from repro.runtime.worker import fork_context

        if fork_context() is None:
            pytest.skip("platform without fork")
        ex = AdaptiveExecutor(inner=ProcessExecutor(max_workers=1),
                              min_workers=1, max_workers=4)
        ws = _drive(_fan_ws([1, 4], executor=ex), [1, 4, 4])
        assert ex.current_workers == 4
        total = ws.value_of(ws.manager.pipeline.tasks["red4"].last_outputs["total"])
        assert isinstance(total, float)
        ex.shutdown()


# ---------------------------------------------------------------------------
# journaled scale records (tentpole layer 3, replay half)
# ---------------------------------------------------------------------------


class TestScaleRecordReplay:
    def test_scale_records_roundtrip_from_journal(self):
        tmp = tempfile.mkdtemp(prefix="koalja-adaptive-")
        jp = os.path.join(tmp, "journal.jsonl")
        ex = AdaptiveExecutor(min_workers=1, max_workers=8)
        ws = _drive(_fan_ws([1, 6], executor=ex, journal_path=jp),
                    [1, 6, 6, 1, 6])
        live_history = list(ex.scale_history)
        live_ledger = ws.stats()["topology"]["ledger"]
        assert live_history, "schedule must provoke at least one resize"
        ws.journal.close()
        ex.shutdown()

        replayed = Workspace.from_journal(jp)
        jstats = replayed.stats()["journal"]
        assert jstats["scale_events"] == live_history
        assert jstats["replayed_counts"]["scale"] == len(live_history)
        # the replayed ledger agrees on every account, compute included
        rledger = replayed.stats()["topology"]["ledger"]
        assert rledger == live_ledger

    def test_scale_record_fields(self):
        tmp = tempfile.mkdtemp(prefix="koalja-adaptive-")
        jp = os.path.join(tmp, "journal.jsonl")
        ex = AdaptiveExecutor(min_workers=1, max_workers=8)
        _drive(_fan_ws([1, 6], executor=ex, journal_path=jp), [1, 6, 6])
        event = ex.scale_history[-1]
        for key in ("executor", "wave", "from", "to", "direction",
                    "width_p95", "queue_high_water"):
            assert key in event
        assert event["direction"] in ("up", "down")
        assert event["from"] != event["to"]
        ex.shutdown()


# ---------------------------------------------------------------------------
# zone-local memo/store tiers (tentpole layer 4)
# ---------------------------------------------------------------------------


class TestZoneLocalTier:
    def _memo_pair(self, hit_zone):
        """Two workspaces sharing one store + memo table (the B15 pattern):
        the first executes in edge-a; the second replays the hit in
        ``hit_zone``."""
        from repro.cache import MemoCache
        from repro.core.store import ArtifactStore

        store, cache = ArtifactStore(), MemoCache()

        def build(pin_zone):
            ws = Workspace("zl", topology=Topology.three_zone(),
                           placement="pin", store=store, cache=cache)
            src = ws.source(lambda: None, name="src",
                            outputs=["x"]).place(pin_zone)
            t = ws.task(lambda x: {"y": x * 2}, name="t",
                        inputs=["x"], outputs=["y"]).place(pin_zone)
            src["x"] >> t["x"]
            return ws

        x = np.ones(64, np.float32)
        cold = build("edge")
        cold.push("src", x=x)
        warm = build(hit_zone)
        return store, cache, cold, warm, x

    def test_hit_without_local_replica_keeps_birth_zone(self):
        store, cache, cold, warm, x = self._memo_pair("cloud")
        warm.push("src", x=x)
        t = warm.manager.pipeline.tasks["t"]
        assert t.cache_hits == 1
        # no cloud replica of the output exists: the AV still points at the
        # birth zone and no zone-local credit is taken
        assert t.last_outputs["y"].zone == "edge"
        assert warm.stats()["topology"]["ledger"]["zone_local_hits"] == 0

    def test_hit_with_local_replica_credits_ledger(self):
        store, cache, cold, warm, x = self._memo_pair("cloud")
        # materialize the output into cloud first (a cloud consumer read it)
        out = cold.manager.pipeline.tasks["t"].last_outputs["y"]
        store.note_zone_resident(out.chash, "cloud")
        warm.push("src", x=x)
        t = warm.manager.pipeline.tasks["t"]
        assert t.cache_hits == 1
        # served from the cloud-local replica: AV rebinds to the replay zone
        assert t.last_outputs["y"].zone == "cloud"
        led = warm.stats()["topology"]["ledger"]
        assert led["zone_local_hits"] == 1
        assert led["bytes_served_zone_local"] == out.meta["nbytes"]
        assert cache.stats()["zone_local_hits"] == 1
        assert store.stats()["zone_local_serves"] >= 1

    def test_store_zone_residency_index(self):
        from repro.core.store import ArtifactStore

        store = ArtifactStore()
        store.note_zone_resident("h1", "edge")
        store.note_zone_resident("h1", "edge")  # idempotent
        store.note_zone_resident("h1", "cloud")
        store.note_zone_resident("h2", None)  # flat circuits: no-op
        assert store.zone_resident("h1", "edge")
        assert store.zone_resident("h1", "cloud")
        assert not store.zone_resident("h2", "edge")
        assert not store.zone_resident("h1", None)
        assert store.zone_resident_counts() == {"cloud": 1, "edge": 1}

    def test_same_zone_executions_index_the_store(self):
        """Every minted output registers residency in its execution zone."""
        ws = _drive(_fan_ws([2]), [2, 2])
        counts = ws.stats()["store"]["zone_residents"]
        assert counts.get("device", 0) > 0  # src outputs
        assert counts.get("cloud", 0) > 0  # reducer outputs + materialized inputs


# ---------------------------------------------------------------------------
# compute-energy account (tentpole layer 2, ledger half)
# ---------------------------------------------------------------------------


class TestComputeEnergyAccount:
    def test_zone_coefficients_and_pricing(self):
        topo = _wan_topology()
        assert topo.compute_j_per_mb("cloud") == pytest.approx(0.02)
        assert topo.compute_j_per_mb("edge") == pytest.approx(0.05)
        assert topo.compute_j_per_mb("device") == pytest.approx(0.12)
        assert topo.compute_energy_j("edge", 2_000_000) == pytest.approx(0.1)
        from repro.topology import TopologyError

        with pytest.raises(TopologyError):
            topo.compute_j_per_mb("mars")
        with pytest.raises(TopologyError):
            Topology("t").zone("z", compute_j_per_mb=-1.0)

    def test_describe_roundtrips_compute(self):
        topo = _wan_topology()
        spec = topo.describe()
        assert spec["compute"]["device"] == pytest.approx(0.12)
        clone = Topology.from_spec(spec)
        assert clone.describe() == spec

    def test_ledger_charges_executions(self):
        ws = _drive(_fan_ws([2]), [2])
        led = ws.stats()["topology"]["ledger"]
        assert led["executions_charged"] > 0
        assert led["compute_energy_j"] > 0
        assert set(led["zone_compute_bytes"]) <= {"cloud", "edge", "device"}
        assert led["total_energy_j"] == pytest.approx(
            led["transfer_energy_j"] + led["compute_energy_j"]
        )


# ---------------------------------------------------------------------------
# a diurnal day on the device fleet: the joules each policy pays
# ---------------------------------------------------------------------------


DIURNAL = [1, 2, 4, 8, 8, 4, 2, 1] * 2  # wave widths, two periods


@pytest.mark.parametrize(
    "placement,make_executor,joules,by_zone,resizes",
    [
        ("energy",
         lambda: AdaptiveExecutor(inner=ConcurrentExecutor(max_workers=1),
                                  min_workers=1, max_workers=8),
         1.33177972, {"cloud": 16, "device": 16, "edge": 60}, 3),
        ("pin", lambda: ConcurrentExecutor(max_workers=1),
         2.91506648, {"cloud": 76, "device": 16}, None),
        ("data_gravity", lambda: ConcurrentExecutor(max_workers=8),
         2.39140848, {"cloud": 16, "device": 76}, None),
    ],
    ids=["adaptive_energy", "pin_pool1", "gravity_pool8"],
)
def test_diurnal_day_joules(placement, make_executor, joules, by_zone, resizes):
    """Two diurnal periods of 256 KiB readings, wave widths 1..8..1: pin
    floats the 60 analyzer firings to the cloud over the metered uplink,
    data gravity drags them onto the battery-powered device, energy-aware
    placement lands them on the edge rack and pays the fewest joules; the
    adaptive pool resizes three times, from wave widths alone."""
    ex = make_executor()
    ws = _drive(_fan_ws(sorted(set(DIURNAL)), executor=ex, placement=placement),
                DIURNAL, n=65536, seed=7)
    ex.shutdown()
    assert ws.stats()["topology"]["ledger"]["total_energy_j"] == pytest.approx(joules, rel=1e-9)
    assert ws.stats()["topology"]["placement"]["by_zone"] == by_zone
    if resizes is not None:
        assert len(ex.scale_history) == resizes
