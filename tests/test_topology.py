"""Extended-cloud topology (ISSUE 4): zones/placement/ledger model,
data-gravity co-location, hash-only cross-zone transport, ZonedExecutor
determinism against Inline/Concurrent, and the gravity-never-loses
property on reducer fan-ins."""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.topology import (
    DataGravityPlacement,
    PinPlacement,
    Topology,
    TopologyError,
    TransferLedger,
    default_topology,
    make_placement,
)
from repro.workspace import (
    AdaptiveExecutor,
    ConcurrentExecutor,
    InlineExecutor,
    WiringError,
    Workspace,
    ZonedExecutor,
)

EDGE_ZONES = ("edge-a", "edge-b", "edge-c")


# ---------------------------------------------------------------------------
# circuits
# ---------------------------------------------------------------------------


def _iot_topology():
    topo = Topology("iot")
    topo.zone("cloud", tier="cloud")
    for z in EDGE_ZONES:
        topo.zone(z, tier="edge")
        topo.link("cloud", z, bandwidth_mbps=50, latency_ms=20, energy_j_per_mb=0.05)
    return topo


def _iot_ws(placement, executor=None, sensors=2, zones=EDGE_ZONES, coalesce=None):
    """Edge fan-in: per-zone sensors -> per-zone aggregator -> cloud merge
    reducer. Sensors and the reducer are pinned; aggregators float.
    ``coalesce`` opts the aggregators and the reducer into arrival
    coalescing (TaskHandle.coalesce) with the given max batch."""
    ws = Workspace(
        "iot", topology=_iot_topology(), placement=placement,
        executor=executor, cache=False,
    )
    for z in zones:
        for i in range(sensors):
            ws.source(
                lambda i=i: {"reading": np.full(4, float(i), np.float32)},
                name=f"s_{z}_{i}", outputs=["reading"],
            ).place(z)
        agg = ws.task(
            lambda **kw: {"agg": sum(kw.values())},
            name=f"agg_{z}", inputs=[f"r{i}" for i in range(sensors)],
            outputs=["agg"],
        )
        if coalesce is not None:
            agg.coalesce(coalesce)
        for i in range(sensors):
            ws[f"s_{z}_{i}"]["reading"] >> agg[f"r{i}"]
    red = ws.task(
        lambda merged: {"total": [float(np.sum(m)) for m in merged]},
        name="reduce", inputs=[f"a_{z}" for z in zones], outputs=["total"],
        mode="merge",
    ).place("cloud")
    if coalesce is not None:
        red.coalesce(coalesce)
    for z in zones:
        ws[f"agg_{z}"]["agg"] >> red[f"a_{z}"]
    return ws


def _drive(ws, rounds=2, n=64, sensors=2, zones=EDGE_ZONES, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(rounds):
        for z in zones:
            for i in range(sensors):
                ws.push(f"s_{z}_{i}", reading=rng.randn(n).astype(np.float32))
    return ws


# ---------------------------------------------------------------------------
# topology model
# ---------------------------------------------------------------------------


class TestTopologyModel:
    def test_zones_and_default(self):
        topo = Topology("t")
        topo.zone("cloud")
        topo.zone("edge", tier="edge")
        assert topo.default_zone == "cloud"  # first declared
        assert topo.zone_names() == ["cloud", "edge"]
        assert Topology("t2", default_zone="x")._default_zone == "x"

    def test_duplicate_zone_and_bad_tier_rejected(self):
        topo = Topology("t")
        topo.zone("a")
        with pytest.raises(TopologyError):
            topo.zone("a")
        with pytest.raises(TopologyError):
            topo.zone("b", tier="orbit")

    def test_link_costs_and_tier_defaults(self):
        topo = Topology("t")
        topo.zone("cloud")
        topo.zone("edge", tier="edge")
        topo.link("cloud", "edge", bandwidth_mbps=100, energy_j_per_mb=0.05)
        # declared link, both directions (symmetric default)
        assert topo.cost("cloud", "edge").energy_j_per_mb == 0.05
        assert topo.cost("edge", "cloud").energy_j_per_mb == 0.05
        # undeclared pair falls back to tier defaults
        topo.zone("dev", tier="device")
        assert topo.cost("edge", "dev").bandwidth_mbps > 0
        # self-edge is free
        assert topo.cost("cloud", "cloud").energy_j_per_mb == 0.0
        # energy scales with bytes
        assert topo.transfer_energy_j("cloud", "edge", 2_000_000) == pytest.approx(0.1)

    def test_three_zone_canned(self):
        topo = Topology.three_zone()
        assert topo.zone_names() == ["cloud", "edge", "device"]
        assert topo.default_zone == "cloud"
        assert topo.tier_of("device") == "device"

    def test_default_topology_env(self, monkeypatch):
        monkeypatch.delenv("KOALJA_TOPOLOGY", raising=False)
        assert default_topology() is None
        monkeypatch.setenv("KOALJA_TOPOLOGY", "flat")
        assert default_topology() is None
        monkeypatch.setenv("KOALJA_TOPOLOGY", "3zone")
        assert default_topology().zone_names() == ["cloud", "edge", "device"]
        monkeypatch.setenv("KOALJA_TOPOLOGY", "klingon")
        with pytest.raises(ValueError):
            default_topology()


class TestLedger:
    def test_charge_once_per_zone_then_dedup(self):
        topo = Topology.three_zone()
        led = TransferLedger(topo)
        led.register_resident("h1", "edge")
        assert led.on_materialize("h1", 1000, "edge", "cloud") is True
        # second consumer in cloud: already resident there -> ghost credit
        assert led.on_materialize("h1", 1000, "edge", "cloud") is False
        assert led.bytes_moved_crosszone == 1000
        assert led.bytes_not_moved_crosszone == 1000
        assert led.stats()["by_pair"] == {"edge->cloud": 1000}

    def test_same_zone_is_free_handover(self):
        led = TransferLedger(Topology.three_zone())
        assert led.on_materialize("h1", 1000, "edge", "edge") is False
        assert led.local_handovers == 1
        assert led.bytes_moved_crosszone == 0

    def test_energy_priced_from_pair_totals(self):
        topo = Topology("t")
        topo.zone("cloud")
        topo.zone("edge", tier="edge")
        topo.link("cloud", "edge", energy_j_per_mb=0.05)
        led = TransferLedger(topo)
        led.on_materialize("h1", 1_000_000, "edge", "cloud")
        led.on_materialize("h2", 1_000_000, "edge", "cloud")
        assert led.transfer_energy_j == pytest.approx(0.1)


class TestPlacementPolicies:
    def test_make_placement_resolution(self):
        topo = Topology.three_zone()
        assert isinstance(make_placement("pin", topo), PinPlacement)
        assert isinstance(make_placement("data_gravity", topo), DataGravityPlacement)
        assert isinstance(make_placement(None, topo), DataGravityPlacement)
        pol = PinPlacement(topo)
        assert make_placement(pol, topo) is pol
        with pytest.raises(TopologyError):
            make_placement("teleport", topo)

    def test_policy_bound_to_foreign_topology_rejected(self):
        """A policy built against another topology would place tasks into
        zones this one never declared — fail at construction, not at the
        first stats() read."""
        mine, theirs = _iot_topology(), Topology.three_zone()
        with pytest.raises(TopologyError, match="bound to topology"):
            make_placement(PinPlacement(theirs), mine)
        ws = Workspace("w", topology=mine, placement=PinPlacement(theirs))
        ws.task(lambda x: {"y": x}, name="t", inputs=["x"], outputs=["y"])
        with pytest.raises(TopologyError):
            ws.push("t", x=1)

    def test_place_requires_topology_and_known_zone(self):
        ws = Workspace("flat", topology=False)
        t = ws.task(lambda x: {"y": x}, name="t", inputs=["x"], outputs=["y"])
        with pytest.raises(WiringError):
            t.place("cloud")
        ws2 = Workspace("topo", topology=Topology.three_zone())
        t2 = ws2.task(lambda x: {"y": x}, name="t", inputs=["x"], outputs=["y"])
        with pytest.raises(WiringError):
            t2.place("mars")
        assert t2.place("edge").zone == "edge"


# ---------------------------------------------------------------------------
# placement through the stack
# ---------------------------------------------------------------------------


class TestPinPlacement:
    def test_unpinned_tasks_run_in_default_zone(self):
        ws = _drive(_iot_ws("pin"))
        zones = ws.stats()["topology"]["zones"]
        # aggregators float -> default (cloud); sensors stay pinned at edge
        assert set(zones["cloud"]["tasks"]) >= {f"agg_{z}" for z in EDGE_ZONES}
        for z in EDGE_ZONES:
            assert f"s_{z}_0" in zones[z]["tasks"]

    def test_all_to_cloud_moves_raw_bytes(self):
        ws = _drive(_iot_ws("pin"), rounds=2, n=64, sensors=2)
        led = ws.stats()["topology"]["ledger"]
        # every raw reading crosses edge->cloud: 3 zones x 2 sensors x 2
        # rounds x 256B; aggregates are born in cloud and never cross
        assert led["bytes_moved_crosszone"] == 3 * 2 * 2 * 64 * 4
        assert all(pair.endswith("->cloud") for pair in led["by_pair"])
        assert led["transfer_energy_j"] > 0


class TestDataGravityPlacement:
    def test_aggregators_follow_their_bytes(self):
        ws = _drive(_iot_ws("data_gravity"))
        zones = ws.stats()["topology"]["zones"]
        for z in EDGE_ZONES:
            assert f"agg_{z}" in zones[z]["tasks"]
            assert zones[z]["executions"] >= 2  # sensors + aggregator ran there
        # the pinned reducer stays in cloud regardless of gravity
        assert "reduce" in zones["cloud"]["tasks"]

    def test_gravity_moves_only_aggregates(self):
        ws = _drive(_iot_ws("data_gravity"), rounds=2, n=64, sensors=2)
        led = ws.stats()["topology"]["ledger"]
        # only the 3 per-zone aggregates cross per round (256B each)
        assert led["bytes_moved_crosszone"] == 3 * 2 * 64 * 4
        assert led["bytes_moved_crosszone"] * 2 == 3 * 2 * 2 * 64 * 4

    def test_gravity_vs_pin_byte_reduction(self):
        pin = _drive(_iot_ws("pin")).stats()["topology"]["ledger"]
        grav = _drive(_iot_ws("data_gravity")).stats()["topology"]["ledger"]
        assert grav["bytes_moved_crosszone"] * 2 == pin["bytes_moved_crosszone"]
        assert grav["transfer_energy_j"] < pin["transfer_energy_j"]

    @pytest.mark.parametrize(
        "sensors,rounds,n,pin_bytes,gravity_bytes",
        [(4, 2, 128, 12_288, 3_072), (8, 3, 256, 73_728, 9_216)],
    )
    def test_fan_in_sets_the_byte_reduction(self, sensors, rounds, n, pin_bytes, gravity_bytes):
        """All-to-cloud moves every raw reading across a zone boundary,
        gravity only each zone's aggregate: the cut is the fan-in, 8x at 8
        sensors a zone (73,728 -> 9,216 B, 0.05 J/MB). Placement and zoned
        execution change neither the merge order nor the visitor events, and
        the zoned run charges the ledger exactly as the inline one."""
        runs = {
            label: _fingerprint(_drive(
                _iot_ws(placement, executor=ex, sensors=sensors),
                rounds=rounds, n=n, sensors=sensors,
            ))
            for label, placement, ex in (
                ("pin", "pin", None),
                ("gravity", "data_gravity", None),
                ("gravity_zoned", "data_gravity",
                 ZonedExecutor(inner=ConcurrentExecutor(max_workers=4))),
            )
        }
        grav = runs["gravity"]["ledger"]
        assert runs["pin"]["ledger"]["bytes_moved_crosszone"] == pin_bytes
        assert grav["bytes_moved_crosszone"] == gravity_bytes
        assert grav["transfer_energy_j"] == pytest.approx(gravity_bytes / 1e6 * 0.05)
        for key in ("merge_order", "events"):
            assert runs["pin"][key] == runs["gravity"][key] == runs["gravity_zoned"][key]
        assert runs["gravity_zoned"]["ledger"] == grav

    def test_pinned_tasks_resist_gravity(self):
        topo = Topology.three_zone()
        ws = Workspace("pins", topology=topo, placement="data_gravity", cache=False)
        src = ws.source(lambda: None, name="src", outputs=["x"]).place("edge")
        sink = ws.task(lambda x: {"y": float(np.sum(x))}, name="sink",
                       inputs=["x"], outputs=["y"]).place("cloud")
        src["x"] >> sink["x"]
        ws.push("src", x=np.ones(32, np.float32))
        zones = ws.stats()["topology"]["zones"]
        assert "sink" in zones["cloud"]["tasks"]  # pinned beats gravity
        assert ws.stats()["topology"]["ledger"]["bytes_moved_crosszone"] == 128

    def test_byte_shares_dedupe_by_uid(self):
        """An AV pending in more than one buffer of the same task (a window
        consumer holds values in both ``fresh`` and ``window``; a dual-wired
        output lands the same AV in two input buffers) exerts gravity once:
        shares weigh payload bytes resident in a zone, not reference count."""
        from types import SimpleNamespace as NS

        av1 = NS(uid="u1", meta={"zone": "edge", "nbytes": 256})
        av2 = NS(uid="u2", meta={"zone": "edge", "nbytes": 256})
        av3 = NS(uid="u3", meta={"zone": "cloud", "nbytes": 100})
        task = NS(policy=NS(buffers={
            "a": NS(fresh=[av1, av2], window=[av1]),  # av1 in both deques
            "b": NS(fresh=[av2], window=[av3]),  # av2 also wired to input b
        }))
        shares = DataGravityPlacement._byte_shares(task)
        assert shares == {"edge": 512, "cloud": 100}

    def test_byte_shares_pinned_for_window_consumer(self):
        """Regression: the pending byte shares of an ``input[N/k]`` consumer
        are exactly one count per resident AV — 4 window slots + 1 fresh
        arrival x 256B, never double-counted across the two deques."""
        topo = Topology.three_zone()
        ws = Workspace("w", topology=topo, placement="data_gravity", cache=False)
        src = ws.source(lambda x: {"x": x}, name="src", outputs=["x"]).place("edge")
        win = ws.task(lambda x: {"y": float(np.sum(x[-1]))}, name="win",
                      inputs=["x[4/2]"], outputs=["y"])
        src["x"] >> win["x"]
        for i in range(5):
            ws.push("src", x=np.full(64, float(i), np.float32))  # 256 B each
        task = ws.pipeline.tasks["win"]
        buf = task.policy.buffers["x"]
        assert (len(buf.window), len(buf.fresh)) == (4, 1)
        shares = DataGravityPlacement._byte_shares(task)
        assert shares == {"edge": 5 * 256}

    def test_crosszone_refs_counted_on_links(self):
        ws = _drive(_iot_ws("pin"))
        stats = ws.stats()
        # sensor->aggregator links cross edge->cloud carrying refs only
        assert stats["topology"]["crosszone_refs"] > 0
        link = stats["links"]["s_edge-a_0.reading->agg_edge-a.r0"]
        assert link["crosszone_refs"] > 0

    def test_crosszone_refs_judged_after_placement(self):
        """An aggregator that gravity co-locates with its sensors consumes
        in the same zone the AVs were born in: no ref crossing, even though
        its pre-placement zone was the cloud default."""
        ws = _drive(_iot_ws("data_gravity"))
        stats = ws.stats()
        for z in EDGE_ZONES:
            link = stats["links"][f"s_{z}_0.reading->agg_{z}.r0"]
            assert link["crosszone_refs"] == 0
        # while the aggregate->reducer links really do cross edge->cloud
        link = stats["links"]["agg_edge-a.agg->reduce.a_edge-a"]
        assert link["crosszone_refs"] > 0

    def test_memo_hit_replays_birth_zone(self):
        """A memo hit replays references to payloads resident where the
        original run executed — the minted AVs must carry that birth zone,
        not the replaying task's zone, or the ledger underbills."""
        from repro.cache import MemoCache
        from repro.core.store import ArtifactStore

        topo_a, topo_b = _iot_topology(), _iot_topology()
        store, cache = ArtifactStore(), MemoCache()

        def build(topo, pin_zone):
            ws = Workspace("memo-zone", topology=topo, placement="pin",
                           store=store, cache=cache)
            src = ws.source(lambda: None, name="src", outputs=["x"]).place(pin_zone)
            t = ws.task(lambda x: {"y": x * 2}, name="t",
                        inputs=["x"], outputs=["y"]).place(pin_zone)
            src["x"] >> t["x"]
            return ws

        x = np.ones(32, np.float32)
        ws_edge = build(topo_a, "edge-a")
        ws_edge.push("src", x=x)  # cold: executes in edge-a
        ws_cloud = build(topo_b, "cloud")
        ws_cloud.push("src", x=x)  # hit: replays in cloud
        t_cloud = ws_cloud.pipeline.tasks["t"]
        assert t_cloud.cache_hits == 1
        assert t_cloud.last_outputs["y"].zone == "edge-a"  # birth, not replay

    def test_ledger_dedup_on_identical_content(self):
        """Two consumers in one zone materializing the same content: bytes
        cross once; the second transfer is a hash-only ghost credit."""
        topo = Topology.three_zone()
        ws = Workspace("dedup", topology=topo, placement="pin", cache=False)
        src = ws.source(lambda: None, name="src", outputs=["x"]).place("edge")
        for i in range(2):
            t = ws.task(lambda x: {"y": float(np.sum(x))}, name=f"c{i}",
                        inputs=["x"], outputs=["y"]).place("cloud")
            src["x"] >> t["x"]
        ws.push("src", x=np.ones(64, np.float32))
        led = ws.stats()["topology"]["ledger"]
        assert led["bytes_moved_crosszone"] == 256
        assert led["bytes_not_moved_crosszone"] == 256


class TestEnergyAwarePlacement:
    """ISSUE 10: the ``energy`` policy minimizes transfer + compute joules
    as a pure function of (topology, pending bytes, coefficients)."""

    def _wan_topology(self):
        """Cheap radio hop to the edge, metered WAN to the cloud, compute
        priced by tier defaults (cloud 0.02 < edge 0.05 < device 0.12)."""
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

    def test_registered_and_env_valid(self):
        from repro.topology import EnergyAwarePlacement

        topo = self._wan_topology()
        pol = make_placement("energy", topo)
        assert isinstance(pol, EnergyAwarePlacement)
        assert isinstance(pol, DataGravityPlacement)  # shares _byte_shares

    def test_minimizes_transfer_plus_compute(self):
        """Device-born bytes: gravity would keep the consumer on the
        battery-powered device (0.12 J/MB compute); energy pays the cheap
        radio hop (0.01) to the edge's 0.05 compute instead."""
        from types import SimpleNamespace as NS

        topo = self._wan_topology()
        pol = make_placement("energy", topo)
        av = NS(uid="u1", meta={"zone": "device", "nbytes": 1_000_000})
        task = NS(pinned_zone=None, zone=None,
                  policy=NS(buffers={"x": NS(fresh=[av], window=[])}))
        assert pol.zone_for(task, None) == "edge"
        # gravity on the same pending bytes stays at the device
        assert make_placement("data_gravity", topo).zone_for(task, None) == "device"

    def test_pin_and_empty_buffers_respected(self):
        from types import SimpleNamespace as NS

        topo = self._wan_topology()
        pol = make_placement("energy", topo)
        pinned = NS(pinned_zone="device", zone=None, policy=NS(buffers={}))
        assert pol.zone_for(pinned, None) == "device"
        idle = NS(pinned_zone=None, zone=None, policy=NS(buffers={}))
        assert pol.zone_for(idle, None) == "cloud"  # default zone

    def test_through_the_stack_lands_on_edge(self):
        ws = Workspace("energy", topology=self._wan_topology(),
                       placement="energy", cache=False)
        src = ws.source(lambda x: {"x": x}, name="src",
                        outputs=["x"]).place("device")
        t = ws.task(lambda x: {"y": float(np.sum(x))}, name="analyze",
                    inputs=["x"], outputs=["y"])
        src["x"] >> t["x"]
        ws.push("src", x=np.ones(65536, np.float32))
        zones = ws.stats()["topology"]["zones"]
        assert "analyze" in zones["edge"]["tasks"]
        led = ws.stats()["topology"]["ledger"]
        assert led["compute_energy_j"] > 0
        assert led["total_energy_j"] == pytest.approx(
            led["transfer_energy_j"] + led["compute_energy_j"]
        )


# ---------------------------------------------------------------------------
# determinism across executors (the ISSUE 4 contract)
# ---------------------------------------------------------------------------


def _fingerprint(ws):
    """Everything that must be identical across executor backends."""
    stats = ws.stats()
    merge_order = ws.value_of(ws.pipeline.tasks["reduce"].last_outputs["total"])
    events = sorted(
        (t, e["event"]) for t in ws.tasks() for e in ws.visitor_log(t)
    )
    return {
        "merge_order": merge_order,
        "events": events,
        "ledger": stats["topology"]["ledger"],
        "placement_by_zone": stats["topology"]["placement"]["by_zone"],
        "zone_executions": {
            z: v["executions"] for z, v in stats["topology"]["zones"].items()
        },
        "sustainability": stats["sustainability"],
    }


class TestExecutorDeterminism:
    @pytest.mark.parametrize("placement", ["pin", "data_gravity", "energy"])
    def test_identical_across_backends(self, placement):
        from repro.runtime import ProcessExecutor, ZonedProcessExecutor

        backends = [
            InlineExecutor(),
            ConcurrentExecutor(max_workers=4),
            ZonedExecutor(),
            ZonedExecutor(inner=ConcurrentExecutor(max_workers=4)),
            ProcessExecutor(max_workers=4),
            ZonedProcessExecutor(max_workers=4),
            AdaptiveExecutor(min_workers=1, max_workers=4),
            ZonedExecutor(inner=AdaptiveExecutor(min_workers=1, max_workers=4)),
        ]
        prints = []
        for ex in backends:
            prints.append(
                _fingerprint(_drive(_iot_ws(placement, executor=ex), rounds=2))
            )
            if hasattr(ex, "shutdown"):
                ex.shutdown()
        for other in prints[1:]:
            assert other == prints[0]

    @pytest.mark.parametrize("placement", ["pin", "data_gravity", "energy"])
    def test_identical_across_backends_with_coalescing(self, placement):
        """Arrival coalescing (PR 8) regroups firings inside one execute
        call; merge-FCFS order, visitor events, ledger bytes, and zone
        executions must stay bit-identical to the uncoalesced schedule on
        every backend."""
        from repro.runtime import ProcessExecutor, ZonedProcessExecutor

        baseline = _fingerprint(_drive(_iot_ws(placement), rounds=2))
        backends = [
            InlineExecutor(),
            ConcurrentExecutor(max_workers=4),
            ZonedExecutor(),
            ZonedExecutor(inner=ConcurrentExecutor(max_workers=4)),
            ProcessExecutor(max_workers=4),
            ZonedProcessExecutor(max_workers=4),
            AdaptiveExecutor(min_workers=1, max_workers=4),
            ZonedExecutor(inner=AdaptiveExecutor(min_workers=1, max_workers=4)),
        ]
        for ex in backends:
            ws = _drive(_iot_ws(placement, executor=ex, coalesce=4), rounds=2)
            print_ = _fingerprint(ws)
            if hasattr(ex, "shutdown"):
                ex.shutdown()
            assert print_ == baseline

    def test_zoned_executor_partitions_by_zone(self):
        ex = ZonedExecutor(inner=ConcurrentExecutor(max_workers=4))
        ws = _drive(_iot_ws("data_gravity", executor=ex))
        topo_stats = ws.stats()["topology"]
        assert set(topo_stats["executor_zones"]) >= set(EDGE_ZONES)
        for z in EDGE_ZONES:
            assert topo_stats["executor_zones"][z]["tasks"] > 0
        assert ex.stats()["inner"]["backend"] == "ConcurrentExecutor"

    def test_zoned_executor_flat_circuit_passthrough(self):
        ws = Workspace("flat", topology=False, executor=ZonedExecutor(), cache=False)
        a = ws.task(lambda x: {"y": x + 1}, name="a", inputs=["x"], outputs=["y"])
        b = ws.task(lambda x: {"y": x + 1}, name="b", inputs=["x"], outputs=["y"])
        a["y"] >> b["x"]
        ws.push("a", x=1)
        assert ws.value_of(ws.pipeline.tasks["b"].last_outputs["y"]) == 3
        assert ws.stats()["topology"] is None

    def test_pull_mode_places_too(self):
        ws = _iot_ws("data_gravity")
        _drive(ws, rounds=1)
        out = ws.pull("reduce")
        assert "total" in out
        zones = ws.stats()["topology"]["zones"]
        assert "reduce" in zones["cloud"]["tasks"]


class TestStatsSurface:
    def test_topology_block_shape(self):
        ws = _drive(_iot_ws("data_gravity"))
        block = ws.stats()["topology"]
        assert block["name"] == "iot"
        assert block["default_zone"] == "cloud"
        assert block["placement"]["policy"] == "data_gravity"
        assert set(block["zones"]) == {"cloud", *EDGE_ZONES}
        for key in ("bytes_moved_crosszone", "transfer_energy_j", "by_pair"):
            assert key in block["ledger"]

    def test_flat_workspace_has_none_block(self):
        ws = Workspace("flat", topology=False, cache=False)
        ws.task(lambda x: {"y": x}, name="t", inputs=["x"], outputs=["y"])
        ws.push("t", x=1)
        assert ws.stats()["topology"] is None

    def test_duplicate_input_wire_rejected(self):
        """Fan-in must use distinct inputs: a second wire into an occupied
        input would shadow the first link and starve the sweep forever."""
        ws = Workspace("dup", topology=False)
        a = ws.task(lambda x: {"y": x}, name="a", inputs=["x"], outputs=["y"])
        b = ws.task(lambda x: {"y": x}, name="b", inputs=["x"], outputs=["y"])
        c = ws.task(lambda x: {"y": x}, name="c", inputs=["x"], outputs=["y"])
        a["y"] >> c["x"]
        b["y"] >> c["x"]
        with pytest.raises(ValueError, match="already wired"):
            ws.push("a", x=1)


# ---------------------------------------------------------------------------
# property: gravity never loses to all-to-cloud on reducer fan-ins
# ---------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    sensors=st.integers(1, 4),
    rounds=st.integers(1, 3),
    n=st.integers(8, 96),
    n_zones=st.integers(1, 3),
)
def test_data_gravity_never_moves_more_bytes(sensors, rounds, n, n_zones):
    """On reducer fan-ins (outputs no larger than any input — the IoT
    regime B10 models), co-locating with the majority share can only cut
    cross-zone bytes: gravity <= all-to-cloud, with identical results."""
    zones = EDGE_ZONES[:n_zones]
    pin = _drive(
        _iot_ws("pin", sensors=sensors, zones=zones),
        rounds=rounds, n=n, sensors=sensors, zones=zones, seed=n,
    )
    grav = _drive(
        _iot_ws("data_gravity", sensors=sensors, zones=zones),
        rounds=rounds, n=n, sensors=sensors, zones=zones, seed=n,
    )
    pin_led = pin.stats()["topology"]["ledger"]
    grav_led = grav.stats()["topology"]["ledger"]
    assert grav_led["bytes_moved_crosszone"] <= pin_led["bytes_moved_crosszone"]
    assert grav_led["transfer_energy_j"] <= pin_led["transfer_energy_j"] + 1e-12
    # placement changes where work runs, never what it computes
    assert pin.value_of(
        pin.pipeline.tasks["reduce"].last_outputs["total"]
    ) == grav.value_of(grav.pipeline.tasks["reduce"].last_outputs["total"])
