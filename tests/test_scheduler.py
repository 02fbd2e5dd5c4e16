"""Event-driven scheduler core (ISSUE 3): notification-driven ready queue,
concurrent executor waves, bounded links/backpressure, notify-threshold
poll-mode fast path, and pull-mode edge cases."""

import threading
import time

import numpy as np
import pytest

from repro.core import (
    AnnotatedValue,
    ArtifactStore,
    LinkBackpressureError,
    Pipeline,
    PipelineManager,
    SmartLink,
    SmartTask,
)
from repro.workspace import ConcurrentExecutor, InlineExecutor, Workspace


# ---------------------------------------------------------------------------
# circuits
# ---------------------------------------------------------------------------


def _chain_ws(n=3, executor=None, cache=False):
    """t0 -> t1 -> ... -> t{n-1}, each incrementing."""
    ws = Workspace("chain", executor=executor, cache=cache)
    prev = ws.task(lambda x: {"y": x + 1}, name="t0", inputs=["x"], outputs=["y"])
    for i in range(1, n):
        cur = ws.task(
            lambda x: {"y": x + 1}, name=f"t{i}", inputs=["x"], outputs=["y"]
        )
        prev["y"] >> cur["x"]
        prev = cur
    return ws


def _fanout_ws(width=4, heavy_ms=0.0, executor=None):
    """src fans out to `width` workers; workers merge-FCFS into a sink."""
    ws = Workspace("fanout", executor=executor)
    outs = [f"o{i}" for i in range(width)]

    def src(x):
        return {f"o{i}": x + i for i in range(width)}

    s = ws.task(src, name="src", inputs=["x"], outputs=outs)

    def work(v):
        if heavy_ms:
            time.sleep(heavy_ms / 1e3)
        return {"w": v * 10}

    sink_inputs = [f"i{i}" for i in range(width)]
    sink = ws.task(
        lambda merged: {"total": list(merged)},
        name="sink",
        inputs=sink_inputs,
        outputs=["total"],
        mode="merge",
    )
    for i in range(width):
        w = ws.task(work, name=f"w{i}", inputs=["v"], outputs=["w"])
        s[f"o{i}"] >> w["v"]
        w["w"] >> sink[f"i{i}"]
    return ws


def _diamond_ws(executor=None, cache=False):
    """     top
           /    \\
        left    right
           \\    /
            join          (swap_new_for_old)
    """
    ws = Workspace("diamond", executor=executor, cache=cache)
    top = ws.task(lambda x: {"y": x * 2}, name="top", inputs=["x"], outputs=["y"])
    left = ws.task(lambda y: {"l": y + 1}, name="left", inputs=["y"], outputs=["l"])
    right = ws.task(lambda y: {"r": y + 2}, name="right", inputs=["y"], outputs=["r"])
    join = ws.task(
        lambda l, r: {"s": l + r},
        name="join",
        inputs=["l", "r"],
        outputs=["s"],
        mode="swap_new_for_old",
    )
    top["y"] >> left["y"]
    top["y"] >> right["y"]
    left["l"] >> join["l"]
    right["r"] >> join["r"]
    return ws


# ---------------------------------------------------------------------------
# event-driven propagation (no polling scans)
# ---------------------------------------------------------------------------


def test_push_results_unchanged_and_no_polling():
    ws = _chain_ws(n=4)
    run = ws.push("t0", x=0)
    assert run["t3"]["y"] == 4
    sched = ws.stats()["scheduler"]
    # 4 tasks enqueued (one per chain stage), while a polling engine would
    # have scanned 4 tasks x (4 waves + quiescence round)
    assert sched["tasks_enqueued"] == 4
    assert sched["tasks_executed"] == 4
    assert sched["polling_scan_equivalent"] > 3 * sched["tasks_enqueued"]
    assert sched["waves"] == 4


def test_enqueued_scales_with_events_not_circuit_size():
    """The acceptance claim: tasks-enqueued << tasks-scanned-equivalent.
    A hot 2-task chain inside a 16-task circuit only ever enqueues the hot
    pair; polling would rescan all 16 every round."""
    ws = Workspace("sparse", cache=False)
    a = ws.task(lambda x: {"y": x}, name="hot_a", inputs=["x"], outputs=["y"])
    b = ws.task(lambda y: {"z": y}, name="hot_b", inputs=["y"], outputs=["z"])
    a["y"] >> b["y"]
    for i in range(14):
        ws.task(lambda q: {"r": q}, name=f"cold{i}", inputs=["q"], outputs=["r"])
    for i in range(10):
        ws.push("hot_a", x=i)
    sched = ws.stats()["scheduler"]
    assert sched["tasks_enqueued"] == 20  # 2 per push
    assert sched["polling_scan_equivalent"] >= 16 * 3 * 10
    assert sched["scan_reduction_x"] > 10


@pytest.mark.parametrize(
    "hot,cold,pushes,scan_reduction_x",
    [(1, 0, 5, 2.0), (2, 14, 10, 24.0), (3, 13, 200, 64 / 3)],
)
def test_scan_reduction_is_exact(hot, cold, pushes, scan_reduction_x):
    """A push down a hot chain of ``hot`` tasks enqueues exactly those
    tasks, while a polling engine scans the whole circuit once per wave and
    once more to find quiescence: hot + cold tasks x (hot + 1) rounds."""
    ws = _chain_ws(n=hot)
    for i in range(cold):
        ws.task(lambda q: {"r": q}, name=f"cold{i}", inputs=["q"], outputs=["r"])
    for i in range(pushes):
        ws.push("t0", x=i)
    sched = ws.stats()["scheduler"]
    assert sched["tasks_enqueued"] == hot * pushes
    assert sched["polling_scan_equivalent"] == (hot + cold) * (hot + 1) * pushes
    assert sched["scan_reduction_x"] == pytest.approx(scan_reduction_x, rel=1e-12)
    assert ws.value_of(ws.pipeline.tasks[f"t{hot - 1}"].last_outputs["y"]) == pushes - 1 + hot


def test_coalescing_cuts_waves_not_results():
    """400 arrivals through a two-stage chain: one wave per firing (800)
    uncoalesced, 26 with both stages coalescing up to 32 snapshots, and the
    same outputs and visitor events either way."""
    runs = {}
    for max_batch in (None, 32):
        ws = Workspace("coalesce", executor=InlineExecutor(), topology=False, cache=False)
        inc = ws.task(lambda x: {"y": x + 1.0}, name="inc", inputs=["x"], outputs=["y"])
        dbl = ws.task(lambda y: {"z": y * 2.0}, name="dbl", inputs=["y"], outputs=["z"])
        inc["y"] >> dbl["y"]
        if max_batch:
            inc.coalesce(max_batch)
            dbl.coalesce(max_batch)
        for i in range(400):
            ws.inject(inc, "x", np.full(8, float(i), np.float32))
        ws.manager.propagate()
        log = [e for t in ws.tasks() for e in ws.visitor_log(t)]
        runs[max_batch] = (
            ws.stats()["scheduler"]["waves"],
            [(e["task"], e["event"]) for e in log],
            [ws.registry.get_av(e["av_uid"]).chash for e in log if e["event"] == "emitted"],
        )
    assert runs[None][0] == 800 and runs[32][0] == 26
    assert runs[None][1:] == runs[32][1:]


def test_cycle_bounded_by_per_task_fire_budget():
    pipe = Pipeline("cyc")
    pipe._add_task(SmartTask("a", lambda x: {"y": x + 1}, ["x"], ["y"]))
    pipe._add_task(SmartTask("b", lambda y: {"x": y}, ["y"], ["x"]))
    pipe._connect("a", "y", "b", "y")
    pipe._connect("b", "x", "a", "x")
    mgr = PipelineManager(pipe, max_rounds=5, cache=False)
    fired = mgr._push("a", x=0)
    assert len(fired["a"]) <= 5  # per-task budget, not global rounds
    assert mgr.scheduler.stats()["budget_exhausted"] >= 1


def test_diamond_fires_once_per_push_no_glitch():
    """swap_new_for_old join must not fire early on the short diamond leg
    with a stale value (wave deferral = the old topological round order)."""
    ws = _diamond_ws()
    ws.push("top", x=1)
    ws.push("top", x=2)
    t = ws.pipeline.tasks["join"]
    assert t.executions + t.cache_hits == 2
    # l = 2x+1, r = 2x+2 -> s = 4x+3
    assert ws.value_of(t.last_outputs["s"]) == 11


def test_scheduler_stats_surface_in_workspace():
    ws = _chain_ws(n=2)
    ws.push("t0", x=1)
    sched = ws.stats()["scheduler"]
    for key in (
        "waves",
        "tasks_enqueued",
        "tasks_executed",
        "queue_depth_high_water",
        "polling_scan_equivalent",
        "notifications_received",
        "backend",
    ):
        assert key in sched
    assert sched["queue_depth_high_water"] >= 1
    assert ws.stats()["executor"]["waves_run"] == sched["waves"]


# ---------------------------------------------------------------------------
# concurrent executor waves
# ---------------------------------------------------------------------------


def test_concurrent_results_match_inline():
    runs = {}
    for name, ex in (("inline", InlineExecutor()), ("conc", ConcurrentExecutor(4))):
        ws = _fanout_ws(width=4, executor=ex)
        ws.push("src", x=100)
        sink = ws.pipeline.tasks["sink"]
        runs[name] = {
            "total": ws.value_of(sink.last_outputs["total"]),
            "sustainability": ws.stats()["sustainability"],
            "events": sorted(
                (t, e["event"])
                for t in ws.tasks()
                for e in ws.visitor_log(t)
            ),
        }
    # merge-FCFS order, sustainability counters, and provenance event
    # multiset are identical across backends (deferred serial emission)
    assert runs["inline"]["total"] == runs["conc"]["total"]
    assert runs["inline"]["sustainability"] == runs["conc"]["sustainability"]
    assert runs["inline"]["events"] == runs["conc"]["events"]


def test_concurrent_wave_actually_parallel():
    ws = _fanout_ws(width=4, heavy_ms=30.0, executor=ConcurrentExecutor(max_workers=4))
    t0 = time.perf_counter()
    ws.push("src", x=0)
    wall = time.perf_counter() - t0
    # 4 x 30ms serially would be >= 120ms; parallel should be well under
    assert wall < 0.100, f"fanout wave did not parallelize (wall={wall:.3f}s)"
    ex = ws.stats()["executor"]
    assert ex["parallel_waves"] >= 1
    assert ex["tasks_parallel"] >= 4


def test_concurrent_merge_order_deterministic_across_runs():
    def run_once():
        ws = _fanout_ws(width=6, heavy_ms=2.0, executor=ConcurrentExecutor(6))
        ws.push("src", x=0)
        sink = ws.pipeline.tasks["sink"]
        return ws.value_of(sink.last_outputs["total"])

    first = run_once()
    assert first == [i * 10 for i in range(6)]  # wave (emission) order
    for _ in range(3):
        assert run_once() == first


def test_mesh_executor_composes_with_concurrent_inner():
    from repro.workspace import MeshExecutor

    inner = ConcurrentExecutor(max_workers=2)
    ex = MeshExecutor(inner=inner)
    ws = _fanout_ws(width=3, executor=ex)
    ws.push("src", x=1)
    sink = ws.pipeline.tasks["sink"]
    assert ws.value_of(sink.last_outputs["total"]) == [10, 20, 30]
    assert ex.stats()["inner"]["waves_run"] >= 1


def test_default_executor_env_selection(monkeypatch):
    from repro.workspace import default_executor

    monkeypatch.delenv("KOALJA_EXECUTOR", raising=False)
    assert type(default_executor()).__name__ == "InlineExecutor"
    monkeypatch.setenv("KOALJA_EXECUTOR", "concurrent")
    monkeypatch.setenv("KOALJA_MAX_WORKERS", "3")
    ex = default_executor()
    assert type(ex).__name__ == "ConcurrentExecutor"
    assert ex.max_workers == 3
    monkeypatch.setenv("KOALJA_EXECUTOR", "bogus")
    with pytest.raises(ValueError):
        default_executor()


# ---------------------------------------------------------------------------
# bounded links / backpressure
# ---------------------------------------------------------------------------


def _offer(link, store, payload=1):
    uri, h = store.put(payload)
    av = AnnotatedValue.produce(h, uri, "a", "v")
    link.offer(av)
    return av


def test_bounded_link_drop_oldest():
    store = ArtifactStore()
    link = SmartLink("l", "a", "b", "x", capacity=2, overflow="drop_oldest")
    avs = [_offer(link, store, i) for i in range(4)]
    assert link.peek_count() == 2
    assert link.stats()["dropped"] == 2
    # ring semantics: the two newest survive
    assert link.poll().uid == avs[2].uid
    assert link.poll().uid == avs[3].uid


def test_bounded_link_error_policy():
    store = ArtifactStore()
    link = SmartLink("l", "a", "b", "x", capacity=1, overflow="error")
    _offer(link, store)
    with pytest.raises(LinkBackpressureError):
        _offer(link, store, 2)


def test_bounded_link_block_times_out_then_unblocks():
    store = ArtifactStore()
    link = SmartLink(
        "l", "a", "b", "x", capacity=1, overflow="block", block_timeout_s=0.05
    )
    _offer(link, store)
    t0 = time.perf_counter()
    with pytest.raises(LinkBackpressureError):
        _offer(link, store, 2)
    assert time.perf_counter() - t0 >= 0.04
    # a consumer draining from another thread releases the producer
    def drain_soon():
        time.sleep(0.02)
        link.poll()

    threading.Thread(target=drain_soon).start()
    link2 = link  # same bounded link; offer blocks briefly then succeeds
    _offer(link2, store, 3)
    assert link.stats()["blocked_waits"] >= 2


def test_block_link_inside_engine_never_stalls_or_loses():
    """The drain thread is both producer and consumer: a full block-policy
    link is relieved by the scheduler (drained into the consumer's policy
    buffer), not blocked against itself until timeout. Suppressed
    notifications keep the consumer from ingesting, so the producer's
    2nd..5th emissions in this drain genuinely hit a full link."""
    ws = Workspace("blockrelief", cache=False)
    a = ws.task(lambda x: {"y": x}, name="a", inputs=["x"], outputs=["y"])
    got = []
    b = ws.task(
        lambda y: got.append(y) or {"z": y}, name="b", inputs=["y"], outputs=["z"]
    )
    wire = a["y"] >> b["y"]
    wire.capacity(1, overflow="block", block_timeout_s=0.2)
    wire.notify_threshold(10.0)
    for i in range(5):
        ws.inject("a", "x", i)  # buffer 5 firings for one drain
    t0 = time.perf_counter()
    ws.manager.propagate()
    wall = time.perf_counter() - t0
    assert wall < 0.2, f"engine stalled on its own bounded link ({wall:.2f}s)"
    assert got == [0, 1, 2, 3, 4], "relief valve must not lose arrivals"
    assert ws.stats()["links"]["a.y->b.y"]["blocked_waits"] == 0


def test_fire_budget_does_not_strand_buffered_acyclic_work():
    """Seed parity: 150 pre-buffered arrivals drain fully in ONE propagate
    even though the per-task fire budget is 100 — self-requeues (draining
    one's own buffers) are exempt; only arrival-driven refires (cycles)
    are budgeted."""
    pipe = Pipeline("buffered")
    pipe._add_task(SmartTask("t", lambda x: {"y": x}, ["x"], ["y"]))
    mgr = PipelineManager(pipe, max_rounds=100, cache=False)
    for i in range(150):
        mgr._inject("t", "x", i)
    fired = mgr.propagate()
    assert len(fired["t"]) == 150
    assert mgr.pipeline.tasks["t"].policy.stats()["pending"]["x"] == 0


def test_throttled_cycle_resumes_on_next_propagate():
    """Seed parity: a budget-capped cycle picks up again when propagate()
    is called a second time (fresh per-drain budgets)."""
    pipe = Pipeline("cyc")
    pipe._add_task(SmartTask("a", lambda x: {"y": x + 1}, ["x"], ["y"]))
    pipe._add_task(SmartTask("b", lambda y: {"x": y}, ["y"], ["x"]))
    pipe._connect("a", "y", "b", "y")
    pipe._connect("b", "x", "a", "x")
    mgr = PipelineManager(pipe, max_rounds=3, cache=False)
    first = mgr._push("a", x=0)
    n1 = len(first.get("a", []))
    assert n1 <= 3
    second = mgr.propagate()
    assert len(second.get("a", [])) >= 1, "cycle resumes with a fresh budget"


def test_workspace_wire_capacity_fluent():
    ws = Workspace("bounded", cache=False)
    a = ws.task(lambda x: {"y": x}, name="a", inputs=["x"], outputs=["y"])
    b = ws.task(
        lambda y: {"z": y}, name="b", inputs=["y[2]"], outputs=["z"]
    )
    (a["y"] >> b["y"]).capacity(1, overflow="drop_oldest")
    ws.push("a", x=1)  # b needs 2 values; 1 sits on the bounded link
    link = ws.pipeline.links[0]
    assert link.capacity == 1 and link.overflow == "drop_oldest"


# ---------------------------------------------------------------------------
# notify_threshold_s: the poll-mode fast path (§III.J)
# ---------------------------------------------------------------------------


def test_notify_threshold_suppresses_but_loses_nothing():
    ws = Workspace("thresh", cache=False)
    a = ws.task(lambda x: {"y": x}, name="a", inputs=["x"], outputs=["y"])
    got = []
    b = ws.task(
        lambda y: got.append(y) or {"z": y}, name="b", inputs=["y"], outputs=["z"]
    )
    # arrivals far faster than 10s -> every offer after the first suppresses
    (a["y"] >> b["y"]).notify_threshold(10.0)
    for i in range(5):
        ws.push("a", x=i)
    assert got == [0, 1, 2, 3, 4], "suppressed arrivals still processed"
    link_stats = ws.stats()["links"]["a.y->b.y"]
    assert link_stats["notified"] == 1  # only the first arrival interrupted
    assert link_stats["suppressed"] == 4
    assert ws.stats()["scheduler"]["sweeps"] >= 1  # coalesced batch polls


def test_notify_threshold_zero_always_notifies():
    store = ArtifactStore()
    link = SmartLink("l", "a", "b", "x", notify_threshold_s=0.0)
    for i in range(3):
        _offer(link, store, i)
    assert link.stats()["notified"] == 3
    assert link.stats()["suppressed"] == 0


def test_notifications_counted_per_event_not_per_subscriber():
    store = ArtifactStore()
    link = SmartLink("l", "a", "b", "x")
    seen1, seen2 = [], []
    link.subscribe(lambda l, av: seen1.append(av.uid))
    link.subscribe(lambda l, av: seen2.append(av.uid))
    _offer(link, store)
    assert len(seen1) == len(seen2) == 1
    assert link.notifications_sent == 1  # one event, not two callbacks


def test_link_concurrent_offers_thread_safety():
    store = ArtifactStore()
    link = SmartLink("l", "a", "b", "x")
    seen = []
    link.subscribe(lambda l, av: seen.append(av.uid))
    uri, h = store.put(0)

    def spam(n):
        for _ in range(n):
            link.offer(AnnotatedValue.produce(h, uri, "a", "v"))

    threads = [threading.Thread(target=spam, args=(50,)) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert link.peek_count() == 200
    assert link.avs_carried == 200
    assert link.notifications_sent == 200
    assert len(seen) == 200


# ---------------------------------------------------------------------------
# pull-mode edge cases (satellite)
# ---------------------------------------------------------------------------


def _reference_recursive_pull(mgr, target, _visiting=None):
    """The seed's recursive pull, verbatim, as a behavioural oracle."""
    _visiting = _visiting if _visiting is not None else set()
    if target in _visiting:
        return mgr.pipeline.tasks[target].last_outputs
    _visiting.add(target)
    t = mgr.pipeline.tasks[target]
    for link in t.in_links.values():
        _reference_recursive_pull(mgr, link.src_task, _visiting)
    t.ingest()
    if t.ready():
        return t.execute(mgr.store, mgr.registry, mgr.cache)
    if t.source and not t.input_specs:
        return t.execute(mgr.store, mgr.registry, mgr.cache)
    if t.last_outputs:
        return t.last_outputs
    raise RuntimeError(f"pull({target}): no data")


def _pull_circuit():
    pipe = Pipeline("p")
    pipe._add_task(SmartTask("double", lambda x: {"y": x * 2}, ["x"], ["y"]))
    pipe._add_task(SmartTask("inc", lambda y: {"z": y + 1}, ["y"], ["z"]))
    pipe._add_task(
        SmartTask("add", lambda y, z: {"w": y + z}, ["y", "z"], ["w"],
                  mode="swap_new_for_old")
    )
    pipe._connect("double", "y", "inc", "y")
    pipe._connect("double", "y", "add", "y")
    pipe._connect("inc", "z", "add", "z")
    return pipe


def test_scheduler_pull_matches_recursive_oracle():
    mgr_new = PipelineManager(_pull_circuit())
    mgr_old = PipelineManager(_pull_circuit())
    mgr_new._push("double", x=21)
    mgr_old._push("double", x=21)
    out_new = mgr_new._pull("add")
    out_old = _reference_recursive_pull(mgr_old, "add")
    assert out_new.keys() == out_old.keys() == {"w"}
    assert mgr_new.value_of(out_new["w"]) == mgr_old.value_of(out_old["w"])
    # identical (re-)execution behaviour, not just identical values
    for name in ("double", "inc", "add"):
        assert (
            mgr_new.pipeline.tasks[name].executions
            == mgr_old.pipeline.tasks[name].executions
        )


def test_pull_cycle_guard_empty_last_outputs_raises():
    """A pure cycle with no data anywhere: the back-edge contributes empty
    last_outputs, so pull must fail loudly (matches the seed recursion)."""
    pipe = Pipeline("cyc")
    pipe._add_task(SmartTask("a", lambda x: {"y": x + 1}, ["x"], ["y"]))
    pipe._add_task(SmartTask("b", lambda y: {"x": y}, ["y"], ["x"]))
    pipe._connect("a", "y", "b", "y")
    pipe._connect("b", "x", "a", "x")
    mgr = PipelineManager(pipe, cache=False)
    with pytest.raises(RuntimeError, match="no prior"):
        mgr._pull("a")


def test_pull_cycle_with_prior_outputs_reuses_them():
    pipe = Pipeline("cyc2")
    pipe._add_task(SmartTask("a", lambda x: {"y": x + 1}, ["x"], ["y"]))
    pipe._add_task(SmartTask("b", lambda y: {"x": y}, ["y"], ["x"]))
    pipe._connect("a", "y", "b", "y")
    pipe._connect("b", "x", "a", "x")
    mgr = PipelineManager(pipe, max_rounds=3, cache=False)
    mgr._push("a", x=0)  # cycle spins up to the fire budget, leaves outputs
    out = mgr._pull("a")
    assert "y" in out


def test_repeated_pull_diamond_shared_ancestor_executes_once():
    ws = _diamond_ws(cache=False)
    ws.push("top", x=3)
    execs_after_push = {n: ws.pipeline.tasks[n].executions for n in ws.tasks()}
    assert execs_after_push["top"] == 1
    first = ws.pull("join")
    second = ws.pull("join")
    # nothing new arrived: both pulls resolve from prior outputs; the shared
    # ancestor (and everything else) never re-executes
    for name in ("top", "left", "right", "join"):
        assert ws.pipeline.tasks[name].executions == execs_after_push[name]
    assert first["s"] == second["s"] == (2 * 3 + 1) + (2 * 3 + 2)


def test_pull_unknown_task_raises_keyerror():
    ws = _chain_ws(n=2)
    with pytest.raises(KeyError):
        ws.manager._pull("nope")
