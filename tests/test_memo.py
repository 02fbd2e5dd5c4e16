"""repro.cache — semantic memoization threaded through the engine.

Covers the §III.F sustainability pillar: snapshot keys (version + ordered
input hashes + policy mode), push/pull short-circuiting with cache_hit
visitor events, memo_of lineage back-pointers, version invalidation,
ghost-run zero-materialization, counter surfacing through Workspace.stats()
and executors, and the repeated-push acceptance counts.
"""

import numpy as np
import pytest

from repro.cache import MemoCache, make_record, snapshot_key
from repro.workspace import Workspace


def _two_stage(calls=None):
    calls = calls if calls is not None else []
    ws = Workspace("memo")

    def stage_a(x):
        calls.append("a")
        return {"y": x * 2.0}

    def stage_b(y):
        calls.append("b")
        return {"z": float(np.sum(y))}

    a = ws.task(stage_a, name="a", inputs=["x"], outputs=["y"])
    b = ws.task(stage_b, name="b", inputs=["y"], outputs=["z"])
    a["y"] >> b["y"]
    return ws, calls


# ---------------------------------------------------------------------------
# snapshot_key semantics
# ---------------------------------------------------------------------------


def test_snapshot_key_includes_policy_mode():
    h = {"x": "abc123"}
    assert snapshot_key("v1", h, policy_mode="all_new") != snapshot_key(
        "v1", h, policy_mode="merge"
    )
    assert snapshot_key("v1", h) == snapshot_key("v1", dict(h))


def test_snapshot_key_buffer_order_significant():
    assert snapshot_key("v", {"x": ["h1", "h2"]}) != snapshot_key(
        "v", {"x": ["h2", "h1"]}
    )


# ---------------------------------------------------------------------------
# push-mode short-circuit
# ---------------------------------------------------------------------------


def test_repeated_push_short_circuits_and_logs_cache_hits():
    ws, calls = _two_stage()
    x = np.arange(8.0)
    ws.push("a", x=x)
    ws.push("a", x=x)
    ws.push("a", x=x)
    assert calls == ["a", "b"], "user code must run exactly once per task"

    for task in ("a", "b"):
        events = [e["event"] for e in ws.visitor_log(task)]
        assert events.count("cache_hit") == 2
        assert events.count("executed") == 1

    s = ws.stats()["sustainability"]
    assert s["executions"] == 2
    assert s["cache_hits"] == 4
    assert s["executions_avoided"] == 4
    assert s["bytes_not_moved"] > 0


def test_changed_content_misses():
    ws, calls = _two_stage()
    ws.push("a", x=np.arange(8.0))
    ws.push("a", x=np.arange(8.0) + 1)  # different content hash
    assert calls == ["a", "b", "a", "b"]


def test_memo_hit_payload_still_retrievable():
    ws, _ = _two_stage()
    x = np.arange(8.0)
    ws.push("a", x=x)
    second = ws.push("a", x=x)
    # the memo AV's (uri, chash) reference resolves to the original payload
    assert second["b"]["z"] == float(np.sum(x * 2.0))


# ---------------------------------------------------------------------------
# forensic reconstruction across a hit
# ---------------------------------------------------------------------------


def test_memo_lineage_points_at_original_run():
    ws, _ = _two_stage()
    x = np.arange(4.0)
    first = ws.push("a", x=x)
    second = ws.push("a", x=x)
    orig_av = first["b"].av("z")
    hit_av = second["b"].av("z")
    assert hit_av.uid != orig_av.uid
    assert hit_av.meta["cache_hit"] is True
    assert hit_av.meta["memo_of"] == orig_av.uid

    lin = ws.lineage(hit_av)
    assert lin["cache_hit"] is True
    assert lin["memo_of"]["uid"] == orig_av.uid
    assert lin["memo_of"]["chash"] == hit_av.chash
    assert lin["memo_of"]["parents"], "original inputs reconstruct"

    # the visitor-log entry names the original run too
    hits = [e for e in ws.visitor_log("b") if e["event"] == "cache_hit"]
    assert hits and hits[0]["note"] == f"memo_of={orig_av.uid}"


def test_invalidate_version_forces_recompute():
    ws, calls = _two_stage()
    x = np.arange(8.0)
    ws.push("a", x=x)
    version = ws.pipeline.tasks["a"].version
    assert ws.manager.cache.invalidate_version(version) == 1
    ws.push("a", x=x)
    # 'a' recomputes; its output content is unchanged so 'b' still hits
    assert calls == ["a", "b", "a"]


# ---------------------------------------------------------------------------
# pull mode and sensors
# ---------------------------------------------------------------------------


def test_pull_mode_uses_memo():
    ws, calls = _two_stage()
    x = np.arange(8.0)
    ws.push("a", x=x)
    ws.inject("a", "x", x)
    out = ws.pull("b")
    assert out["z"] == float(np.sum(x * 2.0))
    assert calls == ["a", "b"], "pull over unchanged inputs is all hits"


def test_source_tasks_never_cache():
    ws = Workspace("sensor")

    def clock():
        return {"t": 42}  # constant output — still must never memoize

    ws.source(clock, name="clock", outputs=["t"])
    ws.sample("clock")
    ws.sample("clock")
    assert ws.pipeline.tasks["clock"].executions == 2
    assert ws.pipeline.tasks["clock"].cache_hits == 0


def test_cache_disabled_executes_every_time():
    calls = []
    ws = Workspace("nocache", cache=False)

    def f(x):
        calls.append(1)
        return {"y": x + 1}

    ws.task(f, name="f", inputs=["x"], outputs=["y"])
    ws.push("f", x=3)
    ws.push("f", x=3)
    assert len(calls) == 2
    assert ws.stats()["cache"] is None


# ---------------------------------------------------------------------------
# ghost runs never materialize
# ---------------------------------------------------------------------------


def test_ghost_run_moves_zero_bytes():
    import jax
    import jax.numpy as jnp

    ws, _ = _two_stage()
    report = ws.ghost({("a", "x"): jax.ShapeDtypeStruct((8,), jnp.float32)})
    store = ws.store.stats()
    assert store["puts"] == 0 and store["gets"] == 0 and store["pins"] == 0
    assert store["local_bytes"] == 0
    assert report["tasks"]["a"]["executions"] == 1
    # ghost firings are not memoized: a later real push still executes
    ws.push("a", x=np.arange(8.0, dtype=np.float32))
    assert ws.pipeline.tasks["a"].executions == 2
    assert ws.pipeline.tasks["a"].cache_hits == 0


def test_shared_fn_different_output_names_do_not_collide():
    """Two tasks wrapping the same fn but promising different output names
    are different computations: a replayed record must not emit the wrong
    names (which would silently drop the emission downstream)."""

    def double(x):
        return x * 2

    ws = Workspace("twins")
    a = ws.task(double, name="a", inputs=["x"], outputs=["y"])
    b = ws.task(double, name="b", inputs=["x"], outputs=["z"])
    sink_calls = []
    sink = ws.task(lambda z: sink_calls.append(z) or {"ok": 1},
                   name="sink", inputs=["z"], outputs=["ok"])
    b["z"] >> sink["z"]

    x = np.arange(4.0)
    ws.push("a", x=x)
    ws.push("b", x=x)  # must not replay a's record under b's promise
    assert ws.pipeline.tasks["b"].last_outputs.keys() == {"z"}
    assert len(sink_calls) == 1, "b's downstream sink must fire"


def test_shared_memo_cache_across_stores_recomputes_not_crashes():
    """A MemoCache shared across workspaces (each with its own store) must
    treat foreign-store records as misses, not replay dangling URIs."""
    from repro.cache import MemoCache

    shared = MemoCache()
    calls = []

    def build():
        ws = Workspace("w", cache=shared)

        def f(x):
            calls.append(1)
            return {"y": x + 1}

        g = ws.task(lambda y: {"z": y * 3}, name="g", inputs=["y"], outputs=["z"])
        h = ws.task(f, name="f", inputs=["x"], outputs=["y"])
        h["y"] >> g["y"]
        return ws

    x = np.arange(4.0)
    ws1, ws2 = build(), build()
    r1 = ws1.push("f", x=x)
    r2 = ws2.push("f", x=x)  # ws2's store has none of ws1's payloads
    np.testing.assert_array_equal(r2["g"]["z"], (x + 1) * 3)
    assert len(calls) == 2, "foreign-store record must recompute"


# ---------------------------------------------------------------------------
# MemoCache unit behavior
# ---------------------------------------------------------------------------


def test_credit_hit_accounting():
    cache = MemoCache()
    rec = make_record("v1", {"y": ("local://h", "h")}, {"y": "av-1"}, {"y": 100})
    cache.insert("k", rec)
    assert cache.lookup("k") is rec
    assert cache.credit_hit(rec) == 100
    assert cache.stats()["executions_avoided"] == 1
    assert cache.stats()["bytes_saved"] == 100


def test_executor_stats_surface():
    ws, _ = _two_stage()
    ws.push("a", x=np.arange(4.0))
    ex = ws.stats()["executor"]
    # the default backend is env-selected (KOALJA_EXECUTOR): assert the
    # selection contract, not just self-reporting
    import os

    env = os.environ.get("KOALJA_EXECUTOR", "inline").strip().lower()
    if env in ("concurrent", "threads", "threadpool"):
        expected = "ConcurrentExecutor"
    elif env in ("zoned", "zoned-concurrent", "zoned_concurrent"):
        expected = "ZonedExecutor"
    else:
        expected = "InlineExecutor"
    assert ex["backend"] == expected
    assert ex["pushes"] == 1


# ---------------------------------------------------------------------------
# repeated-push acceptance: 10x fewer executions, bytes not moved
# ---------------------------------------------------------------------------


def test_repeated_push_benchmark_acceptance():
    """Ten pushes of one unchanged 128x128 float64 array through x @ x.T ->
    column sums: only the first push executes, so the circuit runs 10.0x
    fewer times than its 20 cache-less firings, and the nine memo hits leave
    9 x (x + y + z) = 9 x 263,168 = 2,368,512 bytes unmoved."""
    ws = Workspace("repeated", topology=False)
    a = ws.task(lambda x: {"y": x @ x.T}, name="a", inputs=["x"], outputs=["y"])
    b = ws.task(lambda y: {"z": y.sum(axis=0)}, name="b", inputs=["y"], outputs=["z"])
    a["y"] >> b["y"]
    x = np.random.RandomState(0).randn(128, 128)
    for _ in range(10):
        ws.push(a, x=x)
    stats = ws.stats()
    executions = sum(t["executions"] for t in stats["tasks"].values())
    cache_hit_events = sum(
        e["event"] == "cache_hit" for t in stats["tasks"] for e in ws.visitor_log(t)
    )
    assert 10 * len(stats["tasks"]) / executions == 10.0
    assert stats["sustainability"]["bytes_not_moved"] == 2_368_512
    assert cache_hit_events == 18
