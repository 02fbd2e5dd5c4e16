"""The circuit's spans on the profiler's clock (``repro.core.spans``): their
names, nesting and arguments in a traced push, the device→host byte count,
the GC hook, and a push with no profiler running."""

from __future__ import annotations

import gc

import jax.numpy as jnp
import numpy as np
import pytest

import spantrace
from repro.core import spans
from repro.core.hashing import hashing_stats
from repro.workspace import ConcurrentExecutor, InlineExecutor, Workspace

STAGES = {
    "koalja:hash", "koalja:store.put", "koalja:store.get",
    "koalja:journal.append", "koalja:journal.fsync", "koalja:gc",
}


def _serving(tmp_path, fn, **ws_kwargs):
    ws = Workspace(
        "spans", executor=InlineExecutor(), topology=False,
        journal_path=str(tmp_path / "journal.jsonl"), **ws_kwargs,
    )
    task = ws.task(fn, name="generate", inputs=["prompt", "gen"], outputs=["tokens", "logits"])
    return ws, task


def _device_outputs(prompt, gen):
    return {"tokens": jnp.asarray(prompt) + gen, "logits": jnp.ones((4, 8), jnp.bfloat16)}


def _host_outputs(prompt, gen):
    return {"tokens": np.asarray(prompt) + gen, "logits": np.ones((4, 8), np.float32)}


def _traced_push(tmp_path, ws, task, gen=2):
    prompt = np.arange(4, dtype=np.int32)
    return spantrace.record(lambda: ws.push(task, prompt=prompt, gen=gen), tmp_path / "trace")


def _one(found, name):
    named = [s for s in found if s.name == name]
    assert len(named) == 1, (name, named)
    return named[0]


def test_push_span_tree(tmp_path):
    ws, task = _serving(tmp_path, _device_outputs, journal_flush_every_n=1)
    ws.push(task, prompt=np.zeros(4, np.int32), gen=1)
    _, found = _traced_push(tmp_path, ws, task)
    push = _one(found, "koalja:push")
    assert push.args == {"push": 2, "task": "generate"}
    run = _one(found, "koalja:task")
    assert run.within(push) and run.args == {"task": "generate", "push": 2}
    for s in found:
        assert s is push or s.within(push), s
        assert s in (push, run) or s.name in STAGES, s
    # the outputs are hashed in the push itself, the pushed inputs inside
    # their store.put; each fsync inside the append that triggered it
    puts = [s for s in found if s.name == "koalja:store.put"]
    hashes = [s for s in found if s.name == "koalja:hash"]
    outputs = [h for h in hashes if not any(h.within(p) for p in puts)]
    assert [h.args["payloads"] for h in outputs] == [2]
    assert sum(any(h.within(p) for p in puts) for h in hashes) == 2
    assert all(p.args["tier"] == "local" for p in puts)
    fsyncs = [s for s in found if s.name == "koalja:journal.fsync"]
    appends = [s for s in found if s.name == "koalja:journal.append"]
    assert fsyncs and all(any(f.within(a) for a in appends) for f in fsyncs)
    assert sum(a.args["records"] for a in appends) >= len(appends)
    gets = [s for s in found if s.name == "koalja:store.get"]
    assert len(gets) == 2 and all(g.end <= run.start for g in gets)


@pytest.mark.parametrize("fn, device", [(_device_outputs, True), (_host_outputs, False)])
def test_d2h_bytes_counts_device_outputs(tmp_path, fn, device):
    ws, task = _serving(tmp_path, fn)
    before = hashing_stats()["d2h_bytes"]
    res, found = _traced_push(tmp_path, ws, task)
    moved = hashing_stats()["d2h_bytes"] - before
    out = res[task]
    nbytes = out["tokens"].nbytes + out["logits"].nbytes
    hashed = [s for s in found if s.name == "koalja:hash" and s.args["payloads"] == 2]
    assert len(hashed) == 1 and hashed[0].args["nbytes"] == nbytes
    want = nbytes if device else 0
    assert hashed[0].args["d2h_bytes"] == want
    assert sum(s.args["d2h_bytes"] for s in found if s.name == "koalja:hash") == want
    assert moved == want


def test_forced_gc_in_a_task_leaves_a_gc_span(tmp_path):
    def collecting(prompt, gen):
        gc.collect()
        return _host_outputs(prompt, gen)

    ws, task = _serving(tmp_path, collecting)
    _, found = _traced_push(tmp_path, ws, task)
    run = _one(found, "koalja:task")
    inside = [s for s in found if s.name == "koalja:gc" and s.within(run)]
    assert [s.args["generation"] for s in inside] == [2]
    assert inside[0].args["collected"] >= 0
    assert gc.callbacks.count(spans._on_gc) == 1  # one hook, however many workspaces


def test_push_without_a_profiler_is_unchanged(tmp_path):
    ws, task = _serving(tmp_path, _host_outputs)
    assert not spans.enabled()
    res = ws.push(task, prompt=np.arange(4, dtype=np.int32), gen=3)
    np.testing.assert_array_equal(res[task]["tokens"], np.arange(3, 7))
    assert spans.PUSH.get() == -1  # the push's id does not outlive it
    ws.push(task, prompt=np.arange(4, dtype=np.int32), gen=3)
    st = ws.stats()
    assert st["executor"]["pushes"] == 2 and st["sustainability"]["cache_hits"] == 1
    assert "encode_wall_s" not in st["journal"] and st["journal"]["fsync_s"] >= 0.0


def test_a_failing_push_resets_its_id(tmp_path):
    def failing(prompt, gen):
        raise RuntimeError("user code failed")

    ws, task = _serving(tmp_path, failing)
    with pytest.raises(RuntimeError, match="user code failed"):
        ws.push(task, prompt=np.arange(4), gen=1)
    assert spans.PUSH.get() == -1


def test_pooled_tasks_carry_their_push(tmp_path):
    ws = Workspace("pool", executor=ConcurrentExecutor(max_workers=2), topology=False,
                   journal_path=False)
    src = ws.task(lambda x: x + 1, name="src", inputs=["x"], outputs=["y"])
    left = ws.task(lambda y: y * 2, name="left", inputs=["y"], outputs=["z"])
    right = ws.task(lambda y: y * 3, name="right", inputs=["y"], outputs=["z"])
    src["y"] >> left["y"]
    src["y"] >> right["y"]
    ws.push(src, x=np.zeros(2))
    _, found = spantrace.record(lambda: ws.push(src, x=np.ones(2)), tmp_path / "trace")
    push = _one(found, "koalja:push")
    tasks = {s.args["task"]: s for s in found if s.name == "koalja:task"}
    assert set(tasks) == {"src", "left", "right"}
    assert {s.args["push"] for s in tasks.values()} == {2}
    assert tasks["src"].within(push)
    assert {tasks["left"].thread, tasks["right"].thread} - {push.thread}
