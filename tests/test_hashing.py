"""Batched content hashing (ISSUE 8): batch==scalar across payload tiers,
cross-process digest stability (the repr-fallback fix), the >4 MiB tree
digest vs its numpy/jnp/pallas references, and unstable-hash anomalies."""

import dataclasses
import os
import pickle

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.av import content_hash as content_hash_av
from repro.core.hashing import (
    LARGE_ARRAY_BYTES,
    TREE_BLOCK_WORDS,
    content_hash,
    content_hash_batch,
    hashing_stats,
    tree_digest,
    tree_state_np,
)


@dataclasses.dataclass
class Reading:
    sensor: str
    values: tuple
    ok: bool = True


def _payload_zoo():
    rng = np.random.RandomState(0)
    return [
        rng.randn(64).astype(np.float32),
        np.asfortranarray(rng.randn(8, 8)),
        np.arange(100)[::3],  # non-contiguous
        np.float64(3.25),  # 0-d
        np.array([], dtype=np.int32),
        {"a": 1, "b": [1.5, "x", None, True]},
        [1, 2, {"k": "v"}],
        (4, 5),
        "plain string",
        b"raw bytes",
        12345,
        2.5,
        None,
        True,
        Reading("s0", (1.0, 2.0)),  # dataclass -> pickle tier
        {3, 1, 2},  # set -> canonicalized pickle tier
    ]


class TestBatchEqualsScalar:
    def test_batch_matches_scalar_over_zoo(self):
        zoo = _payload_zoo()
        batch = content_hash_batch(zoo)
        assert batch == [content_hash(p) for p in zoo]
        # av re-export is the same function (historical import site)
        assert content_hash_av is content_hash

    def test_digests_stable_across_calls(self):
        zoo = _payload_zoo()
        assert content_hash_batch(zoo) == content_hash_batch(list(zoo))

    def test_empty_batch(self):
        assert content_hash_batch([]) == []


class TestCrossProcess:
    def test_digests_identical_parent_vs_forked_child(self):
        """The old repr fallback embedded `object at 0x...` addresses, so a
        forked worker could disagree with its parent on the same payload.
        Every tier must now digest identically across the fork boundary."""
        zoo = _payload_zoo() + [np.zeros(LARGE_ARRAY_BYTES // 8 + 7)]
        parent = content_hash_batch(zoo)
        r, w = os.pipe()
        pid = os.fork()
        if pid == 0:  # child
            os.close(r)
            try:
                blob = pickle.dumps(content_hash_batch(zoo))
                os.write(w, blob)
            finally:
                os.close(w)
                os._exit(0)
        os.close(w)
        chunks = []
        while True:
            c = os.read(r, 65536)
            if not c:
                break
            chunks.append(c)
        os.close(r)
        os.waitpid(pid, 0)
        child = pickle.loads(b"".join(chunks))
        assert child == parent


class TestTreeTier:
    def test_large_array_uses_tree_digest(self):
        arr = np.random.RandomState(1).randint(
            0, 255, size=LARGE_ARRAY_BYTES + 13, dtype=np.uint8
        )
        assert content_hash(arr) == tree_digest(arr)

    def test_tree_digest_detects_single_element_change(self):
        arr = np.zeros(LARGE_ARRAY_BYTES * 2, dtype=np.uint8)
        h0 = content_hash(arr)
        arr[LARGE_ARRAY_BYTES] = 1
        assert content_hash(arr) != h0

    def test_numpy_state_matches_kernel_reference(self):
        from repro.kernels.ref import reference_hash_tree

        rng = np.random.RandomState(2)
        for n_words in (TREE_BLOCK_WORDS, 8192, 3 * 8192):
            w = rng.randint(0, 2**32, size=n_words, dtype=np.uint64).astype(
                np.uint32
            )
            got = tree_state_np(w.view(np.uint8))
            want = np.asarray(reference_hash_tree(w))
            assert got == tuple(int(x) for x in want)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_accelerator_backends_agree_with_numpy(self, backend, monkeypatch):
        pytest.importorskip("jax")
        rng = np.random.RandomState(3)
        arrs = [
            rng.randn(1_300_001),  # ragged: kernel bulk + numpy remainder
            rng.randint(0, 255, size=LARGE_ARRAY_BYTES + 13, dtype=np.uint8),
        ]
        want = [tree_digest(a) for a in arrs]
        monkeypatch.setenv("KOALJA_HASH_BACKEND", backend)
        assert [tree_digest(a) for a in arrs] == want


class TestBackendSelection:
    def test_unknown_backend_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("KOALJA_HASH_BACKEND", "palas")  # typo'd
        with pytest.raises(ValueError, match="KOALJA_HASH_BACKEND"):
            content_hash_batch([np.arange(8)])

    def test_kernel_failure_counts_and_reports(self, monkeypatch):
        """A broken accelerator kernel degrades to numpy with a counted,
        reported fallback — never a silent ``except: pass``. The digest is
        bit-identical either way."""
        import sys

        from repro.core.hashing import bind_fallback_anomalies

        big = np.arange(2_000_000, dtype=np.uint32)  # > 4 MiB: tree tier
        want = tree_digest(big)  # numpy reference, no backend in play

        notes = []
        monkeypatch.setitem(sys.modules, "repro.kernels.hash_tree", None)
        monkeypatch.setenv("KOALJA_HASH_BACKEND", "pallas")
        before = hashing_stats()["backend_fallbacks"]
        bind_fallback_anomalies(notes.append)
        try:
            got = tree_digest(big)
        finally:
            bind_fallback_anomalies(None)
        assert got == want
        assert hashing_stats()["backend_fallbacks"] == before + 1
        assert notes and "hash_backend_fallback" in notes[0]
        assert "pallas" in notes[0]

    def test_workspace_routes_fallback_to_anomaly_log(self, monkeypatch):
        """Through the stack: a workspace push that trips the kernel
        fallback lands a ``hashing`` anomaly in the provenance registry."""
        import sys

        from repro.workspace import Workspace

        monkeypatch.setitem(sys.modules, "repro.kernels.hash_tree", None)
        monkeypatch.setenv("KOALJA_HASH_BACKEND", "jnp")
        ws = Workspace("fallback", topology=False, cache=False)
        t = ws.task(lambda x: {"y": x + 1}, name="big",
                    inputs=["x"], outputs=["y"])
        try:
            ws.push(t, x=np.arange(2_000_000, dtype=np.uint32))
        finally:
            from repro.core.hashing import bind_fallback_anomalies

            bind_fallback_anomalies(None)
        anomalies = [
            e for e in ws.visitor_log("hashing") if e["event"] == "anomaly"
        ]
        assert anomalies
        assert "hash_backend_fallback" in (anomalies[0]["note"] or "")


class TestUnstableFallback:
    def test_unpicklable_payload_reports_anomaly(self):
        notes = []
        h = content_hash(lambda x: x, on_unstable=notes.append)
        assert len(h) == 16
        assert notes and "unstable_hash" in notes[0]

    def test_workspace_journals_unstable_hash_anomaly(self, tmp_path):
        from repro.workspace import Workspace

        ws = Workspace(
            "unstable", topology=False, cache=False,
            journal_path=str(tmp_path / "j.jsonl"),
        )
        t = ws.task(
            lambda x: {"y": lambda: x},  # unpicklable output
            name="emit_fn", inputs=["x"], outputs=["y"],
        )
        ws.push(t, x=1)
        assert ws.store.stats()["unstable_hashes"] >= 1
        anomalies = [
            e for e in ws.visitor_log("store") if e["event"] == "anomaly"
        ]
        assert anomalies and "unstable_hash" in (anomalies[0]["note"] or "")

    def test_stats_counters_move(self):
        before = dict(hashing_stats())
        content_hash_batch(_payload_zoo())
        after = hashing_stats()
        assert after["calls"] > before["calls"]
        assert after["payloads"] >= before["payloads"] + len(_payload_zoo())


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(0, 600),
    scale=st.floats(0.1, 1e6, allow_nan=False, allow_infinity=False),
    split=st.integers(1, 7),
)
def test_property_batch_equals_scalar(n, scale, split):
    """Random mixed batches: batch digests equal scalar digests, and any
    partition of the batch yields the same digests (associativity of the
    batch boundary)."""
    rng = np.random.RandomState(n)
    payloads = []
    for i in range(1 + n % 5):
        kind = (n + i) % 4
        if kind == 0:
            payloads.append((rng.randn(max(1, n % 97)) * scale).astype(np.float32))
        elif kind == 1:
            payloads.append({"i": i, "vals": [float(scale), None, "s"]})
        elif kind == 2:
            payloads.append(Reading(f"s{i}", (float(i), scale)))
        else:
            payloads.append(i * int(scale) % (1 << 63))
    whole = content_hash_batch(payloads)
    assert whole == [content_hash(p) for p in payloads]
    cut = split % (len(payloads) + 1)
    assert whole == content_hash_batch(payloads[:cut]) + content_hash_batch(
        payloads[cut:]
    )
