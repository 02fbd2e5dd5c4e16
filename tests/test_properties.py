"""Hypothesis property tests for system invariants."""

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import ContentCache, InputSpec, SnapshotPolicy, snapshot_key
from repro.optim import dequantize_int8, quantize_int8


@settings(max_examples=60, deadline=None)
@given(
    n=st.integers(1, 16),
    k=st.integers(1, 16),
    n_arrivals=st.integers(0, 80),
)
def test_sliding_window_invariants(n, k, n_arrivals):
    """Every window snapshot has exactly N values; consecutive snapshots
    overlap in exactly N-k positions; values appear in arrival order."""
    k = min(k, n)
    p = SnapshotPolicy([InputSpec("x", n, k)], mode="all_new")
    snaps = []
    for v in range(n_arrivals):
        p.arrive("x", v)
        while p.ready():
            snaps.append(p.snapshot()["x"])
    for s in snaps:
        assert len(s) == n
        assert s == sorted(s)  # arrival order preserved
    for a, b in zip(snaps, snaps[1:]):
        assert b[: n - k] == a[k:]  # slide by exactly k


@settings(max_examples=60, deadline=None)
@given(
    bufs=st.lists(st.integers(1, 5), min_size=1, max_size=4),
    arrivals=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 99)), max_size=60),
)
def test_all_new_never_reuses(bufs, arrivals):
    """all_new: every arrived value is consumed at most once."""
    names = [f"i{j}" for j in range(len(bufs))]
    p = SnapshotPolicy(
        [InputSpec(nm, b) for nm, b in zip(names, bufs)], mode="all_new"
    )
    consumed = []
    for idx, val in arrivals:
        p.arrive(names[idx % len(names)], (idx % len(names), val))
        while p.ready():
            snap = p.snapshot()
            for nm, v in snap.items():
                consumed.extend(v if isinstance(v, list) else [v])
    assert len(consumed) == len(set(id(c) for c in consumed)) or len(consumed) == len(
        consumed
    )  # structural: no duplicates beyond equal payloads
    # stronger check: count per input never exceeds arrivals per input
    from collections import Counter

    arrived = Counter(idx % len(names) for idx, _ in arrivals)
    used = Counter(c[0] for c in consumed)
    for j, cnt in used.items():
        assert cnt <= arrived[j]


@settings(max_examples=50, deadline=None)
@given(
    ver=st.text(alphabet="abcdef0123456789", min_size=1, max_size=8),
    hashes=st.dictionaries(
        st.text(alphabet="xyz", min_size=1, max_size=3),
        st.text(alphabet="0123456789abcdef", min_size=4, max_size=8),
        max_size=4,
    ),
)
def test_snapshot_key_deterministic_and_sensitive(ver, hashes):
    k1 = snapshot_key(ver, hashes)
    k2 = snapshot_key(ver, dict(reversed(list(hashes.items()))))
    assert k1 == k2  # order-insensitive
    assert snapshot_key(ver + "x", hashes) != k1  # version-sensitive
    if hashes:
        name = next(iter(hashes))
        mutated = dict(hashes)
        mutated[name] = mutated[name] + "0"
        assert snapshot_key(ver, mutated) != k1  # content-sensitive


@settings(max_examples=60, deadline=None)
@given(
    arr=st.lists(
        st.floats(-1e6, 1e6, allow_nan=False, allow_infinity=False, width=32),
        min_size=1,
        max_size=256,
    )
)
def test_int8_quantization_error_bound(arr):
    """|x - deq(q(x))| <= scale/2 elementwise (symmetric rounding)."""
    x = np.asarray(arr, np.float32)
    q, scale = quantize_int8(x)
    err = np.abs(x - np.asarray(dequantize_int8(q, scale)))
    assert float(err.max()) <= float(scale) / 2 + 1e-6


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_cache_hit_iff_same_key(data):
    cache = ContentCache()
    keys = data.draw(
        st.lists(st.text(alphabet="ab", min_size=1, max_size=4), min_size=1, max_size=10)
    )
    for i, k in enumerate(keys):
        cache.insert(k, {"i": i})
    for k in keys:
        assert cache.lookup(k) is not None
    assert cache.lookup("definitely-not-present") is None
