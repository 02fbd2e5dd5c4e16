"""Batched content hashing — the vectorized half of the data plane.

Every cache key, travel document, and store ingest in Koalja starts from a
content hash. Until PR 8 that was a per-payload Python loop (`content_hash`
in ``repro.core.av``) with a collision-prone 4096-element *sampled* stripe
for large arrays. This module replaces it with a batch-first API:

- :func:`content_hash_batch` hashes a whole wave's payloads in one fused
  call: small arrays are copied into **one** shared buffer and hashed as
  slices of a single memoryview (one allocation, one sequential pass);
  large (> 4 MiB) arrays get a **full-coverage** blockwise tree digest that
  runs at memory bandwidth (~10x sha256 on this host) with bit-identical
  numpy / jnp / pallas implementations (see ``repro.kernels.hash_tree``).
- :func:`content_hash` is now a thin single-payload wrapper.

Digest compatibility contract (existing journals / memo records stay
valid):

=====================  ==========================================
tier                   digest
=====================  ==========================================
ghost (aval only)      ``sha256("ghost:{shape}:{dtype}")``        (unchanged)
array  <= 4 MiB        ``sha256(bytes + shape + dtype)``          (unchanged)
array  >  4 MiB        blockwise tree digest, full coverage       (NEW — was sampled)
pure-JSON container    ``sha256(json.dumps(sort_keys=True))``     (unchanged)
scalar (str/int/...)   ``sha256(repr(payload))``                  (unchanged)
arbitrary object       ``sha256("pickle:" + pickle.dumps)``       (NEW — was repr)
=====================  ==========================================

The last row is the cross-process fix: ``repr`` of an arbitrary object
embeds its memory address (``<... at 0x7f...>``), so identical payloads
hashed differently in every ``ProcessExecutor`` worker, silently defeating
memo dedup and ``bytes_not_moved`` parity. Pickle output is
address-free and fork-stable. When even pickle fails the repr fallback
remains, but the event is surfaced through the ``on_unstable`` callback so
the store can journal an ``unstable_hash`` anomaly instead of silently
producing a process-local digest.

Tree digest definition (the > 4 MiB tier)
-----------------------------------------
The payload bytes are viewed as little-endian uint32 words (a 0..3-byte
tail is packed LE into one extra word). Words are grouped into blocks of
``TREE_BLOCK_WORDS`` = 128; per block ``j``::

    s_j = sum(words in block j)            (uint32, wraparound)
    c_j = (j * 0x9E3779B1 + 0x85EBCA77) | 1
    m_j = (s_j ^ c_j) * c_j                (uint32, wraparound)

and the state is ``(h1, h2, h3) = (sum m_j, xor m_j, sum s_j)``; the final
digest is ``sha256(state || nbytes || shape || dtype || "tree")[:16]``.
All arithmetic wraps mod 2**32, which numpy, XLA, and Pallas implement
identically — the three backends are bit-exact (``KOALJA_HASH_BACKEND``
selects ``numpy`` (default) / ``jnp`` / ``pallas``; the jax paths exist
for accelerator offload and are validated against numpy in the tests).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import sys
from typing import Any, Callable, Iterable, List, Optional, Sequence

from .spans import enabled, span

__all__ = [
    "content_hash",
    "content_hash_batch",
    "tree_state_np",
    "tree_digest",
    "hashing_stats",
    "bind_fallback_anomalies",
    "is_ghost",
    "LARGE_ARRAY_BYTES",
    "TREE_BLOCK_WORDS",
]

# Arrays at or below this many bytes keep the seed-era sha256(bytes) digest
# so existing journals and memo records stay valid; above it the sampled
# stripe is replaced by the full-coverage tree digest.
LARGE_ARRAY_BYTES = 1 << 22  # 4 MiB

TREE_BLOCK_WORDS = 128  # words per level-0 block (512 bytes)
_TREE_GOLD = 0x9E3779B1  # golden-ratio odd constant (Fibonacci hashing)
_TREE_SALT = 0x85EBCA77  # murmur3 fmix constant

# Scalar types whose repr is canonical and address-free: these keep the
# seed-era repr digest. Everything else non-JSON goes through pickle.
_STABLE_REPR_TYPES = (str, bytes, bytearray, int, float, complex, bool, type(None))

_STATS = {
    "calls": 0,  # content_hash_batch invocations
    "payloads": 0,  # payloads hashed
    "fused_bytes": 0,  # bytes that went through the shared small-array buffer
    "tree_hashes": 0,  # large arrays hashed via the tree digest
    "pickle_hashes": 0,  # payloads hashed via the pickle tier
    "unstable_hashes": 0,  # repr fallbacks (pickle failed) — process-local!
    "backend_fallbacks": 0,  # jnp/pallas kernel failures rescued by numpy
    "d2h_bytes": 0,  # bytes copied from a device to the host to be hashed
}

_HASH_BACKENDS = ("numpy", "jnp", "pallas")

# Optional anomaly sink for kernel fallbacks (bound by PipelineManager to
# registry.record_anomaly): a silently degraded backend is an operational
# event worth a forensic record, not just a counter.
_FALLBACK_SINK: Optional[Callable[[str], None]] = None


def bind_fallback_anomalies(sink: Optional[Callable[[str], None]]) -> None:
    """Route hash-backend fallback notices into an anomaly sink (typically
    ``lambda note: registry.record_anomaly("hashing", note)``). Pass None to
    unbind. The digests themselves are unaffected — the numpy path is
    bit-identical — so this is observability, not determinism."""
    global _FALLBACK_SINK
    _FALLBACK_SINK = sink


def _hash_backend() -> str:
    """The validated ``KOALJA_HASH_BACKEND`` selection. Unknown values fail
    loudly (like KOALJA_EXECUTOR / KOALJA_PLACEMENT) instead of silently
    hashing on numpy while the operator believes a kernel is running."""
    backend = os.environ.get("KOALJA_HASH_BACKEND", "numpy")
    if backend not in _HASH_BACKENDS:
        raise ValueError(
            f"KOALJA_HASH_BACKEND={backend!r} is not a hash backend "
            f"(choose from: {', '.join(_HASH_BACKENDS)})"
        )
    return backend


def hashing_stats() -> dict:
    """Counters for the hashing hot path (observability, not determinism)."""
    return dict(_STATS)


def _stable_hash_bytes(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def is_ghost(payload: Any) -> bool:
    """True for abstract payloads (shape+dtype but no materialized bytes):
    ``jax.ShapeDtypeStruct``, :class:`~repro.core.wireframe.GhostValue`, and
    anything else that *declares* ``nbytes = None``. Ghosts are pure
    metadata — the circuit routes them without ever touching the store.

    The check is deliberately narrow: a payload must opt in, either by being
    a ShapeDtypeStruct or by carrying an explicit ``nbytes`` of None. Real
    array-likes that merely lack an ``nbytes`` attribute (e.g. sparse
    matrices) are data, not ghosts, and go through the store."""
    if type(payload).__name__ == "ShapeDtypeStruct":
        return True
    return (
        hasattr(payload, "shape")
        and hasattr(payload, "dtype")
        and hasattr(payload, "nbytes")
        and payload.nbytes is None
    )


# ---------------------------------------------------------------------------
# tree digest (> 4 MiB arrays)
# ---------------------------------------------------------------------------


def _mix_blocks_np(s, j0: int):
    """Mix + combine uint32 blocksums ``s`` whose global block indices start
    at ``j0``. Returns the partial state ``(h1, h2, h3)`` as Python ints."""
    import numpy as np

    j = (np.arange(s.size, dtype=np.uint64) + np.uint64(j0)).astype(np.uint32)
    c = (j * np.uint32(_TREE_GOLD) + np.uint32(_TREE_SALT)) | np.uint32(1)
    m = (s ^ c) * c
    h1 = int(m.sum(dtype=np.uint32))
    h2 = int(np.bitwise_xor.reduce(m)) if m.size else 0
    h3 = int(s.sum(dtype=np.uint32))
    return h1, h2, h3


def _state_from_words(w, tail_bytes: bytes, j0: int):
    """Tree state over uint32 word array ``w`` plus an optional 0..3-byte
    tail, with block numbering starting at global index ``j0``."""
    import numpy as np

    B = TREE_BLOCK_WORDS
    nb = w.size // B
    # reduceat outruns reshape().sum(axis=1) by ~1.5x at memory-bandwidth
    # sizes; u32 addition wraps identically in any order, so the digests
    # are unchanged
    if nb:
        s = np.add.reduceat(w[: nb * B], np.arange(0, nb * B, B), dtype=np.uint32)
    else:
        s = np.empty(0, dtype=np.uint32)
    rem = w[nb * B :]
    if rem.size or tail_bytes:
        s_tail = np.uint32(rem.sum(dtype=np.uint32))
        if tail_bytes:
            s_tail = np.uint32(
                (int(s_tail) + int.from_bytes(tail_bytes, "little")) & 0xFFFFFFFF
            )
        s = np.concatenate([s, np.asarray([s_tail], dtype=np.uint32)])
    return _mix_blocks_np(s, j0)


def _combine_states(a, b):
    return (
        (a[0] + b[0]) & 0xFFFFFFFF,
        a[1] ^ b[1],
        (a[2] + b[2]) & 0xFFFFFFFF,
    )


def tree_state_np(u8) -> tuple:
    """Reference tree state over a 1-D uint8 array (pure numpy, zero-copy:
    the bulk is viewed as uint32 in place, only the <4-byte tail is packed
    separately). This is the canonical definition the jnp / pallas kernels
    must match bit-for-bit."""
    import numpy as np

    u8 = np.ascontiguousarray(u8, dtype=np.uint8).reshape(-1)
    n4 = (u8.size // 4) * 4
    w = u8[:n4].view(np.uint32)
    return _state_from_words(w, u8[n4:].tobytes(), 0)


def _tree_state(u8):
    """Dispatch the tree state to the selected backend. The jax backends
    (``KOALJA_HASH_BACKEND=jnp|pallas``) cover the chunk-aligned bulk with
    the kernel and finish the ragged remainder with numpy — bit-identical
    to the pure-numpy path by construction."""
    backend = _hash_backend()
    if backend == "numpy":
        return tree_state_np(u8)
    try:
        from repro.kernels.hash_tree import CHUNK_BLOCKS, hash_tree_state
        from repro.kernels.ref import reference_hash_tree
    except ImportError as exc:
        # no jax / kernel module: the numpy path computes the same bits, but
        # count the degradation and leave a forensic trail. A kernel that
        # imports but fails to compile or run raises: that is a fault, not
        # a missing backend.
        _STATS["backend_fallbacks"] += 1
        if _FALLBACK_SINK is not None:
            try:
                _FALLBACK_SINK(
                    f"hash_backend_fallback: backend={backend!r} failed "
                    f"({type(exc).__name__}: {exc}); digest computed on "
                    f"numpy (bit-identical)"
                )
            except Exception:
                pass
        return tree_state_np(u8)
    import numpy as np

    u8 = np.ascontiguousarray(u8, dtype=np.uint8).reshape(-1)
    n4 = (u8.size // 4) * 4
    w = u8[:n4].view(np.uint32)
    cw = TREE_BLOCK_WORDS * CHUNK_BLOCKS
    nk = (w.size // cw) * cw
    head = (0, 0, 0)
    if nk:
        kernel = hash_tree_state if backend == "pallas" else reference_hash_tree
        head = tuple(int(x) for x in np.asarray(kernel(w[:nk])))
    rest = _state_from_words(w[nk:], u8[n4:].tobytes(), nk // TREE_BLOCK_WORDS)
    return _combine_states(head, rest)


def tree_digest(arr) -> str:
    """Full-coverage digest of a large array: tree state + (nbytes, shape,
    dtype) finalized through sha256. Replaces the seed-era sampled stripe."""
    import numpy as np

    a = np.asarray(arr)
    if not a.flags["C_CONTIGUOUS"]:
        a = np.ascontiguousarray(a)
    u8 = a.reshape(-1).view(np.uint8) if a.size else np.empty(0, np.uint8)
    h1, h2, h3 = _tree_state(u8)
    trailer = f":{u8.size}:{a.shape}:{a.dtype}:tree".encode()
    return _stable_hash_bytes(struct.pack("<3I", h1, h2, h3) + trailer)


# ---------------------------------------------------------------------------
# tiered per-payload hashing
# ---------------------------------------------------------------------------


def _json_canonical(payload) -> Optional[bytes]:
    """Strict canonical JSON bytes for pure-JSON containers (no ``default``
    hook — anything non-JSON falls through to the pickle tier rather than
    being repr-embedded with a memory address)."""
    try:
        return json.dumps(payload, sort_keys=True).encode()
    except (TypeError, ValueError):
        return None


def _pickle_digest(payload, on_unstable: Optional[Callable[[str], None]]) -> str:
    try:
        if isinstance(payload, (set, frozenset)):
            # Set iteration order is hash-salted per process; canonicalize
            # by sorting when the elements allow it.
            try:
                blob = pickle.dumps(("sorted-set", sorted(payload)), protocol=4)
            except TypeError:
                blob = pickle.dumps(payload, protocol=4)
        else:
            blob = pickle.dumps(payload, protocol=4)
        _STATS["pickle_hashes"] += 1
        return _stable_hash_bytes(b"pickle:" + blob)
    except Exception:
        _STATS["unstable_hashes"] += 1
        if on_unstable is not None:
            try:
                on_unstable(
                    f"unstable_hash: payload of type "
                    f"{type(payload).__name__} is not picklable; repr digest "
                    f"is process-local"
                )
            except Exception:
                pass
        return _stable_hash_bytes(repr(payload).encode())


class _SmallArray:
    """Deferred small-array hash: bytes land in the batch's shared buffer
    and are hashed as one memoryview slice per payload (one allocation and
    one sequential pass for the whole wave)."""

    __slots__ = ("arr", "u8", "index")

    def __init__(self, arr, u8, index):
        self.arr = arr
        self.u8 = u8
        self.index = index


def _device_nbytes(payload) -> int:
    """Bytes ``np.asarray`` copies from a device to the host for
    ``payload``: a ``jax.Array``'s size, 0 for an array already on the host."""
    jax = sys.modules.get("jax")
    if jax is not None and isinstance(payload, jax.Array):
        return int(payload.nbytes)
    return 0


def _classify(payload: Any, out: list, small: list, on_unstable, moved: list) -> None:
    """Hash one payload, or defer it into ``small`` for the fused pass.
    Appends the digest (or a placeholder) to ``out``; adds an array's bytes,
    and those copied from a device to hash it, to ``moved``."""
    try:  # numpy-like arrays
        import numpy as np

        if hasattr(payload, "shape") and hasattr(payload, "dtype"):
            if not hasattr(payload, "nbytes") or payload.nbytes is None:
                # ShapeDtypeStruct / abstract value: hash the aval.
                out.append(
                    _stable_hash_bytes(
                        f"ghost:{payload.shape}:{payload.dtype}".encode()
                    )
                )
                return
            d2h = _device_nbytes(payload)
            arr = np.asarray(payload)
            moved[0] += payload.nbytes
            moved[1] += d2h
            if arr.dtype.hasobject:
                # Object arrays serialize as pointers under tobytes();
                # that digest was always address-garbage — pickle instead.
                out.append(_pickle_digest(payload, on_unstable))
                return
            if payload.nbytes <= LARGE_ARRAY_BYTES:  # <= 4 MiB: real bytes
                if not arr.flags["C_CONTIGUOUS"]:
                    arr = np.ascontiguousarray(arr)
                u8 = (
                    arr.reshape(-1).view(np.uint8)
                    if arr.size
                    else np.empty(0, np.uint8)
                )
                out.append(None)
                small.append(_SmallArray(arr, u8, len(out) - 1))
                return
            # Large arrays: full-coverage tree digest at memory bandwidth
            # (was: a 4096-element sampled stripe, collision-prone).
            _STATS["tree_hashes"] += 1
            out.append(tree_digest(arr))
            return
    except Exception:
        pass
    if isinstance(payload, (dict, list, tuple)):
        blob = _json_canonical(payload)
        if blob is not None:
            out.append(_stable_hash_bytes(blob))
            return
        out.append(_pickle_digest(payload, on_unstable))
        return
    if isinstance(payload, _STABLE_REPR_TYPES):
        out.append(_stable_hash_bytes(repr(payload).encode()))
        return
    out.append(_pickle_digest(payload, on_unstable))


def _fuse_small(small: List[_SmallArray], out: list) -> None:
    """One shared buffer pass for all small arrays in the batch. Digests are
    byte-identical to the seed-era ``sha256(tobytes + shape + dtype)``: the
    shared buffer just replaces N ``tobytes()`` allocations with one."""
    import numpy as np

    total = sum(s.u8.size for s in small)
    buf = np.empty(total, dtype=np.uint8)
    off = 0
    for s in small:
        n = s.u8.size
        buf[off : off + n] = s.u8
        off += n
    mv = memoryview(buf)
    _STATS["fused_bytes"] += total
    off = 0
    for s in small:
        n = s.u8.size
        h = hashlib.sha256(mv[off : off + n])
        h.update(str(s.arr.shape).encode())
        h.update(str(s.arr.dtype).encode())
        out[s.index] = h.hexdigest()[:16]
        off += n


def content_hash_batch(
    payloads: Sequence[Any],
    *,
    on_unstable: Optional[Callable[[str], None]] = None,
) -> List[str]:
    """Content hashes for a whole wave of payloads in one fused call.

    Semantics are identical to mapping :func:`content_hash` over the
    payloads (the property tests assert this); the batch form exists so
    the per-payload Python dispatch and buffer allocations are paid once
    per wave instead of once per AV. ``on_unstable`` is invoked with a
    note for every payload that fell back to a process-local repr digest
    (see :meth:`repro.core.store.ArtifactStore.bind_provenance`).
    """
    payloads = list(payloads)
    _hash_backend()  # fail loudly on a typo'd KOALJA_HASH_BACKEND up front
    _STATS["calls"] += 1
    _STATS["payloads"] += len(payloads)
    out: list = []
    small: List[_SmallArray] = []
    moved = [0, 0]  # array bytes hashed, bytes copied device -> host
    with span("hash", payloads=len(payloads)) as sp:
        for payload in payloads:
            _classify(payload, out, small, on_unstable, moved)
        if small:
            _fuse_small(small, out)
        _STATS["d2h_bytes"] += moved[1]
        if enabled():
            sp.set_metadata(nbytes=moved[0], d2h_bytes=moved[1])
    return out


def content_hash(payload: Any, *, on_unstable=None) -> str:
    """Content hash of a payload for cache keys and travel documents.

    Thin single-payload wrapper over :func:`content_hash_batch` — see the
    module docstring for the tier table and compatibility contract.
    """
    return content_hash_batch((payload,), on_unstable=on_unstable)[0]
