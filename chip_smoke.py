#!/usr/bin/env python3
"""Bring-up run of the Koalja circuit on a TPU, through its normal entry points.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # the four-chip sharded train phase, alone

One chip, in this one process:

  device        JAX must report a TPU. On any other platform the script names
                the platform it found and exits 2; it never carries on.
  serve         a Workspace task holding stablelm-1.6b at full width (random
                bf16 weights from --seed) answers 4 pushed requests through
                ``make_serve_fns`` prefill and decode. Its output AVs are
                hashed and journaled; a repeated request is a memo hit; the
                first decode step's logits agree with a teacher-forced pass.
  serve-driver  ``repro.launch.serve.main`` at full width.
  kernel:*      each Pallas kernel, compiled for the chip, at the widths of a
                config of this repo, against its oracle in ``kernels.ref``;
                ``hash_tree`` through ``core.hashing``, bit-equal to numpy.
  fork          a forked executor refuses to fork from this process, which
                holds the chip.

Four chips (``--chips 4``):

  train         a 2-layer cut of stablelm-1.6b at full width takes one train
                step on one chip and on a four-chip FSDP mesh: loss and grad
                norm agree. Then ``repro.launch.train.main`` runs the full
                24-layer model for a few steps: the loss is finite, never
                rises past the batch-to-batch spread and ends below where it
                began, and the state is spread over all four devices.

Each phase prints one line: its name, shapes, max error and seconds. A phase
that fails raises, and the run exits non-zero. The last line of a run that
passed is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Data and weights are made from ``--seed``; nothing is read from disk.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import re
import sys
import tempfile
import time
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
LOGITS_TOL = 5e-2  # bf16 model logits, max error over the reference's peak
KERNEL_TOL = {"bfloat16": 2e-2, "float32": 1e-4}
STEP_RTOL = 2e-2  # one chip vs four: loss and grad norm, relative
# The train driver draws a new batch each step, so at a fixed model the loss
# still moves by the batch-to-batch spread: 1.2% between the first two steps
# of a 24-layer run on four TPU v5e chips, where the first step's learning
# rate is 0.
LOSS_SPREAD = 2e-2


def _line(name: str, **fields) -> None:
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _max_err(out, ref) -> float:
    """Max absolute error, relative to the reference's peak where that
    exceeds 1."""
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(1.0, float(np.max(np.abs(ref)))))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# serving circuit
# ---------------------------------------------------------------------------


def serving_phase(*, reduced=False, n_requests=4, prompt_len=512, gen=32, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.dist.step import make_serve_fns
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model, init_serve_state
    from repro.workspace import InlineExecutor, Workspace

    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else cfg
    model = build_model(cfg)
    max_len = prompt_len + gen
    prefill, decode, _, shards = make_serve_fns(
        model, make_host_mesh(), max_len=max_len, global_batch=1
    )
    params = jax.jit(lambda k: model.init(k)[0], out_shardings=shards["params"])(
        jax.random.key(seed)
    )
    new_state = jax.jit(
        lambda: init_serve_state(model, 1, max_len), out_shardings=shards["state"]
    )

    def generate(prompt):
        logits, state = prefill(params, jnp.asarray(prompt)[None], new_state())
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        toks, first = [tok], None
        for _ in range(gen - 1):
            logits, state = decode(params, tok, state)
            first = logits[0] if first is None else first
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            toks.append(tok)
        return {"tokens": jnp.concatenate(toks, axis=1)[0], "first_logits": first}

    @jax.jit
    def teacher_forced(params, tokens):
        B, L = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
        x, _, _ = model.trunk(params, model.embed(params, tokens), pos)
        return model.logits(params, x[:, -1:])[:, 0]

    prompts = np.random.RandomState(seed + 1).randint(
        0, cfg.vocab, size=(n_requests, prompt_len)
    ).astype(np.int32)
    with tempfile.TemporaryDirectory() as tmp:
        ws = Workspace(
            "chip-serve",
            executor=InlineExecutor(),
            topology=False,
            journal_path=os.path.join(tmp, "serve.jsonl"),
        )
        task = ws.task(
            generate, name="generate", inputs=["prompt"],
            outputs=["tokens", "first_logits"],
        )
        answers = [ws.push(task, prompt=p)["generate"] for p in prompts]
        executed = ws.stats()["sustainability"]["executions"]
        again = ws.push(task, prompt=prompts[0])["generate"]
        stats = ws.stats()
        tokens = np.stack([np.asarray(a["tokens"]) for a in answers])
        _check(executed == n_requests, f"{executed} executions for {n_requests} requests")
        _check(tokens.shape == (n_requests, gen), f"tokens {tokens.shape}")
        _check(bool(((tokens >= 0) & (tokens < cfg.vocab)).all()), "token ids out of range")
        _check(
            stats["sustainability"]["executions"] == n_requests
            and stats["sustainability"]["cache_hits"] == 1,
            f"repeated request was not a memo hit: {stats['sustainability']}",
        )
        _check(
            again.av("tokens").chash == answers[0].av("tokens").chash,
            "memo hit returned other tokens",
        )
        _check(all(a.av("tokens").chash for a in answers), "output AV without a hash")
        journaled = stats["journal"]["records_written"]
        _check(journaled > 0, "nothing journaled")

        forced = np.concatenate([prompts[0], tokens[0, :1]])[None]
        ref = teacher_forced(params, jnp.asarray(forced))[0]
        err = _max_err(answers[0]["first_logits"], ref)
    _line(
        "serve",
        arch=cfg.name,
        params=f"{cfg.n_params() / 1e9:.2f}B:{cfg.dtype}",
        requests=f"{n_requests}x{prompt_len}+{gen}",
        memo_hits=stats["sustainability"]["cache_hits"],
        journal_records=journaled,
        max_err=f"{err:.3e}",
        seconds=f"{time.perf_counter() - t0:.1f}",
    )
    _check(err < LOGITS_TOL, f"decode logits vs teacher forcing: {err:.3e} >= {LOGITS_TOL}")


def serve_driver_phase(*, reduced=False, batch=4, prompt_len=512, gen=8, seed=0):
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve

    t0 = time.perf_counter()
    argv = [
        "--arch", ARCH, "--batch", str(batch), "--prompt-len", str(prompt_len),
        "--gen", str(gen), "--seed", str(seed),
    ] + (["--reduced"] if reduced else [])
    tokens = np.asarray(serve.main(argv))
    cfg = get_config(ARCH)
    vocab = (cfg.reduced() if reduced else cfg).vocab
    _check(tokens.shape == (batch, gen), f"serve driver returned {tokens.shape}")
    _check(bool(((tokens >= 0) & (tokens < vocab)).all()), "token ids out of range")
    _line(
        "serve-driver",
        argv=" ".join(argv),
        tokens=f"{batch}x{gen}",
        max_err="n/a",
        seconds=f"{time.perf_counter() - t0:.1f}",
    )


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _check_kernel(name, fn, ref_fn, args, *, shapes, tol, exact=False) -> bool:
    """Run one kernel and its oracle; return whether it ran compiled."""
    import jax

    t0 = time.perf_counter()
    jitted = jax.jit(fn)
    compiled = "tpu_custom_call" in jitted.lower(*args).as_text()
    out = jax.block_until_ready(jitted(*args))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_fn)(*args)
    pairs = list(zip(jax.tree.leaves(out), jax.tree.leaves(ref)))
    if exact:
        import numpy as np

        err = float(sum(int(np.any(np.asarray(o) != np.asarray(r))) for o, r in pairs))
    else:
        err = max(_max_err(o, r) for o, r in pairs)
    _line(
        f"kernel:{name}",
        shapes=shapes,
        mode="compiled" if compiled else "interpreted",
        max_err=f"{err:.3e}",
        seconds=f"{time.perf_counter() - t0:.1f}",
    )
    _check(err <= tol, f"{name}: max error {err:.3e} > {tol}")
    return compiled


def kernel_phase(*, reduced=False, seed=0) -> dict:
    """Each kernel at the widths of a config of this repo; returns
    {kernel: ran compiled}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import hashing
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_decode import flash_decode
    from repro.kernels.hash_tree import CHUNK_BLOCKS, hash_tree_state
    from repro.kernels.mamba_scan import mamba_scan
    from repro.kernels.moe_gmm import moe_gmm

    def cfg_of(arch):
        cfg = get_config(arch)
        return cfg.reduced() if reduced else cfg

    keys = iter(jax.random.split(jax.random.key(seed), 32))
    normal = lambda shape, dt, s=1.0: (jax.random.normal(next(keys), shape) * s).astype(dt)
    modes = {}

    # flash attention + flash decode at stablelm-1.6b's heads
    cfg = cfg_of(ARCH)
    dt, tol = cfg.compute_dtype(), KERNEL_TOL[cfg.dtype]
    H, Dh, L = cfg.n_heads, cfg.head_dim, (64 if reduced else 2048)
    q, k, v = (normal((1, L, H, Dh), dt) for _ in range(3))
    modes["flash_attention"] = _check_kernel(
        "flash_attention", flash_attention, ref.reference_attention, (q, k, v),
        shapes=f"{cfg.name}:q{q.shape}:{cfg.dtype}", tol=tol,
    )
    B, S, KVH = 4, L, cfg.n_kv_heads
    q1 = normal((B, 1, H, Dh), dt)
    kc, vc = normal((B, S, KVH, Dh), dt), normal((B, S, KVH, Dh), dt)
    n_valid = jnp.asarray([S, (3 * S) // 4, S // 3, 1], jnp.int32)
    k_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    modes["flash_decode"] = _check_kernel(
        "flash_decode", flash_decode, ref.reference_decode,
        (q1, kc, vc, k_pos, n_valid - 1, n_valid),
        shapes=f"{cfg.name}:cache{kc.shape}:{cfg.dtype}", tol=tol,
    )

    # grouped expert FFN at mixtral-8x7b's widths
    cfg = cfg_of("mixtral-8x7b")
    dt, tol = cfg.compute_dtype(), KERNEL_TOL[cfg.dtype]
    E, C, D, F = cfg.n_experts, (16 if reduced else 256), cfg.d_model, cfg.d_ff
    gmm_args = (
        normal((E, C, D), dt),
        normal((E, D, F), dt, D**-0.5),
        normal((E, D, F), dt, D**-0.5),
        normal((E, F, D), dt, F**-0.5),
    )
    modes["moe_gmm"] = _check_kernel(
        "moe_gmm", moe_gmm, ref.reference_gmm, gmm_args,
        shapes=f"{cfg.name}:x{gmm_args[0].shape}:w{gmm_args[1].shape}:{cfg.dtype}", tol=tol,
    )

    # selective scan at falcon-mamba-7b's widths (the scan itself is f32)
    cfg = cfg_of("falcon-mamba-7b")
    Bs, Ls, Di, N = 1, (64 if reduced else 512), cfg.d_inner, cfg.ssm_state
    scan_args = (
        normal((Bs, Ls, Di), cfg.compute_dtype()),
        jax.nn.softplus(normal((Bs, Ls, Di), jnp.float32) - 3.0),
        normal((Bs, Ls, N), jnp.float32),
        normal((Bs, Ls, N), jnp.float32),
        -jnp.exp(normal((Di, N), jnp.float32, 0.5)),
    )
    modes["mamba_scan"] = _check_kernel(
        "mamba_scan", mamba_scan, ref.reference_selective_scan, scan_args,
        shapes=f"{cfg.name}:x{scan_args[0].shape}:N{N}", tol=KERNEL_TOL["float32"],
    )

    # tree hash: the kernel against its oracle, then through core.hashing
    chunk = hashing.TREE_BLOCK_WORDS * CHUNK_BLOCKS
    n_words = chunk * (3 if reduced else 1 << 11)  # 64 MiB at full size
    words = jax.random.bits(next(keys), (n_words,), jnp.uint32)
    modes["hash_tree"] = _check_kernel(
        "hash_tree", hash_tree_state, ref.reference_hash_tree, (words,),
        shapes=f"words({n_words},):uint32", tol=0, exact=True,
    )
    t0 = time.perf_counter()
    payload = np.concatenate(
        [np.asarray(words).view(np.uint8), np.arange(1237, dtype=np.uint8)]
    )  # a ragged tail the kernel leaves to numpy
    with mock.patch.dict(os.environ, {"KOALJA_HASH_BACKEND": "numpy"}):
        want = hashing.tree_digest(payload)
    fallbacks = hashing.hashing_stats()["backend_fallbacks"]
    with mock.patch.dict(os.environ, {"KOALJA_HASH_BACKEND": "pallas"}):
        got = hashing.tree_digest(payload)
    _check(hashing.hashing_stats()["backend_fallbacks"] == fallbacks, "hash kernel fell back")
    _line(
        "kernel:hash_tree:core.hashing",
        shapes=f"bytes({payload.size},)",
        digest=got,
        max_err=f"{float(got != want):.3e}",
        seconds=f"{time.perf_counter() - t0:.1f}",
    )
    _check(got == want, f"pallas digest {got} != numpy digest {want}")
    return modes


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------


def fork_phase():
    """A forked executor must refuse to fork from a process holding the chip."""
    import numpy as np

    from repro.runtime import ProcessExecutor
    from repro.workspace import Workspace

    t0 = time.perf_counter()
    ws = Workspace(
        "chip-fork", executor=ProcessExecutor(max_workers=2),
        topology=False, journal_path=False,
    )
    src = ws.task(lambda x: {"y": x}, name="src", inputs=["x"], outputs=["y"])
    a = ws.task(lambda x: {"y": x + 1}, name="a", inputs=["x"], outputs=["y"])
    b = ws.task(lambda x: {"y": x + 2}, name="b", inputs=["x"], outputs=["y"])
    src["y"] >> a["x"]
    src["y"] >> b["x"]
    try:
        ws.push(src, x=np.arange(4))
    except RuntimeError as exc:
        _check("cannot fork" in str(exc), f"unexpected error: {exc}")
        _line("fork", wave="2 tasks", refused="yes", max_err="n/a",
              seconds=f"{time.perf_counter() - t0:.1f}")
        return
    raise AssertionError("a forked executor ran a wave while this process holds the chip")


# ---------------------------------------------------------------------------
# four chips: sharded training
# ---------------------------------------------------------------------------


def _one_train_step(cfg, devices, *, batch, seq, seed) -> dict:
    import functools

    import jax

    from repro.data.pipeline import synthetic_batch
    from repro.dist.step import init_train_state, make_train_step
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model
    from repro.optim import cosine_warmup

    model = build_model(cfg)
    step, _, state_shard, batch_shard = make_train_step(
        model, make_host_mesh(devices=devices), cosine_warmup(3e-4, 2, 10),
        global_batch=batch,
    )
    state = jax.jit(
        functools.partial(init_train_state, model), out_shardings=state_shard
    )(jax.random.key(seed))
    data = jax.device_put(synthetic_batch(cfg, batch, seq, step=0, seed=seed), batch_shard)
    _, metrics = step(state, data)
    return {k: float(metrics[k]) for k in ("loss", "grad_norm")}


def train_phase(*, reduced=False, batch=4, seq=512, steps=8, lr=1e-4, seed=0, n_chips=4):
    import jax

    from repro.configs import get_config
    from repro.launch import train

    devices = jax.devices()[:n_chips]
    _check(len(devices) == n_chips, f"{len(devices)} devices, want {n_chips}")
    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else cfg
    cut = dataclasses.replace(cfg, n_layers=2 * len(cfg.layout))

    t0 = time.perf_counter()
    one = _one_train_step(cut, devices[:1], batch=batch, seq=seq, seed=seed)
    many = _one_train_step(cut, devices, batch=batch, seq=seq, seed=seed)
    err = max(abs(many[k] - one[k]) / max(abs(one[k]), 1e-9) for k in one)
    _line(
        "train:cut-1v4",
        arch=f"{cfg.name}:{cut.n_layers}layers",
        batch=f"{batch}x{seq}",
        one=f"loss={one['loss']:.6f},gnorm={one['grad_norm']:.6f}",
        four=f"loss={many['loss']:.6f},gnorm={many['grad_norm']:.6f}",
        max_err=f"{err:.3e}",
        seconds=f"{time.perf_counter() - t0:.1f}",
    )
    _check(err < STEP_RTOL, f"1 vs {n_chips} chips differ by {err:.3e}")

    t0 = time.perf_counter()
    argv = [
        "--arch", ARCH, "--steps", str(steps), "--batch", str(batch),
        "--seq", str(seq), "--lr", str(lr), "--ckpt-every", "0", "--seed", str(seed),
    ] + (["--reduced"] if reduced else [])
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        state = train.main(argv)
    print(out.getvalue(), end="", flush=True)
    losses = [float(x) for x in re.findall(r"^step\s+\d+ loss (\S+)", out.getvalue(), re.M)]
    _check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    _check(
        losses[-1] < losses[0] and max(losses) <= losses[0] * (1 + LOSS_SPREAD),
        f"loss rose: {losses}",
    )

    per_device: dict = {}
    leaves = jax.tree.leaves(state)
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = per_device.get(shard.device.id, 0) + shard.data.nbytes
    total = sum(leaf.nbytes for leaf in leaves)
    share = max(per_device.values()) / total
    peaks = {
        d.id: (d.memory_stats() or {}).get("peak_bytes_in_use", "n/a") for d in devices
    }
    _line(
        "train:full",
        arch=f"{cfg.name}:{cfg.n_layers}layers:{cfg.n_params() / 1e9:.2f}B",
        batch=f"{batch}x{seq}",
        losses=",".join(f"{x:.4f}" for x in losses),
        state_bytes=total,
        state_bytes_per_device=",".join(f"{d}:{b}" for d, b in sorted(per_device.items())),
        peak_bytes_in_use=",".join(f"{d}:{b}" for d, b in sorted(peaks.items())),
        max_err="n/a",
        seconds=f"{time.perf_counter() - t0:.1f}",
    )
    _check(len(per_device) == n_chips, f"state on devices {sorted(per_device)}")
    _check(share < 1.5 / n_chips, f"one device holds {share:.0%} of the state")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the four-chip sharded train phase, and no other")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.device import device_info, enable_compile_cache

    cache = enable_compile_cache()
    dev = device_info()
    _line("device", **dev, compile_cache=cache)
    if dev["platform"] != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX found platform {dev['platform']!r} "
            f"({dev['kind']}); not running on it",
            file=sys.stderr,
        )
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {dev['count']} device(s)", file=sys.stderr)
        return 2

    if args.chips == 4:
        train_phase(seed=args.seed)
    else:
        serving_phase(seed=args.seed)
        serve_driver_phase(seed=args.seed)
        modes = kernel_phase(seed=args.seed)
        interpreted = sorted(k for k, compiled in modes.items() if not compiled)
        _check(not interpreted, f"kernels not compiled for the chip: {interpreted}")
        fork_phase()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
