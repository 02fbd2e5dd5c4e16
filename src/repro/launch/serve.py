"""Batched serving driver (prefill + decode against KV/SSM caches).

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
      --batch 4 --prompt-len 32 --gen 16

Prints the device it ran on, then the prefill time, the first decode step
(which compiles) and the steady decode steps, each ended by
``block_until_ready``. ``--gen`` is at least 2.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.dist.sharding import make_rules
from repro.dist.step import make_serve_fns
from repro.launch.device import device_info, enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model, init_serve_state


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"device {dev['platform']} {dev['kind']} x{dev['count']}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = make_host_mesh()
    max_len = args.prompt_len + args.gen + 8

    prefill_jit, decode_jit, st_shapes, shards = make_serve_fns(
        model, mesh, max_len=max_len, global_batch=args.batch,
        rules=make_rules(cfg, mesh, "serve", args.batch),
    )
    # params and caches are made where they live: sharded, on the devices
    params = jax.jit(lambda k: model.init(k)[0], out_shardings=shards["params"])(
        jax.random.key(args.seed)
    )
    state = jax.jit(
        lambda: init_serve_state(model, args.batch, max_len),
        out_shardings=shards["state"],
    )()
    prompts = jax.random.randint(
        jax.random.key(args.seed + 1), (args.batch, args.prompt_len), 0, cfg.vocab
    )
    frames = (
        jax.random.normal(jax.random.key(2), (args.batch, cfg.frontend_len, cfg.d_model))
        if cfg.encoder_layers
        else None
    )
    prefix = (
        jax.random.normal(jax.random.key(3), (args.batch, cfg.frontend_len, cfg.d_model))
        if cfg.frontend == "vision"
        else None
    )

    t0 = time.perf_counter()
    prefill_c = prefill_jit.lower(params, prompts, state, frames, prefix).compile()
    compile_s = time.perf_counter() - t0

    # every timing ends in block_until_ready: without it, it is the enqueue
    t0 = time.perf_counter()
    logits, state = prefill_c(params, prompts, state, frames, prefix)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None].block_until_ready()
    prefill_s = time.perf_counter() - t0

    def decode(tok, state):
        logits, state = decode_jit(params, tok, state)
        return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], state

    outs = [tok]
    t0 = time.perf_counter()
    tok, state = decode(tok, state)  # the first step compiles the decode
    outs.append(tok.block_until_ready())
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(args.gen - 2):
        tok, state = decode(tok, state)
        outs.append(tok)
    tok.block_until_ready()
    decode_s = time.perf_counter() - t0
    gen = jnp.concatenate(outs, axis=1)

    steps = args.gen - 2
    print(f"prefill {args.batch}x{args.prompt_len}: {prefill_s:.3f}s (compile {compile_s:.3f}s)")
    print(f"decode  first step (with compile): {first_s:.3f}s")
    print(
        f"decode  {steps} steps: {decode_s:.3f}s "
        f"({steps * args.batch / max(decode_s, 1e-9):.1f} tok/s)"
    )
    print("sample generations (token ids):")
    for row in gen[: min(4, args.batch)]:
        print("  ", row.tolist())
    return gen


if __name__ == "__main__":
    main()
