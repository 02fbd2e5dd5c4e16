import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is Koalja wireframing (§III.K) applied to the TPU program: ghost batches
(ShapeDtypeStructs) are pushed through the full distributed train/serve step —
``jit(...).lower(...).compile()`` — proving the sharded wiring (collective
schedule, per-device memory) without allocating a byte of real data.

  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all                # 40-cell baseline table
  python -m repro.launch.dryrun --all --multipod     # 2-pod (512 chip) pass

Each cell's record (device count, compile time, per-device state and XLA's
memory analysis) is written to
benchmarks/results/dryrun/<mesh>/<arch>__<shape>.json.
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, all_cells, cell_skip_reason, get_config
from repro.dist.step import (
    make_batch_specs,
    make_serve_fns,
    make_train_state_specs,
    make_train_step,
)
from repro.dist.sharding import make_rules
from repro.launch.device import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model
from repro.optim import cosine_warmup

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmarks", "results", "dryrun")


def _mesh_name(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def dryrun_cell(
    arch: str,
    shape: str,
    *,
    multi_pod: bool = False,
    overrides: dict | None = None,
    compress_pods: bool = False,
    microbatches: int = 1,
    verbose: bool = True,
    save: bool = True,
    tag: str = "",
):
    """Lower+compile one cell; returns its record (dict)."""
    import dataclasses

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    spec = SHAPES[shape]
    skip = cell_skip_reason(cfg, shape)
    if skip:
        rec = {"arch": arch, "shape": shape, "mesh": _mesh_name(multi_pod), "skip": skip}
        if save:
            _save(rec, multi_pod, arch, shape, tag)
        if verbose:
            print(f"[SKIP] {arch} x {shape}: {skip}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    model = build_model(cfg)
    mode = "train" if spec.kind == "train" else "serve"
    rules = make_rules(cfg, mesh, mode, spec.global_batch)
    t0 = time.time()

    if spec.kind == "train":
        jitted, state_shapes, state_shard, batch_shard = make_train_step(
            model,
            mesh,
            cosine_warmup(3e-4, 2000, 100_000),
            rules=rules,
            global_batch=spec.global_batch,
            microbatches=microbatches,
            compress_pods=compress_pods,
        )
        batch = make_batch_specs(cfg, "train", spec.global_batch, spec.seq_len)
        with mesh:
            lowered = jitted.lower(state_shapes, batch)
            compiled = lowered.compile()
    else:
        max_len = spec.seq_len
        if spec.kind == "prefill" and cfg.frontend == "vision":
            max_len += cfg.frontend_len  # image prefix occupies cache slots
        prefill_jit, decode_jit, st_shapes, shards = make_serve_fns(
            model, mesh, max_len=max_len, global_batch=spec.global_batch, rules=rules
        )
        if spec.kind == "prefill":
            batch = make_batch_specs(cfg, "prefill", spec.global_batch, spec.seq_len)
            frames = batch.get("frames")
            prefix = batch.get("prefix")
            with mesh:
                lowered = prefill_jit.lower(
                    _param_shapes(model), batch["tokens"], st_shapes, frames, prefix
                )
                compiled = lowered.compile()
        else:  # decode: one new token against a seq_len-deep cache
            dec_state = dict(st_shapes)
            if cfg.encoder_layers:
                dec_state["memory"] = jax.ShapeDtypeStruct(
                    (spec.global_batch, cfg.frontend_len, cfg.d_model),
                    cfg.compute_dtype(),
                )
            tokens = jax.ShapeDtypeStruct((spec.global_batch, 1), jnp.int32)
            with mesh:
                lowered = decode_jit.lower(_param_shapes(model), tokens, dec_state)
                compiled = lowered.compile()

    compile_s = time.time() - t0

    # analytic per-device state size from the actual shardings (params +
    # optimizer state for train; params + caches for serve)
    def _sharded_gb(shapes_tree, shard_tree):
        import math as _math

        total = 0
        for s, sh in zip(jax.tree.leaves(shapes_tree), jax.tree.leaves(shard_tree)):
            n = s.size * s.dtype.itemsize
            div = 1
            for entry in sh.spec:
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                div *= _math.prod(mesh.shape[a] for a in axes)
            total += n / div
        return total / 1e9

    if spec.kind == "train":
        state_gb = _sharded_gb(state_shapes, state_shard)
    else:
        from repro.dist.step import param_specs as _ps

        pshapes, _ = _ps(model)
        state_gb = _sharded_gb(pshapes, shards["params"]) + _sharded_gb(
            st_shapes["caches"], shards["state"]["caches"]
        )

    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": _mesh_name(multi_pod),
        "n_devices": n_dev,
        "kind": spec.kind,
        "compile_seconds": compile_s,
        "state_gb_per_device": state_gb,
    }
    if state_gb > 16.0:
        print(f"[WARN] {arch} x {shape}: state {state_gb:.1f} GB/device exceeds v5e HBM")
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(ma, k))
            for k in (
                "temp_size_in_bytes",
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
            if hasattr(ma, k)
        }
    except Exception:
        rec["memory_analysis"] = None

    if verbose:
        print(
            f"[OK] {arch} x {shape} ({_mesh_name(multi_pod)}): "
            f"state {state_gb:.2f} GB/device (compile {compile_s:.1f}s)"
        )
    if save:
        _save(rec, multi_pod, arch, shape, tag)
    return rec


def _param_shapes(model):
    from repro.dist.step import param_specs

    shapes, _ = param_specs(model)
    return shapes


def _save(rec: dict, multi_pod: bool, arch: str, shape: str, tag: str = ""):
    d = os.path.join(os.path.abspath(RESULTS_DIR), _mesh_name(multi_pod))
    os.makedirs(d, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(d, f"{arch}__{shape}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=str)


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--compress-pods", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument(
        "--set", action="append", default=[],
        help="ArchConfig override, e.g. --set remat=full --set block_kv=1024",
    )
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = json.loads(v.lower()) if v.lower() in ("true", "false") else (
            int(v) if v.lstrip("-").isdigit() else v
        )

    failures = []
    if args.all:
        for arch, shape, skip in all_cells():
            try:
                dryrun_cell(
                    arch, shape,
                    multi_pod=args.multipod,
                    overrides=overrides or None,
                    compress_pods=args.compress_pods,
                    microbatches=args.microbatches,
                    tag=args.tag,
                )
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape, repr(e)))
                print(f"[FAIL] {arch} x {shape}: {e}")
        if failures:
            print(f"\n{len(failures)} cell(s) FAILED:")
            for a, s, e in failures:
                print(f"  {a} x {s}: {e}")
            sys.exit(1)
        print("\nAll cells passed.")
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        dryrun_cell(
            args.arch, args.shape,
            multi_pod=args.multipod,
            overrides=overrides or None,
            compress_pods=args.compress_pods,
            microbatches=args.microbatches,
            tag=args.tag,
        )


if __name__ == "__main__":
    main()
