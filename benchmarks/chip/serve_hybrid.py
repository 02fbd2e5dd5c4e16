"""A serving cell of the hybrid Mamba / attention / MoE decoder: requests
pushed through a Koalja ``Workspace`` on one chip, as in ``serve.py``.

It shares ``serve.py``'s circuit, open-loop window, sample and verdict, and
has its own model: the program's configuration and parameter tree for the
hybrid layout (``arch_config``, ``program_params``), its weights
(``weights_hybrid.py``), reference (``reference_hybrid.py``) and work
(``work_hybrid.py``).

The served steps keep, in each MoE layer's slot of the serve cache, the
routing counters of its calls (held, absent and dropped token-expert rows,
held experts reached) and each position's experts. The task leaves them on
the device; after the window the run reads each request's counters
(``Run.route``, which the per-layer metrics read) and the sampled requests'
experts. The check compares the served logits with the reference's run on
the program's own expert choices where the reference's router margin is at
most the configuration's ``route_margin_tau``, and the choices with the
reference's own wherever its margin exceeds it.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np

import bench
import serve
import traffic
from serve import correct, lateness, sample_of  # noqa: F401  (the kind's verdict)


@dataclasses.dataclass
class Run(serve.Run):
    route: list = dataclasses.field(default_factory=list)  # per served request, see work_hybrid


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def arch_config(c: dict):
    """The program's ``ArchConfig`` for a configuration file: the registry's
    entry for ``arch`` with the file's sizes, its held experts, and a check
    that what the program derives (layout, dt rank, head size) is the file's."""
    from repro.configs import get_config

    import work_hybrid

    base = get_config(c["arch"])
    held = c["held_experts"]
    cfg = dataclasses.replace(
        base,
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        n_experts=c["router_outputs"],
        experts_held=c["num_experts"],
        experts_first=held[0],
        top_k=c["num_experts_per_tok"],
        ssm_state=c["mamba_d_state"],
        ssm_conv=c["mamba_d_conv"],
        ssm_expand=c["mamba_expand"],
        norm_eps=float(c["rms_norm_eps"]),
        dtype=c["torch_dtype"],
    )
    k = work_hybrid.dims(c)
    got = ([(s.mixer, s.ffn) for s in cfg.layout] * cfg.n_groups, cfg.dt_rank, cfg.head_dim, held)
    want = (work_hybrid.layout(c), k["R"], k["Dh"], list(range(held[0], held[0] + k["Eh"])))
    if got != want:
        raise ValueError(f"the program's {c['arch']} derives {got}, the configuration states {want}")
    return cfg


def program_params(w: dict, model) -> dict:
    """The benchmark's weights in the tree the program's steps take, checked
    leaf by leaf against the shapes the program declares."""
    import jax

    from repro.dist.step import param_specs

    tree = {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "blocks": list(w["layers"]),
    }
    want, _ = param_specs(model)
    if jax.tree.structure(want) != jax.tree.structure(tree):
        raise ValueError(
            "the program's parameter tree is not the one the benchmark makes: "
            f"{jax.tree.structure(want)} != {jax.tree.structure(tree)}"
        )
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(tree)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"program expects {a.shape}:{a.dtype}, benchmark made {b.shape}:{b.dtype}")
    return tree


def build_program(c: dict, mix: dict, device) -> serve.Program:
    import jax
    import jax.numpy as jnp

    from repro.dist.step import make_serve_fns
    from repro.launch.mesh import make_host_mesh
    from repro.models.registry import build_model, init_serve_state

    model = build_model(arch_config(c))
    max_len = int(mix["max_len"])
    prefill, decode, _, shards = make_serve_fns(
        model, make_host_mesh(devices=[device]), max_len=max_len, global_batch=1
    )
    new_state = jax.jit(
        lambda: init_serve_state(model, 1, max_len), out_shardings=shards["state"]
    )
    pick = jax.jit(lambda logits: jnp.argmax(logits, -1).astype(jnp.int32)[:, None])
    put = jax.jit(
        lambda buf, row, i: jax.lax.dynamic_update_slice_in_dim(
            buf, row.reshape((1,) + buf.shape[1:]), i, 0
        ),
        donate_argnums=0,
    )
    gen_max = int(mix["gen_max"])
    empty = jax.jit(lambda: (
        jnp.full((gen_max,), -1, jnp.int32),
        jnp.zeros((gen_max, c["vocab_size"]), jnp.dtype(c["torch_dtype"])),
    ))
    return serve.Program(prefill, decode, new_state, pick, put, empty, model)


def moe_slots(model) -> list:
    """Layout positions whose serve cache holds a routing record."""
    return [j for j, s in enumerate(model.cfg.layout) if s.ffn == "moe"]


def make_generate(prog: serve.Program, box: dict, calls: list, routes: list):
    """The task function, on the parameters in ``box["params"]``. Appends its
    own wall time to ``calls`` and the final state's routing records, left
    on the device, to ``routes``."""
    import jax
    from jax.profiler import TraceAnnotation

    slots = moe_slots(prog.model)

    def generate(prompt, gen):
        params = box["params"]
        t0 = time.perf_counter()
        with TraceAnnotation("bench:task"):
            with TraceAnnotation("bench:prefill"):
                toks, rows = prog.empty()
                logits, state = prog.prefill(params, np.asarray(prompt)[None], prog.new_state())
                tok = prog.pick(logits)
                toks, rows = prog.put(toks, tok, 0), prog.put(rows, logits, 0)
            with TraceAnnotation("bench:decode"):
                for i in range(1, int(gen)):
                    logits, state = prog.decode(params, tok, state)
                    tok = prog.pick(logits)
                    toks, rows = prog.put(toks, tok, i), prog.put(rows, logits, i)
            with TraceAnnotation("bench:wait"):
                toks, rows = jax.block_until_ready((toks, rows))
        calls.append(time.perf_counter() - t0)
        caches = state["caches"]
        routes.append([(caches[j]["route"], caches[j]["choice"]) for j in slots])
        return {"tokens": toks, "logits": rows}

    return generate


def scope_map(prog: serve.Program, params, mix: dict) -> dict:
    """Operation name to named scope of every compiled serve step (see
    ``scopes.py``): the prefill at each prompt length and the decode step."""
    import scopes

    def text(fn, tokens):  # lowered on arguments placed as the served ones are
        return fn.lower(params, tokens, prog.new_state()).compile().as_text()

    hlo = {f"prefill_fn:{n}": text(prog.prefill, np.zeros((1, n), np.int32))
           for n in sorted(set(mix["prompt_lengths"]))}
    token = prog.pick(prog.empty()[1][:1])  # a served token: the device's, as pick leaves it
    return scopes.op_scopes({**hlo, "decode_fn": text(prog.decode, token)})


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def check(run: Run, weights, choices: dict, *, control: str | None = None) -> dict:
    """Compare the sampled requests' served logits with the reference's, run
    on the served expert choices (``choices``, by index into ``run.served``)
    where its own router margin is at most ``route_margin_tau`` and on its own
    elsewhere, at every served position, and each served token with the top of its own
    logits; count the choices that differ from the reference's own where its
    margin exceeds ``route_margin_tau``, and the dropped rows of every
    request. With ``control`` ("int8") the control, routed by itself, stands
    in for the program: the same comparison has to find it not correct."""
    import reference_hybrid as ref

    c, mix = run.config, run.mix
    vocab, tau = c["vocab_size"], c["limits"]["route_margin_tau"]
    bad = sum(
        int(len(s.tokens) != s.req.gen or not ((s.tokens >= 0) & (s.tokens < vocab)).all())
        for s in run.served
    )
    dropped = sum(r[0][2] + r[1][2] for route in run.route if route for r in route)
    err, not_greedy, compared, wide, flips, flip_margin = 0.0, 0, 0, 0, 0, 0.0
    for i, s in enumerate(run.served):
        if s.logits is None:
            continue
        args = (weights, c, s.req.prompt, s.tokens, int(mix["max_len"]), int(mix["gen_max"]))
        if control:
            got, chosen, _ = ref.served(*args, control=control)
        else:
            got, chosen = np.asarray(s.logits, np.float32)[: len(s.tokens)], choices[i]
        want, own, margin = ref.served(*args, forced=chosen, tau=tau)
        err = max(err, ref.logit_err(got, want))
        not_greedy += int(np.sum(got.argmax(-1) != s.tokens))
        compared += len(s.tokens)
        n = own.shape[1]
        w, f, m = ref.route_mismatch(chosen[:, :n], own, margin, tau)
        wide, flips, flip_margin = wide + w, flips + f, max(flip_margin, m)
    return {
        "logit_err": {"value": err if compared else None, "limit": c["limits"]["logit_err"]},
        "not_greedy": {"value": not_greedy, "limit": 0},
        "bad_answers": {"value": bad, "limit": 0},
        "dropped_tokens": {"value": dropped, "limit": 0},
        "route_mismatch_wide": {"value": wide, "limit": 0},
        "route_flips": {"value": flips, "limit": None},
        "route_flip_margin_max": {"value": flip_margin, "limit": None},
        "compared_tokens": {"value": compared, "limit": None},
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Session(serve.Session):
    routes: list = dataclasses.field(default_factory=list)  # device records, per task call


def setup(prog: serve.Program, c: dict, mix: dict, seed: int, seconds: float, tmp: str) -> Session:
    """Weights from the seed, a fresh workspace, the window's requests, and a
    warm-up push of each prompt length."""
    import weights_hybrid

    words = bench.seeds(seed)
    w = weights_hybrid.make(c, words[0])
    calls: list = []
    routes: list = []
    box = {"params": program_params(w, prog.model)}
    ws, task = serve.make_workspace(make_generate(prog, box, calls, routes), tmp)
    reqs = traffic.schedule(mix, seconds, words[1:3], c["vocab_size"])
    keep = sample_of(reqs, int(mix["check_requests"]), words)
    for prompt in traffic.warmup_prompts(mix, seconds, words[1:3], c["vocab_size"]):
        ws.push(task, prompt=prompt, gen=2)
    routes.clear()
    return Session(w, ws, task, calls, reqs, keep, box, routes)


def read_routes(run: Run, ses: Session) -> dict:
    """After the window: each request's counters into ``run.route`` (None for
    a memo hit), per MoE layer in layer order a prefill and a decode row of
    ``ROUTE_COUNTERS``, and the sampled requests' expert choices, (M, S, K)
    each, by served index."""
    misses = [i for i, s in enumerate(run.served) if not s.hit]
    if len(misses) != len(ses.routes):
        raise RuntimeError(f"{len(ses.routes)} task calls for {len(misses)} memo misses")
    by_req = dict(zip(misses, ses.routes))

    def layers(records, k):  # (G, ...) per MoE slot -> one entry per layer
        arrays = [np.asarray(rec[k]) for rec in records]
        return [a[g] for g in range(arrays[0].shape[0]) for a in arrays]

    run.route = [
        np.asarray(layers(by_req[i], 0)).tolist() if i in by_req else None
        for i in range(len(run.served))
    ]
    choices = {
        i: np.stack([ch[0] for ch in layers(by_req[i], 1)])
        for i, s in enumerate(run.served) if s.logits is not None and i in by_req
    }
    ses.routes.clear()
    return choices


def run(c: dict, mix: dict, *, seed: int, seconds: float, trace: bool, devices,
        started: float, tmp: str) -> tuple:
    """One run of the cell, its files under ``tmp``. Returns (Run, checks,
    device block)."""
    import jax

    import devtrace

    prog = build_program(c, mix, devices[0])
    ses = setup(prog, c, mix, seed, seconds, tmp)
    trace_dir = os.path.join(tmp, "trace")
    setup_s = time.perf_counter() - started
    if trace:
        jax.profiler.start_trace(trace_dir)
    opened, served, failed, ended = serve.window(
        ses.ws, ses.task, ses.reqs, seconds, ses.calls, ses.keep
    )
    if trace:
        jax.profiler.stop_trace()
    device = bench.device_block(devices)
    result = Run(c, mix, seconds, opened, served, failed, setup_s, ses.ws.stats(), ended=ended)
    choices = read_routes(result, ses)
    if trace:
        result.trace = devtrace.collect(trace_dir)
        result.trace["scopes"] = scope_map(prog, ses.box["params"], mix)
    late = lateness(result)
    push_s = [s.end - s.start for s in served]
    print(
        f"window: {len(served)} served, {len(failed)} failed; push {np.mean(push_s):.4f} s "
        f"mean, task {np.mean([s.task_s for s in served]):.4f} s mean (capacity "
        f"{len(served) / max(sum(push_s), 1e-9):.3f} req/s); pushing thread late on "
        f"{late['n']} free starts by {late['mean_ms']:.3f} ms mean, {late['max_ms']:.3f} ms max",
        file=sys.stderr,
    )
    ses.release()
    checks = check(result, ses.weights, choices)
    return result, checks, device
