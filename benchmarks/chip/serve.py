"""A serving cell: requests pushed through a Koalja ``Workspace`` on one chip.

The circuit under test holds one task, ``generate(prompt, gen)``: prefill
through the program's ``make_serve_fns`` into a fresh cache, then ``gen - 1``
greedy decode steps, ending in ``jax.block_until_ready``; its output AVs
are the served token ids and the logits each was picked from. The
workspace is the program's default inline executor on a flat topology,
with its memo and a journal in the run's temporary directory. One thread
pushes the mix's requests open loop: each is pushed when it is due, or as
soon as the one before it returns. A request's latency runs from its due
time to the return of its push.

After the window the logits of a sample of finished requests, drawn from the
seed and always holding the longest, are compared with the plain float32
reference's (``reference.py``) and each served token with the top of its
own logits, and the run's checks printed beside their limits.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np

import bench
import traffic

DRAIN_S = 60.0  # a request not pushed this long after the window closes fails


@dataclasses.dataclass
class Served:
    req: traffic.Request
    due: float  # perf_counter seconds
    start: float
    end: float
    task_s: float  # wall time of the task function itself; 0 for a memo hit
    hit: bool
    tokens: np.ndarray  # the first ``gen`` served ids
    logits: object = None  # (gen_max, V) as served, kept for the sampled requests

    @property
    def latency(self) -> float:
        return self.end - self.due


@dataclasses.dataclass
class Run:
    """What a serving run leaves for the metric readers."""

    config: dict
    mix: dict
    seconds: float
    opened: float  # perf_counter when the window opened
    served: list
    failed: list  # requests never pushed (past the drain limit)
    setup_s: float
    stats: dict
    ended: float = 0.0  # perf_counter when the last push returned
    trace: dict | None = None
    peak: dict | None = None

    @property
    def closes(self) -> float:
        return self.opened + self.seconds

    def latencies(self) -> list:
        """Every request due in the window: a served one by its latency, a
        failed one by the least it would have had, its wait to the run's end."""
        return [s.latency for s in self.served] + [
            self.ended - (self.opened + r.due) for r in self.failed
        ]


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def arch_config(c: dict):
    """The program's ``ArchConfig`` for a configuration file: the registry's
    entry for ``arch`` with the file's sizes."""
    from repro.configs import get_config

    base = get_config(c["arch"])
    return dataclasses.replace(
        base,
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_head=c.get("head_dim", 0),
        d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype=c["torch_dtype"],
    )


def program_params(w: dict, model) -> dict:
    """The benchmark's weights in the tree the program's steps take, checked
    leaf by leaf against the shapes the program declares."""
    import jax

    from repro.dist.step import param_specs

    L = w["layers"]
    tree = {
        "embed": w["embed"],
        "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "blocks": [
            {
                "ln1": L["ln1"],
                "mixer": {k: L[k] for k in ("wq", "wk", "wv", "wo")},
                "ln2": L["ln2"],
                "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")},
            }
        ],
    }
    want, _ = param_specs(model)
    if jax.tree.structure(want) != jax.tree.structure(tree):
        raise ValueError("the program's parameter tree is not the one the benchmark makes")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(tree)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"program expects {a.shape}:{a.dtype}, benchmark made {b.shape}:{b.dtype}")
    return tree


@dataclasses.dataclass
class Program:
    """The jitted pieces of the served path for one configuration."""

    prefill: object
    decode: object
    new_state: object
    pick: object
    put: object
    empty: object
    model: object


def build_program(c: dict, mix: dict, device) -> Program:
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
    return Program(prefill, decode, new_state, pick, put, empty, model)


def make_generate(prog: Program, box: dict, calls: list):
    """The task function, on the parameters in ``box["params"]``. Appends its
    own wall time to ``calls``."""
    import jax
    from jax.profiler import TraceAnnotation

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
        return {"tokens": toks, "logits": rows}

    return generate


def make_workspace(generate, tmp: str):
    from repro.workspace import InlineExecutor, Workspace

    ws = Workspace(
        "bench-serve",
        executor=InlineExecutor(),
        topology=False,
        journal_path=os.path.join(tmp, "journal.jsonl"),
    )
    task = ws.task(
        generate, name="generate", inputs=["prompt", "gen"], outputs=["tokens", "logits"]
    )
    return ws, task


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def window(ws, task, reqs: list, seconds: float, calls: list, keep=frozenset()):
    """Push ``reqs`` open loop; return (opened, served, failed, ended). The
    served logits of the requests at the indices ``keep`` are kept for the
    check. Counts the programs compiled meanwhile, which should be none."""
    import jax
    from jax.profiler import TraceAnnotation

    served, failed, compiles = [], [], []

    def count(event, duration, **kwargs):
        if "backend_compile" in event:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(count)
    opened = time.perf_counter()
    try:
        with TraceAnnotation("bench:window"):
            for i, r in enumerate(reqs):
                due = opened + r.due
                now = time.perf_counter()
                if now < due:
                    time.sleep(due - now)
                start = time.perf_counter()
                if start > opened + seconds + DRAIN_S:
                    failed.append(r)
                    continue
                calls.clear()
                with TraceAnnotation("bench:push"):
                    res = ws.push(task, prompt=r.prompt, gen=r.gen)
                end = time.perf_counter()
                out = np.asarray(res[task]["tokens"])[: r.gen]
                task_s = calls[0] if calls else 0.0
                rows = res[task]["logits"] if i in keep else None
                served.append(Served(r, due, start, end, task_s, not calls, out, rows))
        ended = time.perf_counter()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    print(f"window: {len(compiles)} compilations", file=sys.stderr)
    return opened, served, failed, ended


def lateness(run: Run) -> dict:
    """How late the pushing thread started requests it was free to start on
    time: the generator's own delay, apart from queueing."""
    late, free_at = [], run.opened
    for s in run.served:
        if free_at <= s.due:
            late.append(s.start - s.due)
        free_at = s.end
    return {
        "n": len(late),
        "mean_ms": 1e3 * float(np.mean(late)) if late else 0.0,
        "max_ms": 1e3 * float(np.max(late)) if late else 0.0,
    }


def check(run: Run, weights, *, control: str | None = None) -> dict:
    """Compare the sampled requests' served logits with the reference's, at
    every served position, and each served token with the top of its own
    logits. With ``control`` ("int8", "fp8") the control's logits, read at
    the same positions on the same prompts and served tokens, stand in for
    the program's: the same comparison has to find them not correct."""
    import reference

    c, mix = run.config, run.mix
    vocab = c["vocab_size"]
    bad = sum(
        int(len(s.tokens) != s.req.gen or not ((s.tokens >= 0) & (s.tokens < vocab)).all())
        for s in run.served
    )
    firsts: dict = {}
    mismatch = 0
    for s in run.served:
        if s.req.popular >= 0:
            first = firsts.setdefault(s.req.popular, s.tokens)
            mismatch += int(not np.array_equal(first, s.tokens))
    err, not_greedy, compared = 0.0, 0, 0
    for s in run.served:
        if s.logits is None:
            continue
        args = (weights, c, s.req.prompt, s.tokens, int(mix["max_len"]), int(mix["gen_max"]))
        ref = reference.served_logits(*args)
        if control:
            got = reference.served_logits(*args, control=control)
        else:
            got = np.asarray(s.logits, np.float32)[: len(s.tokens)]
        err = max(err, reference.logit_err(got, ref))
        not_greedy += int(np.sum(got.argmax(-1) != s.tokens))
        compared += len(s.tokens)
    return {
        "logit_err": {"value": err if compared else None, "limit": c["limits"]["logit_err"]},
        "not_greedy": {"value": not_greedy, "limit": 0},
        "bad_answers": {"value": bad, "limit": 0},
        "repeat_mismatch": {"value": mismatch, "limit": 0},
        "compared_tokens": {"value": compared, "limit": None},
    }


def sample_of(reqs: list, n: int, seed_words: list) -> frozenset:
    """Indices of ``n`` of the window's requests drawn from the seed, the
    longest among them."""
    if not reqs:
        return frozenset()
    longest = max(range(len(reqs)), key=lambda i: reqs[i].prompt_len + reqs[i].gen)
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = np.random.default_rng([*seed_words, 2])
    pick = rng.choice(rest, size=min(n - 1, len(rest)), replace=False).tolist() if rest else []
    return frozenset([longest, *pick])


def correct(checks: dict) -> bool:
    return all(
        c["limit"] is None or (c["value"] is not None and c["value"] <= c["limit"])
        for c in checks.values()
    )


@dataclasses.dataclass
class Session:
    """One set of weights behind a fresh workspace, ready for a window."""

    weights: dict
    ws: object
    task: object
    calls: list
    reqs: list
    keep: frozenset  # indices of the requests the check samples
    box: dict

    def release(self) -> None:
        """Drop the task's hold on the weights. The newest workspace
        outlives its last reference (``core.hashing`` keeps a process-wide
        callback into the newest pipeline manager), so its task must not
        keep the weights alive while the next session makes its own."""
        self.box.clear()


def setup(prog: Program, c: dict, mix: dict, seed: int, seconds: float, tmp: str) -> Session:
    """Everything before the window but the programs: weights from the seed,
    a fresh workspace, the window's requests, and a warm-up push of each
    prompt length."""
    import weights as weights_mod

    words = bench.seeds(seed)
    w = weights_mod.make(c, words[0])
    calls: list = []
    box = {"params": program_params(w, prog.model)}
    ws, task = make_workspace(make_generate(prog, box, calls), tmp)
    reqs = traffic.schedule(mix, seconds, words[1:3], c["vocab_size"])
    keep = sample_of(reqs, int(mix["check_requests"]), words)
    for prompt in traffic.warmup_prompts(mix, seconds, words[1:3], c["vocab_size"]):
        ws.push(task, prompt=prompt, gen=2)
    return Session(w, ws, task, calls, reqs, keep, box)


def run(c: dict, mix: dict, *, seed: int, seconds: float, trace: bool, devices,
        started: float, tmp: str) -> tuple:
    """One run of a serving cell, its files under ``tmp``. Returns (Run,
    checks, device block)."""
    import jax

    import devtrace

    prog = build_program(c, mix, devices[0])
    ses = setup(prog, c, mix, seed, seconds, tmp)
    trace_dir = os.path.join(tmp, "trace")
    setup_s = time.perf_counter() - started
    if trace:
        jax.profiler.start_trace(trace_dir)
    opened, served, failed, ended = window(
        ses.ws, ses.task, ses.reqs, seconds, ses.calls, ses.keep
    )
    if trace:
        jax.profiler.stop_trace()
    device = bench.device_block(devices)
    result = Run(c, mix, seconds, opened, served, failed, setup_s, ses.ws.stats(), ended=ended)
    if trace:
        result.trace = devtrace.collect(trace_dir)
    late = lateness(result)
    push_s = [s.end - s.start for s in served]
    miss = [s for s in served if not s.hit] or served
    circuit_ms = 1e3 * np.asarray([s.end - s.start - s.task_s for s in miss])
    task_s = np.asarray([s.task_s for s in miss])
    print(
        f"window: circuit ms per miss p50 {np.median(circuit_ms):.3f} max "
        f"{circuit_ms.max():.3f}; task s p50 {np.median(task_s):.4f} max "
        f"{task_s.max():.4f}; latency s max {max(s.latency for s in served):.4f}",
        file=sys.stderr,
    )
    print(
        f"window: {len(served)} served, {len(failed)} failed, "
        f"{sum(s.hit for s in served)} memo hits; push {np.mean(push_s):.4f} s mean, "
        f"task {np.mean([s.task_s for s in served if not s.hit] or [0]):.4f} s mean "
        f"(capacity {len(served) / max(sum(push_s), 1e-9):.3f} req/s); pushing thread "
        f"late on {late['n']} free starts by {late['mean_ms']:.3f} ms mean, "
        f"{late['max_ms']:.3f} ms max",
        file=sys.stderr,
    )
    ses.release()
    checks = check(result, ses.weights)
    return result, checks, device
