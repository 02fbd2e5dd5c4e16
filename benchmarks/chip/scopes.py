"""Device time of the program's named scopes, from a profiler trace and the
compiled programs' HLO.

A trace names each device operation by its instruction name in the compiled
module (``fusion.48``), and each execution of a module by the jitted
function's name and a fingerprint (``jit_prefill_fn(1508...)``). Names are
unique inside one module, not across modules, and the prefill compiles once
per prompt length, so the map from operation to scope is kept per compiled
program: ``{"decode_fn": {op: scope}, "prefill_fn:256": {...}, ...}``,
built from the ``op_name`` metadata of each compiled instruction. A prefill
execution is told apart by the prompt length of the push it falls in.
"""

from __future__ import annotations

import bisect
import re

import devtrace

SCOPES = {  # a scope of the program, by the name it goes under here
    "mamba_scan": ("jnp_mamba_scan", "pallas_mamba_scan"),
    "moe": ("moe_layer",),
}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_MODULE = re.compile(r"^jit_(\w+?)(?:\(|$)")


def scope_of(op_name: str):
    parts = op_name.split("/")
    for scope, names in SCOPES.items():
        if any(n in parts for n in names):
            return scope
    return None


def op_scopes(hlo: dict) -> dict:
    """``{program: compiled HLO text}`` to ``{program: {op: scope}}``. An
    instruction the compiler made, with no traced op's name stack in its
    ``op_name`` (the grouped matmul's kernel and masked convolutions, say),
    takes the scope of the operands it computes on, where they have one."""
    out: dict = {}
    for key, text in hlo.items():
        scope: dict = {}
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            op_name = _OP_NAME.search(rest)
            if op_name and "/" in op_name.group(1):  # a traced op's name stack
                scope[name] = scope_of(op_name.group(1))
            else:
                args = re.findall(r"%([\w.\-]+)", rest.split(", metadata=")[0])
                scope[name] = next((scope[a] for a in args if scope.get(a)), None)
        out[key] = {op: s for op, s in scope.items() if s}
    return out


def scope_ns(trace: dict, scope: str, lo: float, hi: float, prompt_lens: list):
    """Device time in [lo, hi] covered by operations of ``scope``, averaged
    over the device planes: the union of their intervals, so a loop and the
    operations of its body count once. ``prompt_lens`` are the prompt
    lengths of the window's pushes, in order. None without a scope map or a
    device."""
    scopes = trace.get("scopes")
    planes = list(trace["devices"].values())
    if not scopes or not planes:
        return None
    pushes = [s for s, _ in devtrace.spans(trace, "push")]
    total = 0.0
    for p in planes:
        mods = sorted(p["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        mine = []
        for name, s, e in p["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            fn = _MODULE.match(mods[i][0]) if i >= 0 and s < mods[i][2] else None
            if fn is None:
                continue
            key = fn.group(1)
            if key == "prefill_fn":
                k = bisect.bisect_right(pushes, mods[i][1]) - 1
                if not 0 <= k < len(prompt_lens):
                    continue
                key = f"prefill_fn:{prompt_lens[k]}"
            if scopes.get(key, {}).get(name) == scope and min(e, hi) > max(s, lo):
                mine.append([name, max(s, lo), min(e, hi)])
        total += sum(b - a for a, b in devtrace.union(mine))
    return total / len(planes)
