"""Device time by the scopes INSIDE a unit.

``reduce/scopes.py`` files every device operation under the unit of the
model it belongs to (the first scope the program opened).  A decoder layer
opens more inside its own: ``attn_qkv``, ``attn_core``, ``attn_out``,
``router``, ``dispatch``, ``experts``, ``combine``, ``shared_expert``,
``dense_ffn`` (``znicz_tpu/decoder.py``, ``ops/moe.py``).  This reads the
same trace against the same compiled texts with the same matching
(``scopes.Program``, ``scopes._match``: imported, nothing there is
edited) and keeps the NEXT scope too::

    (unit, inner, direction) -> self seconds on device 0

``inner`` is ``""`` for an operation directly under the unit's scope and
``update`` for the optimizer's; ``direction`` is ``forward``,
``recompute`` (the rematerialised forward inside the backward pass:
``rematted_computation`` in the path), ``backward`` or ``update``.  A
fusion takes its root's tag, else its body's most frequent; an
instruction without metadata takes what ``scopes.Program`` worked out for
it (the unit, no inner scope).
"""

from __future__ import annotations

import json
import re

from benchmark.reduce import scopes, xplane

_OP_NAME = re.compile(r'op_name="([^"]*)"')
UNSCOPED = ("", scopes.UNSCOPED, "")


def _peel(part: str):
    """``(name, is a jit(...))`` of a path component without its
    ``jvp``/``transpose`` wrappers."""
    while True:
        m = scopes._WRAPPED.match(part)
        if not m:
            return part, False
        if m.group(1) in ("jit", "pjit"):
            return part, True
        part = m.group(2)


def tag_of(op_name: str):
    """``(unit, inner, direction)`` of an instruction's ``op_name``, or
    ``None`` where the program named nothing."""
    found = scopes.scope_of(op_name)
    if not found:
        return None
    scope, direction = found
    unit = scopes.unit_of(scope)
    if direction == "update":
        return unit, "update", "update"
    parts = op_name.split("/")[:-1]
    if "rematted_computation" in parts:
        direction = "recompute"
    inner, seen = "", False
    for part in parts:
        name, is_jit = _peel(part)
        if not seen:
            seen = name == unit
            continue
        if is_jit:
            break                       # a library function's own naming
        if name and name != unit and not scopes._STRUCTURAL.match(name) \
                and not scopes._QUALNAME.match(name):
            inner = name
            break
    return unit, inner, direction


class Tags:
    """Instruction -> tag for one compiled text."""

    def __init__(self, program: scopes.Program, text: str):
        own = {}
        for line in text.splitlines():
            stripped = line.strip()
            if " = " not in stripped:
                continue
            m = _OP_NAME.search(stripped)
            if m:
                own[xplane.parse_op(stripped)[0]] = tag_of(m.group(1))
        self.tag = {}
        for name, ins in program.instr.items():
            tag = own.get(name)
            if ins["opcode"] == "fusion":
                body = [own.get(b) for b in program.bodies.get(
                    ins["calls"], [])]
                body = [t for t in body if t]
                root = own.get(program.roots.get(ins["calls"], ""))
                if body:
                    tag = root or max(set(body), key=body.count)
            if tag is None:
                scope = program.scope.get(name)
                if isinstance(scope, tuple):
                    tag = (scopes.unit_of(scope[0]), "",
                           scope[1] if scope[1] != "any" else "")
                elif scope == scopes.COLLECTIVE:
                    tag = ("", scopes.COLLECTIVE, "")
                elif scope == scopes.MIXED:
                    tag = ("", scopes.MIXED, "")
            self.tag[name] = tag or UNSCOPED


def reduce_inner(path: str, hlo_texts) -> dict:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    _, ops, modules = scopes._device0(profile)
    out = {"seconds": {}, "busy_s": 0.0, "unscoped_share": 1.0}
    if not ops:
        return out
    programs, tags = {}, {}
    for text in hlo_texts:
        program = scopes.Program(text)
        if program.name:
            programs.setdefault(program.name, []).append(program)
            tags[id(program)] = Tags(program, text)
    by_exec = {}
    for _s, _e, name, k in ops:
        by_exec.setdefault(k, []).append(name)
    program_of = {}
    for k, (_s, _e, module) in enumerate(modules):
        if module not in program_of:
            program_of[module] = scopes._match(programs, module,
                                               by_exec.get(k, []))

    def tag(name, k):
        program = program_of.get(modules[k][2]) if k is not None else None
        if program is None:
            return UNSCOPED
        return tags[id(program)].tag.get(xplane.parse_op(name)[0],
                                         UNSCOPED)

    table = {}
    for _name, key, t in xplane._self_times(
            [(s, e, name, tag(name, k)) for s, e, name, k in ops]):
        table[key] = table.get(key, 0.0) + t
    busy_ns = xplane._length(xplane._union([(s, e) for s, e, _, _ in ops]))
    out.update(
        seconds={"|".join(k): v / 1e9 for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])},
        busy_s=busy_ns / 1e9,
        unscoped_share=table.get(UNSCOPED, 0.0) / max(busy_ns, 1e-12))
    return out


def layer_kind(run: dict, unit: str) -> str:
    """``full`` or ``window`` for a decoder layer's unit (its name ends in
    its index among the forwards; the embedding is 0)."""
    digits = re.search(r"(\d+)$", unit)
    kinds = run["shape"]["model"]["layer_types"]
    if not digits or not 1 <= int(digits.group(1)) <= len(kinds):
        return ""
    return ("window" if kinds[int(digits.group(1)) - 1]
            == "sliding_attention" else "full")


def of_run(run: dict):
    """The reduction of a traced run, made once, kept in ``run["inner"]``
    and printed on an earlier line (``{"phase": "scopes"}``: seconds by
    inner scope and direction, the attention core split into window and
    full layers).  ``None`` where the run has no trace, or the driver kept
    no compiled texts, or more than ``scopes.MAX_UNSCOPED`` of the busy
    time carries no name."""
    if "inner" in run:
        return run["inner"]
    trace = run.get("trace") or {}
    path, programs = trace.get("path"), trace.get("programs")
    reduction = None
    if path and programs is not None:
        reduction = reduce_inner(path, programs.texts)
        steps = max(trace["train_steps"] + trace["eval_steps"], 1)
        by_inner, attention = {}, {}
        for key, t in reduction["seconds"].items():
            unit, inner, direction = key.split("|")
            k = f"{inner or 'unit'}|{direction}"
            by_inner[k] = by_inner.get(k, 0.0) + t
            if inner == "attn_core":
                kind = layer_kind(run, unit)
                attention[kind] = attention.get(kind, 0.0) + t
        reduction["by_inner_s"] = by_inner
        reduction["attn_core_ms_per_step"] = {
            k: v / steps * 1e3 for k, v in attention.items()}
        print(json.dumps({"phase": "scopes", "table": "inner", **{
            k: reduction[k] for k in ("busy_s", "unscoped_share",
                                      "by_inner_s",
                                      "attn_core_ms_per_step")}}),
              flush=True)
        if not reduction["busy_s"] \
                or reduction["unscoped_share"] > scopes.MAX_UNSCOPED:
            reduction = None
    run["inner"] = reduction
    return reduction


def seconds(reduction: dict, match) -> float:
    """Seconds under the tags ``match(unit, inner, direction)`` accepts."""
    return sum(t for key, t in reduction["seconds"].items()
               if match(*key.split("|")))


def ms_per_step(run: dict, match):
    reduction = of_run(run)
    if not reduction:
        return None
    trace = run["trace"]
    total = seconds(reduction, match)
    steps = trace["train_steps"] + trace["eval_steps"]
    return total / max(steps, 1) * 1e3 if total else None


def window_flops(run: dict):
    """``flops_decoder.window_flops`` of the traced window, or ``None``
    where the run lacks what it is computed from."""
    from benchmark import flops_decoder

    trace, shape = run.get("trace") or {}, run.get("shape") or {}
    if "moe_rows_routed" not in trace or "model" not in shape:
        return None
    return flops_decoder.window_flops(
        shape["model"], shape["share"], shape["batch"], shape["row_tokens"],
        trace["train_steps"], trace["eval_steps"], trace["moe_rows_routed"],
        trace["moe_counted_steps"])


def roofline(run: dict, part: str, inner: str):
    """Share of the bf16 peak that ``part``'s needed operations reach
    over the self time under ``inner``, in per cent."""
    reduction, flops, peaks = of_run(run), window_flops(run), run.get("peaks")
    if not reduction or not flops or not peaks:
        return None
    busy = seconds(reduction, lambda _u, i, _d: i == inner)
    if busy <= 0:
        return None
    return 100.0 * flops[part] / (peaks["bf16_tflops"] * 1e12) / busy
