"""From a profiler trace to the program's own names.

``reduce/xplane.py`` says how long the device was busy and with which
XLA operation; this says with which *unit of the model*, and what the
*program* was doing while the device idled — read from names the program
gave: ``jax.named_scope`` in the traced step (``input``, one scope per
forward unit, ``loss``, ``update/<layer>``; jax wraps the forward in
``jvp(...)`` and names the backward ``transpose(jvp(...))``), and the
``znicz:<cat>:<name>`` annotations every ``TraceRing.span()`` enters while
a profiler session is on (``znicz_tpu/telemetry/trace.py``).

Three tables, all of device 0:

(a) self time by ``(scope, direction)``.  Each ``XLA Ops`` event belongs
    to the program execution (``XLA Modules`` event) that contains it,
    and through that program's compiled text to a scope: the
    instruction's ``metadata={op_name=...}``; a fusion takes what its
    body's instructions say (its root's direction; ``mixed`` where the
    body spans two units); an instruction without metadata takes the
    scope all of its consumers share, or else all of its producers,
    seen through the plumbing of tuples and loops (the whole-shard cast
    is such a ``copy``: its only consumer is the gather inside the scan's
    body); what is left is ``unscoped``.  A collective is ``collective``
    whatever its metadata says: the partitioner put it there, and a
    combined all-reduce of every layer's gradient carries the name of
    one.  Nothing is guessed from shapes or instruction numbers, and an
    executable fetched from a compile cache written before the scopes
    existed carries no metadata at all (by default the cache's key
    ignores it): everything is then ``unscoped`` and the readers report
    nothing.
(b) program executions by program: the device's own dispatch count.
(c) idle gaps of at least 10 us by the innermost ``znicz:*`` span around
    them, with no clock offset: the spans are in the trace.

On the CPU backend the executor threads' ``hlo_op`` events stand in for
the device plane (their ``hlo_module``/``run_id`` statistics for the
module line), as in ``xplane.py``.  Such a reduction measures nothing.
"""

from __future__ import annotations

import bisect
import json
import re
import statistics

from benchmark.reduce import xplane

PREFIX = "znicz:"
UNSCOPED, MIXED, COLLECTIVE = "unscoped", "mixed", "collective"
#: readers report nothing above this share of busy time without a name
MAX_UNSCOPED = 0.05
#: spans that are the epoch's tail; ``sync`` and ``decide`` count inside
TAIL_SPANS = ("tail", "epoch_hook")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_HEADER = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
#: path components jax and XLA put around the program's own scopes
_STRUCTURAL = re.compile(
    r"^(?:while|body|cond|closed_call|core_call|checkpoint|remat|"
    r"rematted_computation|shard_map|branch_\d+_fun|"
    r"custom_[jv][jv]p_call(?:_jaxpr)?)$")
#: jax's own name for a traced function (``FusedTrainer.loss_and_metrics``
#: around an argmax's reduction): not a scope the program opened.  A unit
#: named like a dotted Python path would be taken for one and skipped.
_QUALNAME = re.compile(r"^[A-Za-z_]\w*(?:\.(?:<locals>|[A-Za-z_]\w*))+$")
#: instructions that move values and do no work of their own
_PLUMBING = ("get-tuple-element", "bitcast")


def scope_of(op_name: str):
    """``(scope, direction)`` of an instruction's ``op_name``, or ``None``
    where the program named nothing: the first path component after the
    leading ``jit(...)`` ones that is not structure (``while``, ``body``
    ...), with its ``jvp``/``transpose`` wrappers peeled; the last
    component is the primitive.  A ``jit(...)`` further in is a library
    function (``jit(_threefry_fold_in)/...``): what follows it is jax's
    naming, not the program's.  ``update/<layer>`` keeps its layer."""
    parts = op_name.split("/")
    leading = True
    for k, part in enumerate(parts[:-1]):
        backward = False
        while True:
            m = _WRAPPED.match(part)
            if not m or m.group(1) in ("jit", "pjit"):
                break
            backward |= m.group(1) == "transpose"
            part = m.group(2)
        if _WRAPPED.match(part):            # a jit(...)
            if leading:
                continue
            return None
        leading = False
        if not part or _STRUCTURAL.match(part) or _QUALNAME.match(part):
            continue                        # "jvp()" peels to nothing
        if part == "update":
            layer = parts[k + 1] if k + 2 < len(parts) else ""
            return (f"update/{layer}" if layer else "update"), "update"
        return part, ("backward" if backward else "forward")
    return None


def unit_of(scope: str) -> str:
    """The unit a scope belongs to: ``update/fc6`` and ``fc6`` are one."""
    return scope.removeprefix("update/")


def closing(s: str, start: int) -> int:
    """Index of the parenthesis that closes the one at ``start`` (the
    last character where none does)."""
    depth = 0
    for i in range(start, len(s)):
        depth += (s[i] == "(") - (s[i] == ")")
        if depth == 0:
            return i
    return len(s) - 1


def _operands(rest: str):
    """Names referenced inside the opcode's own parentheses."""
    start = rest.find("(")
    if start < 0:
        return []
    return _REF.findall(rest[start:closing(rest, start) + 1])


class Program:
    """One compiled module's text: its instructions, and the scope of
    each."""

    def __init__(self, text: str):
        self.name = text.split(None, 2)[1].rstrip(",") if text.startswith(
            "HloModule") else ""
        self.instr = {}         # name -> dict
        self.bodies = {}        # computation -> [instruction names]
        self.roots = {}         # computation -> root instruction name
        comp = None
        for line in text.splitlines():
            stripped = line.strip()
            header = _HEADER.match(stripped)
            if header and " = " not in stripped.split("(")[0]:
                comp = header.group(2)
                self.bodies[comp] = []
                continue
            if stripped == "}":
                comp = None
                continue
            if comp is None or " = " not in stripped:
                continue
            name, opcode, _kind, shape, calls = xplane.parse_op(stripped)
            after = stripped.partition(" = ")[2]
            if after.startswith("("):       # past a tuple shape
                after = after[closing(after, 0) + 1:]
            else:
                after = after.partition(" ")[2]
            op_name = _OP_NAME.search(stripped)
            index = _INDEX.search(after)
            body = re.search(r"body=%?([\w.\-]+)", after)
            self.instr[name] = {
                "opcode": opcode, "shape": shape, "calls": calls,
                "comp": comp, "operands": _operands(after),
                "index": int(index.group(1)) if index else None,
                "body": body.group(1) if body else "",
                "scope": scope_of(op_name.group(1)) if op_name else None}
            self.bodies[comp].append(name)
            if stripped.startswith("ROOT "):
                self.roots[comp] = name
        self.signature = {(n, i["opcode"], i["shape"])
                          for n, i in self.instr.items()}
        self.mixed_units = {}   # fusion -> "unit+unit"
        self.scope = self._attribute()
        scopes = {s for s in self.scope.values() if isinstance(s, tuple)}
        self.has_input = any(s[0] == "input" for s in scopes)
        self.role = (("train" if any(d == "update" for _, d in scopes)
                      else "eval") if scopes else "")

    # -- scopes ----------------------------------------------------------------

    def _fusion_scope(self, name):
        """What a fusion's body says: one unit -> the root's scope and
        direction (else the body's most frequent); two -> ``mixed``."""
        ins = self.instr[name]
        body = self.bodies.get(ins["calls"], [])
        inner = [self.instr[b]["scope"] for b in body
                 if self.instr[b]["scope"]]
        if not inner:
            return ins["scope"]
        units = sorted({unit_of(s) for s, _ in inner})
        if len(units) > 1:
            self.mixed_units[name] = "+".join(units)
            return MIXED
        root = self.instr.get(self.roots.get(ins["calls"], ""), {})
        return root.get("scope") or max(set(inner), key=inner.count)

    def _edges(self):
        """Producer -> consumers, seen through tuples and loops: a value
        put into a ``while``'s tuple is consumed where the body reads
        that index, and what the body's root puts at an index is what
        the next iteration and the loop's result read there."""
        tuples = {n: i["operands"] for n, i in self.instr.items()
                  if i["opcode"] == "tuple"}
        fed_by = {}             # body computation -> the while's operand
        for n, i in self.instr.items():
            if i["opcode"] == "while" and i["body"] and i["operands"]:
                fed_by[i["body"]] = i["operands"][0]
        users = {}

        def edge(src, dst):
            users.setdefault(src, []).append(dst)

        for n, i in self.instr.items():
            if i["opcode"] in ("tuple", "while", "parameter"):
                continue
            if i["opcode"] == "get-tuple-element" and i["operands"]:
                src, k = i["operands"][0], i["index"]
                origin = self.instr.get(src, {})
                sources = []
                if src in tuples:
                    sources = [tuples[src]]
                elif origin.get("opcode") == "parameter" \
                        and origin["comp"] in fed_by:
                    root = self.roots.get(origin["comp"], "")
                    sources = [tuples.get(fed_by[origin["comp"]]),
                               tuples.get(root)]
                elif origin.get("opcode") == "while":
                    sources = [tuples.get(self.roots.get(origin["body"],
                                                         ""))]
                hit = False
                for elems in sources:
                    if elems and k is not None and k < len(elems):
                        edge(elems[k], n)
                        hit = True
                if hit:
                    continue
            for src in i["operands"]:
                edge(src, n)
        return users

    def _attribute(self) -> dict:
        scope = {}
        for n, i in self.instr.items():
            scope[n] = (self._fusion_scope(n) if i["opcode"] == "fusion"
                        else COLLECTIVE if i["opcode"].startswith(
                            xplane.COLLECTIVE) else i["scope"])
        users = self._edges()
        producers = {}
        for src, dsts in users.items():
            for dst in dsts:
                producers.setdefault(dst, []).append(src)

        def reach(start, graph):
            """The nearest instructions past plumbing."""
            seen, stack, out = {start}, list(graph.get(start, [])), []
            while stack:
                n = stack.pop()
                if n in seen or n not in self.instr:
                    continue
                seen.add(n)
                if self.instr[n]["opcode"] in _PLUMBING:
                    stack.extend(graph.get(n, []))
                else:
                    out.append(n)
            return out

        fused = {i["calls"] for i in self.instr.values() if i["calls"]}
        work = [n for n, i in self.instr.items() if scope[n] is None
                and i["opcode"] not in ("parameter", "tuple", "constant")
                and i["comp"] not in fused]
        for _ in range(8):          # chains of unnamed instructions
            changed = False
            for n in work:
                if scope[n] is not None:
                    continue
                for graph in (users, producers):
                    named = {scope[m] for m in reach(n, graph)
                             if isinstance(scope[m], tuple)}
                    units = {unit_of(s) for s, _ in named}
                    if len(named) == 1:
                        scope[n] = named.pop()
                    elif len(units) == 1:
                        # one layer's weights on their way to its forward,
                        # backward and update: that layer's, no direction
                        scope[n] = (units.pop(), "any")
                    else:
                        continue
                    changed = True
                    break
            if not changed:
                break
        return {n: (s if s is not None else UNSCOPED)
                for n, s in scope.items()}


def programs_by_name(hlo_texts) -> dict:
    out = {}
    for text in hlo_texts:
        program = Program(text)
        if program.name:
            out.setdefault(program.name, []).append(program)
    return out


def live_texts() -> list:
    """Compiled texts of the executables this process holds."""
    import jax

    return [m.to_string()
            for exe in jax.devices()[0].client.live_executables()
            for m in exe.hlo_modules()]


# -- the trace -------------------------------------------------------------------


def _device0(profile):
    """``(plane, ops, executions)`` of the first device: ops as ``(start,
    end, name, execution index or None)``, executions as ``(start, end,
    module event name)``."""
    for plane in profile.planes:
        if not (plane.name.startswith("/device:TPU:")
                and plane.name.split(":")[-1].isdigit()):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
            elif line.name == "XLA Modules":
                modules = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events)
        if not ops:
            continue
        starts = [m[0] for m in modules]
        placed = []
        for s, e, name in ops:
            k = bisect.bisect_right(starts, s) - 1
            inside = k >= 0 and s < modules[k][1]
            placed.append((s, e, name, k if inside else None))
        return plane.name, placed, modules
    # the CPU stand-in: executor threads' hlo_op events
    ops, runs = [], {}
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" not in stats:
                    continue
                module = (f"{stats.get('hlo_module', '')}"
                          f"({stats.get('program_id', 0)})")
                key = (module, stats.get("run_id", 0))
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                run = runs.setdefault(key, [s, e, module])
                run[0], run[1] = min(run[0], s), max(run[1], e)
                ops.append((s, e, ev.name, key))
    if not ops:
        return None, [], []
    order = sorted(runs, key=lambda k: runs[k][0])
    index = {k: i for i, k in enumerate(order)}
    return ("cpu-executor (stand-in, no measurement)",
            [(s, e, n, index[k]) for s, e, n, k in ops],
            [tuple(runs[k]) for k in order])


def _znicz_spans(profile, cats=("train",)):
    """``(start, end, cat:name)`` of the program's spans of ``cats`` in
    the host plane (``name`` up to its first colon, as the ring's labels
    are cut).  The train loop's thread is what the device waits for; a
    writer thread's span around the same moment says nothing about it."""
    spans = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    cat, _, name = ev.name[len(PREFIX):].partition(":")
                    if cat in cats:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      f"{cat}:{name.split(':')[0]}"))
    return spans


def _match(programs, module_name, seen_ops):
    """The program of a module event: by name, and among programs of one
    name (the train and the evaluation step are both ``jit_step``) the
    one whose instructions the execution's events name."""
    candidates = programs.get(module_name.split("(")[0], [])
    if len(candidates) <= 1:
        return candidates[0] if candidates else None
    seen = {(i, o, s) for i, o, _k, s, _c in map(xplane.parse_op, seen_ops)}
    bare = {i for i, _, _ in seen}
    # whole lines where the trace has them (the TPU's events); else the
    # names the execution used that the text has, less those it lacks,
    # and the smaller program among equals (an evaluation step's names
    # are nearly all in the train step's text, not the other way round)
    return max(candidates, key=lambda p: (
        len(seen & p.signature),
        len(bare & p.instr.keys()) - len(bare - p.instr.keys()),
        -len(p.instr)))


def reduce_scopes(path: str, hlo_texts, top: int = 10) -> dict:
    """The three tables of one ``.xplane.pb`` (see the module's text), as
    plain data."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    plane, ops, modules = _device0(profile)
    out = {"path": path, "device": plane, "scope_s": {}, "mixed_s": {},
           "unscoped_ops_s": {}, "executions": {}, "input_executions": 0,
           "busy_s": 0.0, "unscoped_share": 1.0}
    if not ops:
        return out
    programs = programs_by_name(hlo_texts)
    by_exec = {}
    for s, e, name, k in ops:
        by_exec.setdefault(k, []).append(name)
    program_of = {}
    for k, (_s, _e, module) in enumerate(modules):
        if module not in program_of:
            program_of[module] = _match(programs, module,
                                        by_exec.get(k, []))
        program = program_of[module]
        label = module.split("(")[0] + (
            f"/{program.role}" if program is not None and program.role
            else "")
        out["executions"][label] = out["executions"].get(label, 0) + 1
        out["input_executions"] += bool(program and program.has_input)

    def tag(name, k):
        program = program_of.get(modules[k][2]) if k is not None else None
        if program is None:
            return UNSCOPED, ""
        instr = xplane.parse_op(name)[0]
        scope = program.scope.get(instr, UNSCOPED)
        if scope == MIXED:
            return MIXED, program.mixed_units.get(instr, "")
        return scope, ""

    tagged = [(s, e, name, tag(name, k)) for s, e, name, k in ops]
    table, mixed, unnamed = {}, {}, {}
    for name, (scope, detail), t in xplane._self_times(tagged):
        key = scope if isinstance(scope, str) else f"{scope[0]}|{scope[1]}"
        table[key] = table.get(key, 0.0) + t
        if scope == MIXED:
            mixed[detail] = mixed.get(detail, 0.0) + t
        elif scope == UNSCOPED:
            label = xplane.op_label(name)
            unnamed[label] = unnamed.get(label, 0.0) + t
    busy = xplane._union([(s, e) for s, e, _, _ in ops])
    busy_ns = xplane._length(busy)

    def ranked(d, n=None):
        return {k: v / 1e9 for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:n]}

    out.update(
        scope_s=ranked(table), mixed_s=ranked(mixed, top),
        unscoped_ops_s=ranked(unnamed, top), busy_s=busy_ns / 1e9,
        window_s=(busy[-1][1] - busy[0][0]) / 1e9,
        unscoped_share=table.get(UNSCOPED, 0.0) / max(busy_ns, 1e-12),
        programs_unmatched=sorted({m.split("(")[0] for m, p
                                   in program_of.items() if p is None}))
    out.update(_idle_by_span(busy, _znicz_spans(profile), top))
    return out


def _idle_by_span(busy, spans, top: int) -> dict:
    """Idle gaps of device 0 against the program's own spans."""
    w0, w1 = busy[0][0], busy[-1][1]
    near = [sp for sp in spans if sp[1] >= w0 and sp[0] <= w1]
    gaps = sorted(((b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])
                   if b[0] - a[1] >= xplane.MIN_GAP_NS), reverse=True)
    labels = [xplane._span_at(start + dur / 2, near) for dur, start in gaps]
    by_label = {}
    for (dur, _), label in zip(gaps, labels):
        by_label[label] = by_label.get(label, 0.0) + dur
    # idle inside the epoch's tail, by the innermost span: cut each gap
    # at every span boundary inside it.  A span that is in the trace at
    # all is there whole: the profiler keeps none that it saw only one
    # end of (the traced window opens and closes inside a tail).
    whole = {kind: [sp for sp in near if sp[2].split(":")[1] == kind]
             for kind in TAIL_SPANS}
    by_leaf = {}
    for kind, group in whole.items():
        for t0, t1, _ in group:
            inside = [sp for sp in near if sp[0] >= t0 and sp[1] <= t1]
            for dur, start in gaps:
                lo, hi = max(start, t0), min(start + dur, t1)
                if hi <= lo:
                    continue
                cuts = sorted({lo, hi} | {x for sp in inside
                                          for x in sp[:2] if lo < x < hi})
                for a, b in zip(cuts, cuts[1:]):
                    leaf = xplane._span_at((a + b) / 2, inside)
                    leaf = leaf.split(":", 1)[1]
                    by_leaf[leaf] = by_leaf.get(leaf, 0.0) + (
                        (b - a) / len(group))
    dispatch = [e - s for s, e, name in near
                if name.split(":")[1] == "dispatch" and s >= w0]
    return {
        "spans_in_window": len(near),
        "longest_gaps": [[label, dur / 1e9] for (dur, _), label
                         in zip(gaps[:top], labels)],
        "gap_s_by_label": {k: v / 1e9 for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])},
        "tails": len(whole["tail"]), "epoch_hooks": len(whole["epoch_hook"]),
        # in the trace at all, also before the device's first operation
        "tail_spans_in_trace": sum(sp[2].split(":")[1] in TAIL_SPANS
                                   for sp in spans),
        "tail_idle_s_by_leaf": {k: v / 1e9 for k, v in sorted(
            by_leaf.items(), key=lambda kv: -kv[1])},
        "dispatch_spans": len(dispatch),
        "dispatch_ms_median": (statistics.median(dispatch) / 1e6
                               if dispatch else None),
    }


# -- what the readers call -------------------------------------------------------


def of_run(run: dict):
    """The reduction of a traced run, made once: kept in ``run["scopes"]``
    (so it lands in the run's detail file) and printed on an earlier line.
    ``None`` where the run has no trace."""
    if "scopes" in run:
        return run["scopes"]
    path = (run.get("trace") or {}).get("path")
    run["scopes"] = reduction = (reduce_scopes(path, live_texts())
                                 if path else None)
    if reduction:
        print(json.dumps({"phase": "scopes", **{
            k: v for k, v in reduction.items() if k != "path"}}),
              flush=True)
    return reduction


def named(run: dict):
    """``of_run`` for the readers of device time: ``None`` too where more
    than ``MAX_UNSCOPED`` of the busy time carries no name."""
    reduction = of_run(run)
    if not reduction or not reduction["busy_s"] \
            or reduction["unscoped_share"] > MAX_UNSCOPED:
        return None
    return reduction


def scope_seconds(reduction: dict, match) -> float:
    """Seconds under the scopes whose unit ``match`` accepts, every
    direction."""
    return sum(t for key, t in reduction["scope_s"].items()
               if "|" in key and match(unit_of(key.split("|")[0])))
