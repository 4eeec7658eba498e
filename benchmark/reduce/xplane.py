"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but jax.  A device
plane (``/device:TPU:<n>``) carries a line ``XLA Ops`` with one event per
executed HLO operation; control operations (``while``, ``call``,
``conditional``) appear as long events that contain their bodies' events,
so every duration here is *self* time: an event's duration minus the part
its children cover.  Busy time is the union of the events' intervals, idle
share is 1 minus busy over the traced window, and the longest idle gaps are
labelled by what the host was doing: the benchmark's own
``TraceAnnotation`` spans (``bench:*``) and, through a clock offset taken
at a sync annotation, the program's ``TraceRing`` spans.

On the CPU backend there is no device plane; the executor threads'
``hlo_op`` events stand in for one so that the rehearsal and the tests run
the same code.  Such a reduction measures nothing.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute", "collective-broadcast")
CONTROL = ("while", "call", "conditional")
MXU_OPS = ("convolution", "dot")
MIN_GAP_NS = 10_000         # 10 us: shorter gaps are not the host's
BENCH_PREFIX = "bench:"
SYNC_NAME = "bench:sync"


def newest_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


_LAYOUT = re.compile(r"\{[^{}]*\}")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}",
                          re.S | re.M)


def parse_op(name: str):
    """``(instruction, opcode, fusion kind, result shape, called
    computation)`` of an event's name.  The TPU trace names an operation
    by its whole HLO line, ``%fusion.12 = bf16[128,96]{1,0:T(8,128)}
    fusion(...), kind=kOutput, calls=%fused_computation.3``; other
    backends give the bare instruction name, whose kind without its number
    then stands for the opcode."""
    head, sep, rest = name.partition(" = ")
    instr = head.strip().removeprefix("ROOT ").lstrip("%")
    if not sep:
        base, _, tail = instr.rpartition(".")
        return (instr, (base if base and tail.isdigit() else instr), "", "",
                "")
    if rest.startswith("("):            # a tuple shape: match the bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, after = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, after = rest.partition(" ")
    opcode, _, args = after.strip().partition("(")
    kind = re.search(r"kind=(\w+)", args)
    calls = _CALLS.search(args)
    return (instr, opcode.strip(), kind.group(1) if kind else "",
            _LAYOUT.sub("", shape), calls.group(1) if calls else "")


def op_label(name: str, width: int = 96) -> str:
    """A short, stable name for the breakdown: instruction, opcode and
    result shape without layouts."""
    instr, opcode, kind, shape, _ = parse_op(name)
    if not shape:
        return instr[:width]
    return f"{instr} {opcode}{'/' + kind if kind else ''} {shape}"[:width]


def fusion_table(hlo_texts) -> dict:
    """``{(instruction, called computation): holds a convolution or dot}``
    of every fusion in the compiled modules' texts (``as_text()`` of an
    executable, ``to_string()`` of its HLO module).  A fusion's event in
    the trace carries both names, so what a fusion computes is read from
    the program that ran and not guessed from the event.  For backends
    whose events carry the instruction's name alone, ``(instruction, "")``
    gives the same answer, and is there for a plain convolution or dot
    too."""
    table = {}
    for text in hlo_texts:
        bodies = {m.group(1): m.group(2)
                  for m in _COMPUTATION.finditer(text)}
        for line in text.splitlines():
            if " = " not in line:
                continue
            instr, opcode, _, _, calls = parse_op(line.strip())
            if opcode == "fusion":
                body = bodies.get(calls, "")
                table[(instr, calls)] = table[(instr, "")] = any(
                    f" {op}(" in body for op in MXU_OPS)
            elif opcode in MXU_OPS:
                table[(instr, "")] = True
    return table


def category(name: str, fusions: dict) -> str:
    """``collective``, ``control``, ``mxu``, ``other`` or ``unknown``.

    ``mxu`` is every operation rooted in a convolution or a matrix
    product: the opcodes themselves and the fusions whose computation
    holds one (``fusion_table``) — XLA fuses an epilogue (bias,
    activation, the optimizer's update of a weight gradient) into the
    output of a convolution or dot, so the epilogue's time is inside
    ``mxu``.  A fusion that no compiled text lists is ``unknown``, and
    the readers that need the split then report nothing."""
    instr, opcode, _, _, calls = parse_op(name)
    op = opcode.lower()
    if op.startswith(COLLECTIVE):
        return "collective"
    if op in CONTROL:
        return "control"
    holds = fusions.get((instr, calls))
    if holds is None and op.endswith("fusion"):
        return "unknown"
    return "mxu" if holds or op in MXU_OPS else "other"


def _union(intervals):
    """Merged, sorted ``[start, end]`` lists of possibly nested or
    overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def _subtract(a, b) -> float:
    """Length of the part of merged ``a`` that merged ``b`` does not
    cover."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _self_times(events):
    """``events``: ``(start, end, name, cat)`` of one line.  Yields
    ``(name, cat, self_ns)`` with children's time taken out of their
    parents'."""
    out = []
    stack = []              # [end, name, cat, start, covered]
    for s, e, name, cat in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            end, n, c, st, cov = stack.pop()
            out.append((n, c, max(end - st - cov, 0.0)))
        if stack:
            stack[-1][4] += min(e, stack[-1][0]) - s
        stack.append([e, name, cat, s, 0.0])
    while stack:
        end, n, c, st, cov = stack.pop()
        out.append((n, c, max(end - st - cov, 0.0)))
    return out


def _device_lines(profile):
    """``(plane name, events)`` per device, events as ``(start, end,
    name)``."""
    devices, cpu = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") \
                and plane.name.split(":")[-1].isdigit():
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append((plane.name, [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                cpu.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events
                           if "hlo_op" in dict(ev.stats))
    if not devices and cpu:
        devices.append(("cpu-executor (stand-in, no measurement)", cpu))
    return devices


def _host_spans(profile):
    """The benchmark's own annotations: ``(start, end, name)``."""
    spans = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(BENCH_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _span_at(mid: float, spans) -> str:
    """Innermost span active at ``mid``; ``unattributed`` if none."""
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "unattributed"


def reduce_trace(path: str, ring_events=(), sync_perf_s=None,
                 hlo_texts=(), top: int = 10) -> dict:
    """The whole reduction of one ``.xplane.pb``.  ``hlo_texts`` are the
    compiled texts of the programs that ran (``fusion_table``).

    ``ring_events`` are ``TraceRing`` tuples ``(cat, name, ts_us, dur_us,
    tid, args)`` on ``perf_counter``; ``sync_perf_s`` is the
    ``perf_counter`` reading taken inside the ``bench:sync`` annotation,
    which puts them on the trace's clock."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    bench = _host_spans(profile)
    spans = [s for s in bench if s[2] != SYNC_NAME]
    sync = [s for s in bench if s[2] == SYNC_NAME]
    offset_known = bool(sync) and sync_perf_s is not None
    if offset_known:
        offset = (sync[0][0] + sync[0][1]) / 2 - sync_perf_s * 1e9
        for cat, name, ts_us, dur_us, _tid, _args in ring_events:
            start = ts_us * 1e3 + offset
            spans.append((start, start + dur_us * 1e3,
                          f"{cat}:{name.split(':')[0]}"))
    devices = []
    fusions = fusion_table(hlo_texts)
    for plane_name, events in _device_lines(profile):
        if not events:
            continue
        tagged = [(s, e, name, category(name, fusions))
                  for s, e, name in events]
        w0 = min(t[0] for t in tagged)
        w1 = max(t[1] for t in tagged)
        busy = _union([(s, e) for s, e, _, _ in tagged])
        ops, cats = {}, {}
        for n, c, t in _self_times(tagged):
            n = op_label(n)
            ops[n] = ops.get(n, 0.0) + t
            cats[c] = cats.get(c, 0.0) + t
        coll = _union([(s, e) for s, e, _, c in tagged
                       if c == "collective"])
        compute = _union([(s, e) for s, e, _, c in tagged
                          if c in ("mxu", "other", "unknown")])
        gaps = sorted(((b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])),
                      reverse=True)
        # only what the host can have caused is looked up: the thousands
        # of sub-microsecond gaps between a program's own operations are
        # summed under one name
        near = [sp for sp in spans if sp[1] >= w0 and sp[0] <= w1]
        by_label = {}
        for dur, start in gaps:
            label = (_span_at(start + dur / 2, near) if dur >= MIN_GAP_NS
                     else "between operations")
            by_label[label] = by_label.get(label, 0.0) + dur
        devices.append({
            "plane": plane_name,
            "window_s": (w1 - w0) / 1e9,
            "busy_s": _length(busy) / 1e9,
            "n_events": len(tagged),
            "ops_s": {n: t / 1e9 for n, t in sorted(
                ops.items(), key=lambda kv: -kv[1])},
            "category_s": {c: t / 1e9 for c, t in cats.items()},
            "collective_s": _length(coll) / 1e9,
            "collective_exposed_s": _subtract(coll, compute) / 1e9,
            "longest_gaps": [[_span_at(start + dur / 2, near), dur / 1e9]
                             for dur, start in gaps[:top]],
            "gap_s_by_label": {k: v / 1e9 for k, v in sorted(
                by_label.items(), key=lambda kv: -kv[1])},
        })
    out = {"path": path, "devices": devices,
           "clock_offset_known": offset_known,
           "bench_spans": len(spans)}
    if devices:
        n = len(devices)
        out["busy_s"] = sum(d["busy_s"] for d in devices) / n
        out["window_s"] = sum(d["window_s"] for d in devices) / n
        out["idle_share_device0"] = 1 - devices[0]["busy_s"] / max(
            devices[0]["window_s"], 1e-12)
        out["idle_share_worst"] = max(
            1 - d["busy_s"] / max(d["window_s"], 1e-12) for d in devices)
    return out


def breakdown(reduction: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's last line: the device
    operations that took most time (device 0, self time) and the longest
    idle gaps by what the host was doing."""
    if not reduction.get("devices"):
        return {"device_ops": [], "idle_gaps": []}
    d0 = reduction["devices"][0]
    return {"device_ops": [[n, t] for n, t in
                           list(d0["ops_s"].items())[:top]],
            "idle_gaps": d0["longest_gaps"][:top]}
