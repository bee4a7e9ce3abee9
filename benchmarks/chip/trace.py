"""Reduction of one profiler trace (``.xplane.pb``) to the benchmark's numbers.

The window is the host annotation ``bench/window``, which the harness opens
around the traced steps. Inside it, per device plane (``/device:TPU:<n>``):

* busy time: the union of the intervals of the events on the plane's op
  line (``XLA Ops``), clipped to the window;
* time by op kind: each op's own device time (what its nested ops, such
  as a while loop's body, do not cover), summed by ``op_kind``. The trace
  names an op by its HLO instruction text (``%fusion.3464 = (u32[4915200],
  ...) fusion(...), kind=kCustom, calls=%fused_computation.208``), so the
  kind is its opcode, and for a fusion the opcode its computation is rooted
  in, read from the program's optimized HLO (``fusion_roots``): XLA on the
  TPU puts most scatters and gathers in such fusions;
* idle gaps, on the first device plane: the complement of its busy union in
  the window, each named by the Trainer phase (host annotation ``repro/<phase>``) that overlaps it
  most, or ``host`` where none does.

Every device number is averaged over the device planes. Nothing here knows a
model: later metrics read the summary this returns.
"""
from __future__ import annotations

import collections
import pathlib
import re

WINDOW = "bench/window"
PHASE_PREFIX = "repro/"
OP_LINES = ("XLA Ops",)

KINDS = ("sort", "scatter", "gather", "all-to-all")
_PASS_THROUGH = ("bitcast", "convert", "copy", "reshape", "transpose", "get-tuple-element")


def opcode(text: str) -> tuple[str, str]:
    """(name, opcode) of one HLO instruction as the trace names it:
    ``%<name> = <shape> <opcode>(<operands>), ...``."""
    m = re.match(r"\s*(?:ROOT\s+)?%(\S+) = ", text)
    if m is None:
        return text, ""
    rest = text[m.end():]
    if rest.startswith("("):  # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return m.group(1), rest.lstrip().split("(", 1)[0]


def fusion_roots(hlo_text: str) -> dict[str, str]:
    """{fused computation: the opcode it is rooted in} from an executable's
    optimized HLO text. A tuple or a pass-through root is followed to the
    instruction that makes the data, and a fusion root to the computation
    it calls."""
    roots, calls, comp, ops = {}, {}, None, {}
    for line in hlo_text.splitlines():
        head = re.match(r"\s*(?:ENTRY\s+)?%(\S+) .*\{\s*$", line)
        if head and " = " not in line:
            comp, ops = head.group(1), {}
            continue
        if comp is None:
            continue
        if line.strip() == "}":
            comp = None
            continue
        name, op = opcode(line)
        operands = re.findall(r"%([\w.\-]+)", line.split("(", 1)[1] if "(" in line else "")
        ops[name] = (op, operands, line)
        if line.lstrip().startswith("ROOT"):
            seen, todo, kind, text = set(), [name], op, line
            while todo:
                n = todo.pop(0)
                if n in seen or n not in ops:
                    continue
                seen.add(n)
                kind, args, text = ops[n]
                if kind == "tuple" or kind in _PASS_THROUGH:
                    todo.extend(args)
                    continue
                break
            roots[comp] = kind
            called = re.search(r"calls=%([\w.\-]+)", text)
            if kind == "fusion" and called:
                calls[comp] = called.group(1)
    for comp in calls:
        seen = {comp}
        while roots.get(comp) == "fusion" and calls.get(comp) and calls[comp] not in seen:
            seen.add(calls[comp])
            roots[comp] = roots.get(calls[comp], "fusion")
            calls[comp] = calls.get(calls[comp])
    return roots


def op_kind(text: str, roots: dict[str, str] | None = None) -> str:
    """``sort``, ``scatter``, ``gather``, ``all-to-all`` or ``other``: the
    op's opcode, or for a fusion the opcode it is rooted in."""
    _, op = opcode(text)
    if op == "fusion" and roots:
        m = re.search(r"calls=%([\w.\-]+)", text)
        op = roots.get(m.group(1), op) if m else op
    op = op.removesuffix("-start").removesuffix("-done")
    return op if op in KINDS else "other"


def _exclusive(events):
    """Self time of each (start, end, text): each instant goes to the
    innermost op running then (a while loop's body ops are its own)."""
    bounds = []
    for i, (s, e, _) in enumerate(events):
        if e > s:
            bounds.append((s, 1, i))
            bounds.append((e, 0, i))
    bounds.sort()
    own = [0.0] * len(events)
    stack, prev = [], None
    for t, is_start, i in bounds:
        if stack and prev is not None:
            own[stack[-1]] += t - prev
        prev = t
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
    return own


def find_xplane(directory) -> pathlib.Path | None:
    found = sorted(pathlib.Path(directory).glob("**/*.xplane.pb"))
    return found[-1] if found else None


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(path, roots: dict[str, str] | None = None, top: int = 10) -> dict | None:
    """Summary of the trace at ``path``; None when it holds no window or no
    device op in it. ``roots``: ``fusion_roots`` of the traced program."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window, phases, devices = None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events)
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(PHASE_PREFIX):
                        phases.append((e.start_ns, e.start_ns + e.duration_ns,
                                       e.name[len(PHASE_PREFIX):]))
    if window is None or not devices:
        return None
    w0, w1 = window
    n = len(devices)
    busy = 0.0
    kinds: dict[str, float] = collections.defaultdict(float)
    by_op: dict[str, float] = collections.defaultdict(float)
    gaps = []
    for i, ops in enumerate(devices):
        inside = [(max(s, w0), min(e, w1), name) for s, e, name in ops if e > w0 and s < w1]
        for (_, _, name), own in zip(inside, _exclusive(inside)):
            kinds[op_kind(name, roots)] += own / n
            by_op[_short(name)] += own / n
        merged = _union((s, e) for s, e, _ in inside)
        busy += sum(e - s for s, e in merged) / n
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(g1 - g0, g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]
    named = []
    for dur, g0, g1 in sorted(gaps, reverse=True)[:top]:
        best = max(phases, key=lambda p: _overlap(g0, g1, p[0], p[1]), default=None)
        label = best[2] if best and _overlap(g0, g1, best[0], best[1]) > 0 else "host"
        named.append([label, dur * 1e-9])
    ops_top = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * 1e-9,
        "kind_s": {k: v * 1e-9 for k, v in kinds.items()},
        "device_ops": [[name, ns * 1e-9] for name, ns in ops_top],
        "idle_gaps": named,
    }


def _short(text: str) -> str:
    """An op's name and opcode (and a fusion's computation) for the
    breakdown."""
    name, op = opcode(text)
    m = re.search(r"calls=%([\w.\-]+)", text)
    return f"{name} {op}" + (f" {m.group(1)}" if m else "")


def describe(path, per_line: int = 8) -> str:
    """Planes, lines and a few events with their stats: for reading a trace
    by hand before trusting ``op_kind``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            names = collections.Counter(e.name for e in events)
            out.append(f"    top names: {names.most_common(40)}")
            for e in events[:per_line]:
                stats = {k: v for k, v in e.stats} if hasattr(e, "stats") else {}
                out.append(f"    {e.name} start={e.start_ns} dur={e.duration_ns} {stats}")
    return "\n".join(out)
