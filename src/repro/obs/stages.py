"""Stage names of the train step's sparse engine and towers (DESIGN.md §9).

Each stage is one ``jax.named_scope``. The scope lands in the ``op_name``
metadata of every HLO instruction the stage lowers to, and survives
fusion into the optimized HLO, so a profiler trace of the compiled step
can be read by stage instead of by opcode or by a fusion's number. Scopes
are metadata only: they add no device work.

Stages do not nest: each wraps code that calls no other stage. Ops that
``jax.grad`` generates keep their forward stage inside
``transpose(jvp(...))``; ``stage_of`` names the stage either way and
``backward_of`` tells the two directions apart.
"""
from __future__ import annotations

import re

import jax

STAGES = (
    "recis.ids.hash",             # feature transforms + salted engine ids
    "recis.exchange.bucket",      # requester dedupe + owner bucketing
    "recis.exchange.all_to_all",  # the ids out, the rows back
    "recis.exchange.owner_merge", # owner-side merge + dedupe of received ids
    "recis.idmap.probe",          # IDMap pass 1: find existing keys
    "recis.idmap.claim",          # IDMap pass 2: claim empty slots
    "recis.idmap.alloc",          # row allocation, offsets / last_use writes
    "recis.blocks.init_rows",     # initialise the rows of new ids
    "recis.blocks.gather",        # owner rows read from Blocks
    "recis.embed.route",          # owner rows → per-value rows (and back)
    "recis.embed.pool",           # per-feature pooling
    "recis.tower",                # the dense model's loss
    "recis.dense.adamw",          # dense optimizer
    "recis.sparse.adam",          # row-wise SparseAdam on the touched rows
)

_STAGE_RE = re.compile(r"recis(?:\.[A-Za-z0-9_]+)+")


def stage(name: str):
    """``jax.named_scope(name)`` for a stage of ``STAGES``."""
    if name not in STAGES:
        raise ValueError(f"{name!r} is not a stage; known: {STAGES}")
    return jax.named_scope(name)


def stage_of(op_name: str) -> str | None:
    """The innermost ``recis.`` segment of an HLO ``op_name``, or None."""
    found = _STAGE_RE.findall(op_name)
    return found[-1] if found else None


def backward_of(op_name: str) -> bool:
    """True for an op ``jax.grad`` generated (its path is transposed)."""
    return "transpose(" in op_name


_HEAD = re.compile(r"\s*(?:ENTRY\s+)?%(\S+) .*\{\s*$")
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def op_names(hlo_text: str) -> dict[str, str]:
    """{instruction: op_name} of every instruction in an optimized HLO
    module's text (``Compiled.as_text()``), as the stages read it.

    The compiler makes some instructions anew and without metadata: it
    rewrites a scatter on two index arrays into one on a linear index, say.
    Such an instruction, where it has no stage of its own, takes the op_name
    of its fusion: for a fusion, the computation it calls; inside a fused
    computation, that computation. A fused computation's op_name is that
    of its last instruction with a stage (the root is printed last).
    """
    comps: dict[str, list[tuple[str, str, str | None]]] = {}
    fused: set[str] = set()
    body = None
    for line in hlo_text.splitlines():
        head = _HEAD.match(line)
        if head and " = " not in line:
            body = comps.setdefault(head.group(1), [])
            continue
        m = _INSTR.match(line)
        if body is None or m is None:
            continue
        own = _OP_NAME.search(line)
        calls = _CALLS.search(line) if " fusion(" in line else None
        if calls:
            fused.add(calls.group(1))
        body.append((m.group(1), own.group(1) if own else "",
                     calls.group(1) if calls else None))

    comp_name: dict[str, str] = {}

    def of_comp(comp: str) -> str:
        if comp not in comp_name:
            found = ""
            for _, own, calls in reversed(comps.get(comp, [])):
                found = own if stage_of(own) else of_comp(calls) if calls else ""
                if found:
                    break
            comp_name[comp] = found
        return comp_name[comp]

    out = {}
    for comp, body in comps.items():
        for name, own, calls in body:
            if not stage_of(own):
                own = (calls and of_comp(calls)) or (comp in fused and of_comp(comp)) or own
            out[name] = own
    return out
