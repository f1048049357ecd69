"""Partitioned-HLO analysis: collective bytes with while-loop trip counts.

XLA's ``cost_analysis``/naive text scans count a while (lax.scan) body ONCE.
This parser splits the HLO module into computations, finds ``while`` ops,
extracts trip counts from their condition computations (the max integer
constant — lax.scan lowers to ``compare(iter, L)``), and multiplies each
body's collective bytes through the call graph. Shapes in partitioned HLO
are per-device, so totals are per-device bytes on the wire.

Conditionals (``lax.cond`` — e.g. the compacted-/dense-exchange fallback)
execute exactly ONE branch, so summing every branch would overstate wire
traffic. Each conditional contributes the single branch with the LARGEST
total collective bytes — a taken-branch upper bound (``BRANCH_RULE``),
tight whenever one branch dominates (the dense fallback), never the sum.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple


def cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty where the backend
    reports none)."""
    return compiled.cost_analysis() or {}


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# how conditional branches enter the totals (recorded in dryrun artifacts)
BRANCH_RULE = "taken-branch-upper-bound(max)"
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_BLOCK_START = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?.*\{")
_WHILE_RE = re.compile(r"while\(.*?\)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"to_apply=%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"(?:true_computation|false_computation|"
                        r"branch_computations=\{)[^,}]*")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(type_str: str) -> int:
    b = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        b += n * _DTYPE_BYTES[dt]
    return b


def parse_collectives(hlo_text: str) -> Tuple[Dict[str, float],
                                              Dict[str, float]]:
    """Returns (bytes_by_collective, counts_by_collective), per device,
    with while-loop bodies multiplied by their trip counts."""
    # --- split into computations ---
    blocks: Dict[str, list] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{"):
            m = _BLOCK_START.match(s)
            if m:
                cur = m.group(1)
                blocks[cur] = []
                if s.startswith("ENTRY"):
                    entry = cur
                continue
        if s == "}":
            cur = None
            continue
        if cur is not None:
            blocks[cur].append(s)

    # --- per-block direct stats and child edges ---
    direct_b: Dict[str, Dict[str, float]] = {}
    direct_c: Dict[str, Dict[str, float]] = {}
    children: Dict[str, list] = {}
    branches: Dict[str, list] = {}   # per block: conditional branch groups
    trip_of: Dict[str, int] = {}

    for name, lines in blocks.items():
        db = {c: 0.0 for c in COLLECTIVES}
        dc = {c: 0.0 for c in COLLECTIVES}
        ch = []
        br = []
        for s in lines:
            m = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.*)", s)
            if not m:
                continue
            rest = m.group(1)
            for c in COLLECTIVES:
                cm = re.search(rf"^(.*?)\b{c}(-start)?\(", rest)
                if cm:
                    # result type — possibly a tuple "(u32[...], ...)", which
                    # a naive split at the first "(" would read as empty.
                    # Async *-start tuples are (operand alias, result) pairs:
                    # halve so the wire bytes aren't double-counted.
                    b = _shape_bytes(cm.group(1))
                    if cm.group(2) and cm.group(1).lstrip().startswith("("):
                        b /= 2
                    db[c] += b
                    dc[c] += 1
                    break
            if " while(" in rest or rest.startswith("while("):
                bm = _BODY_RE.search(rest)
                cm = _COND_RE.search(rest)
                trip = 1
                if cm and cm.group(1) in blocks:
                    consts = [int(x) for x in _CONST_RE.findall(
                        "\n".join(blocks[cm.group(1)]))]
                    trip = max(consts) if consts else 1
                if bm:
                    ch.append((bm.group(1), max(trip, 1)))
                if cm:
                    ch.append((cm.group(1), max(trip, 1)))
            for cm in _CALL_RE.finditer(rest):
                ch.append((cm.group(1), 1))
            # conditional branches: ONE executes — group them so the totals
            # take the max-bytes branch, not the sum of all branches
            group = [cm.group(1) for cm in re.finditer(
                r"(?:true_computation|false_computation)=%?([\w\.\-]+)",
                rest)]
            for cm in re.finditer(r"branch_computations=\{([^}]*)\}", rest):
                group += [b.strip().lstrip("%")
                          for b in cm.group(1).split(",")]
            if group:
                br.append(group)
        direct_b[name], direct_c[name] = db, dc
        children[name], branches[name] = ch, br

    # --- DFS with memo ---
    memo_b: Dict[str, Dict[str, float]] = {}
    memo_c: Dict[str, Dict[str, float]] = {}

    def total(name, stack=()):
        if name in memo_b:
            return memo_b[name], memo_c[name]
        if name not in direct_b or name in stack:
            z = {c: 0.0 for c in COLLECTIVES}
            return z, dict(z)
        tb = dict(direct_b[name])
        tc = dict(direct_c[name])
        for child, mult in children[name]:
            cb, cc = total(child, stack + (name,))
            for c in COLLECTIVES:
                tb[c] += mult * cb[c]
                tc[c] += mult * cc[c]
        for group in branches[name]:
            totals = [total(b, stack + (name,)) for b in group]
            bb, bc_ = max(totals, key=lambda t: sum(t[0].values()))
            for c in COLLECTIVES:
                tb[c] += bb[c]
                tc[c] += bc_[c]
        memo_b[name], memo_c[name] = tb, tc
        return tb, tc

    if entry is None:
        z = {c: 0.0 for c in COLLECTIVES}
        return z, dict(z)
    return total(entry)
