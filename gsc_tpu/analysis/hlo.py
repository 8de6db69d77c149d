"""Compiled-HLO structure metrics — the op-count perf proxy.

The substep is op-COUNT bound (BENCH_NOTES round-5 roofline: ~60 small
fusions at ~30 µs apiece, ~100x above the HBM roof), so the number of
fusion computations in the compiled executable is the cheapest faithful
proxy for its per-call overhead — countable on any backend, no chip
window needed.  It exists as a GATE because bit-exactness alone is not
enough: the rejected round-5 scatter-merge was bit-exact yet REGRESSED
281 -> 294 fusions and lost throughput; a fusion-count check would have
rejected it before the chip ever saw it.

Consumers: the cost ledger (``obs/perf.py``: ``fusions`` and, per
``jax.named_scope`` layer, ``scopes`` and the operation-to-scope map
``op_map`` that ``obs.trace`` joins a device trace through) and the
tier-1 fusion-budget regression test (``tests/test_engine.py``), which
pins the compiled flagship-interval ``engine.apply`` count on the CPU
backend.

Stdlib-only on purpose (the gsc-lint convention for analysis/): the
argument is an already-compiled jax ``Compiled`` object (or its
``as_text()`` dump) — this module never imports jax.
"""
from __future__ import annotations

import collections
import re
import zlib
from typing import List, NamedTuple

__all__ = ["collective_stats", "count_fusions", "count_ops", "hlo_text",
           "instruction_head", "module_name", "op_histogram", "op_scopes",
           "scope_map", "scope_stats"]


def hlo_text(compiled_or_text) -> str:
    """Post-optimization HLO text of a ``jax`` ``Compiled`` object (the
    result of ``jit(f).lower(*args).compile()``); strings pass through."""
    if isinstance(compiled_or_text, str):
        return compiled_or_text
    return compiled_or_text.as_text()


def count_fusions(compiled_or_text) -> int:
    """Number of fusion computations in the compiled executable.

    Counts ``" fusion("`` instruction sites in the post-optimization HLO
    — fusion *calls*, including those inside while-loop bodies (an
    ``lax.scan`` body compiles once, so a per-substep op costs one count,
    not one per iteration).  Comparisons are only meaningful at a fixed
    jaxlib version and backend; the budget test re-measures both sides of
    its assertion in the same process for exactly that reason.
    """
    return hlo_text(compiled_or_text).count(" fusion(")


def count_ops(compiled_or_text, op: str) -> int:
    """Occurrences of an HLO op (e.g. ``"while"``, ``"gather"``,
    ``"scatter"``, ``"dot"``) in the compiled executable — the drill-down
    companion to :func:`count_fusions` (a CPU scatter lowers to a serial
    ``while``)."""
    return hlo_text(compiled_or_text).count(f" {op}(")


def op_histogram(compiled_or_text, ops) -> dict:
    """``{op: count}`` over a list of HLO op names, one text pass per op —
    the batch form the cost ledger (obs.perf) stores per entry point."""
    text = hlo_text(compiled_or_text)
    return {op: text.count(f" {op}(") for op in ops}


#: the cross-device movers a partitioned program can contain — the
#: interconnect cost the `tp` rulebook spends bit-equality to reduce.
#: Async forms (``all-reduce-start``/``-done``) count as ONE op on the
#: ``-start`` side (the ``-done`` is the same transfer completing).
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# every `dtype[dims]` occurrence in an HLO result type, tuple results
# included: `(f32[4,8]{1,0}, f32[4]{0})`
_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*|pred)\[([0-9,]*)\]")


def _shape_bytes(type_text: str, largest_only: bool = False) -> int:
    """Payload bytes of an HLO result-type string: the sum over tuple
    elements, or with ``largest_only`` just the biggest one — async
    ``-start`` forms return ``(operand, result)`` tuples, where summing
    would double-count the transfer (the result is the payload; for
    all-gather it is the larger element, for all-reduce both are
    equal)."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(type_text):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        sizes.append(n * size)
    if not sizes:
        return 0
    return max(sizes) if largest_only else sum(sizes)


def collective_stats(compiled_or_text, ops=COLLECTIVE_OPS) -> dict:
    """Per-collective count + payload bytes of a compiled executable:
    ``{"ops": {op: {"count", "bytes"}}, "count": total, "bytes": total}``.

    Bytes are summed over each collective instruction's RESULT shape
    (the text between ``=`` and the op name — operand shapes inside the
    parens never match), so an ``all-gather`` counts its gathered output
    and an ``all-reduce`` its reduced tensor.  This is a per-call
    *payload* figure, not wire traffic (a ring all-reduce moves
    ~2x(n-1)/n of it per hop) — stable across backends, which is what a
    tp-vs-sharded interconnect comparison needs.  Ops inside while-loop
    bodies count once per program, same convention as
    :func:`count_fusions`."""
    text = hlo_text(compiled_or_text)
    per_op = {op: {"count": 0, "bytes": 0} for op in ops}
    for line in text.splitlines():
        # `head` holds the instruction name only; the result type leads
        # the right-hand side, before the op token
        head, eq, rhs = line.partition("=")
        if not eq:
            continue
        for op in ops:
            idx, is_start = -1, False
            for token, start in ((f" {op}(", False),
                                 (f" {op}-start(", True)):
                idx = rhs.find(token)
                if idx >= 0:
                    is_start = start
                    break
            if idx < 0:
                continue
            rec = per_op[op]
            rec["count"] += 1
            rec["bytes"] += _shape_bytes(rhs[:idx],
                                         largest_only=is_start)
            break
    present = {op: rec for op, rec in per_op.items() if rec["count"]}
    return {"ops": present,
            "count": sum(r["count"] for r in present.values()),
            "bytes": sum(r["bytes"] for r in present.values())}


# ------------------------------------------------------- operations by scope
#: instructions that are no device operation of their own: values the
#: compiler threads through, and the control flow whose bodies are walked
_NOT_AN_OP = frozenset(("parameter", "constant", "tuple",
                        "get-tuple-element", "bitcast",
                        "while", "call", "conditional"))
_COPY_OPS = ("copy", "copy-start")
# `[ENTRY ]%name (params) -> type {`
_COMPUTATION_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# computations an instruction RUNS (a fusion's `calls=` and a reducer's
# `to_apply=` are part of their instruction, not operations of their own)
_RUNS_RE = re.compile(r"(?:body|condition|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_WORD_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
# `jit(name)` on a path is a function's name, never a scope
_JIT_NAME_RE = re.compile(r"p?jit\([^()]*\)")
_SCALAR_RE = re.compile(r"[a-z0-9]+\[\]")
#: operations kept per scope path to count its executions by
ANCHORS = 4
_INSTR_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


class _Instr(NamedTuple):
    name: str
    opcode: str
    type_text: str
    path: List[str]        # scope names on its own op_name, outermost first
    operands: List[str]
    named: bool            # carries an op_name at all
    runs: List[str]        # computations a control-flow instruction runs
    body: List[str]        # a ``while``'s body


def _split_instruction(line: str):
    """``(result type text, opcode, rest)`` of an HLO instruction line, or
    None.  The result type is one token, or a parenthesised tuple."""
    _, eq, rhs = line.partition(" = ")
    if not eq:
        return None
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        type_text, rest = rhs[:i + 1], rhs[i + 1:].lstrip()
    else:
        type_text, _, rest = rhs.partition(" ")
    opcode, paren, rest = rest.partition("(")
    if not paren or not re.fullmatch(r"[a-z][a-z0-9\-]*", opcode):
        return None
    return type_text, opcode, rest


def _walk_ops(text: str, scopes):
    """Every device operation of an HLO module text as ``(instruction
    name, opcode, result type text, scope path, own, computation, loop
    body)``: ``path`` the names of ``scopes`` the operation lies under,
    outermost first, ``own`` whether its own ``op_name`` gave them (else
    inherited), the computation it stands in and whether that is a
    ``while`` body (each of its operations runs once per iteration); in
    the text's order within each computation, the schedule's order in a
    scheduled module."""
    known = frozenset(scopes)
    instructions = {}          # computation -> [_Instr]
    entry = current = None
    for line in text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head:
            current = head.group(2)
            instructions[current] = []
            if head.group(1):
                entry = current
            continue
        parts = _split_instruction(line) if current else None
        if parts is None:
            continue
        type_text, opcode, rest = parts
        found = _OP_NAME_RE.search(rest)
        path = [w for part in (found.group(1) if found else "").split("/")
                for w in _WORD_RE.findall(_JIT_NAME_RE.sub("", part))
                if w in known]
        runs = []
        if opcode in ("while", "conditional"):
            runs = _RUNS_RE.findall(rest)
            for group in _BRANCHES_RE.findall(rest):
                runs += [b.strip().lstrip("%")
                         for b in group.split(",") if b.strip()]
        elif opcode == "call":
            runs = _TO_APPLY_RE.findall(rest)
        instructions[current].append(_Instr(
            _INSTR_NAME_RE.match(line).group(1), opcode, type_text, path,
            _OPERAND_RE.findall(rest[:rest.find(")") + 1]), bool(found),
            runs, _BODY_RE.findall(rest) if opcode == "while" else []))
    bodies = {b for body in instructions.values() for i in body
              if i.opcode == "while" for b in i.body}
    seen, todo = set(), [(entry, [])] if entry else []
    while todo:
        comp, caller_path = todo.pop()
        if comp in seen or comp not in instructions:
            continue
        seen.add(comp)
        body = instructions[comp]
        inherited = _inherit_paths(body)
        # what is left without a path: the path more than half of the
        # body's scoped instructions share (a loop body written under one
        # scope), else that of the instruction that runs the body
        own_paths = collections.Counter(tuple(i.path) for i in body
                                        if i.path)
        common, n = (own_paths.most_common(1) or [((), 0)])[0]
        rest = list(common) if 2 * n > sum(own_paths.values()) \
            else caller_path
        for i in body:
            path = i.path or inherited.get(i.name) \
                or ([] if i.named else rest)
            todo += [(c, path) for c in i.runs]
            if i.opcode not in _NOT_AN_OP:
                yield (i.name, i.opcode, i.type_text, path, bool(i.path),
                       comp, comp in bodies)


def scope_stats(compiled_or_text, scopes) -> dict:
    """Device operations of a compiled program by ``jax.named_scope``.

    Walks the post-optimisation HLO text once.  An *operation* is an
    instruction of a computation the device steps through — the entry,
    ``while`` bodies and conditions, ``call`` and ``conditional`` targets —
    and not one inside a fused computation or a reducer; ``parameter``,
    ``constant``, ``tuple``, ``get-tuple-element``, ``bitcast`` and the
    control-flow instructions themselves are not operations.  Each is
    counted once per text occurrence (a scan body once, not once per
    iteration), the convention of :func:`count_fusions`.

    An operation's scope is the innermost name of ``scopes`` on its
    ``op_name`` path (a fusion carries its root's path; autodiff wraps a
    name as ``transpose(jvp(name))`` and it still counts; ``jit(name)`` is
    a function's name and does not).  What the compiler inserts carries
    no path at all — layout copies, the ``copy-start``/``copy-done`` and
    ``slice-start``/``slice-done`` pairs of its prefetches, buffer
    allocations: such an instruction is put down to the scoped
    instruction it feeds, else to the one that feeds it, within its
    computation (through tuples, as a loop's operands are), else to the
    path that more than half of its computation's scoped instructions
    share (a loop body written under one scope), else to the control-flow
    instruction that runs its computation (a loop the compiler itself
    made of a scatter or a sort), and counted under ``inherited`` as
    well.  Whatever is left is ``unscoped``.

    Returns ``{scope: {"ops", "fusions", "copies", "out_bytes",
    "inherited", "ops_incl"}}`` for every scope and ``unscoped``:
    ``copies`` are ``copy``/``copy-start``, ``out_bytes`` the result
    bytes, and ``ops_incl`` the operations whose path passes through the
    scope at any depth, so a layer can be read with what is nested in
    it."""
    return scope_map(compiled_or_text, scopes)["stats"]


def scope_map(compiled_or_text, scopes) -> dict:
    """:func:`scope_stats` and the map from each operation to its scope
    path, from one walk of the compiled text (the rules of
    :func:`scope_stats`, ``inherited`` operations where it puts them).

    Returns ``{"stats", "paths", "anchors", "signatures"}``: ``stats`` is
    :func:`scope_stats`'s result; ``paths`` maps each scope path (the
    names outermost first, joined by ``/``; ``unscoped`` for none) to its
    operations' instruction names, the join key of a device trace's
    events; ``anchors`` maps each path to the operations whose
    occurrences count its executions: the path's own operations in the
    ``while`` body that holds most of them (else in the computation that
    does), up to :data:`ANCHORS` of them in the text's order and none with
    a scalar result (a chip's scalar unit runs those, and its trace does
    not record them), so each runs once per iteration of the loop that
    carries the layer — a control step for ``rollout_step``, a substep
    for ``sim_substep``, a gradient step for ``learn_burst``'s own
    operations; ``signatures`` maps each operation
    to :func:`instruction_head`'s check of its result type and opcode,
    which a trace's event name shows beside the instruction's name."""
    scopes = tuple(scopes)
    stats = {name: {"ops": 0, "fusions": 0, "copies": 0, "out_bytes": 0,
                    "inherited": 0, "ops_incl": 0}
             for name in scopes + ("unscoped",)}
    paths: dict = {}
    signatures: dict = {}
    homes: dict = {}       # path -> computation -> [loop body, count, ops]
    for name, opcode, type_text, path, own, comp, body in _walk_ops(
            hlo_text(compiled_or_text), scopes):
        rec = stats[path[-1] if path else "unscoped"]
        rec["ops"] += 1
        rec["fusions"] += opcode == "fusion"
        rec["copies"] += opcode in _COPY_OPS
        rec["out_bytes"] += _shape_bytes(type_text)
        rec["inherited"] += bool(path) and not own
        for scope in set(path) or ("unscoped",):
            stats[scope]["ops_incl"] += 1
        key = "/".join(path) or "unscoped"
        paths.setdefault(key, []).append(name)
        signatures[name] = _signature(type_text, opcode)
        if path:
            home = homes.setdefault(key, {}).setdefault(comp, [body, 0, []])
            home[1] += 1
            if len(home[2]) < ANCHORS and not _SCALAR_RE.match(type_text):
                home[2].append(name)
    anchors = {}
    for key, by_comp in homes.items():
        ops = max(by_comp.values(), key=lambda h: h[:2])[2]
        if ops:
            anchors[key] = ops
    return {"stats": stats, "paths": paths, "anchors": anchors,
            "signatures": signatures}


def _signature(type_text: str, opcode: str) -> int:
    return zlib.crc32(f"{type_text} {opcode}".encode())


def instruction_head(line: str):
    """``(instruction name, signature)`` of an HLO instruction line or of
    a device trace's event, which is named by the instruction's text
    (``%fusion.8 = f32[8]{0} fusion(...)``); the signature is a CRC-32 of
    the result type and the opcode.  None for a line that is no
    instruction (a CPU trace's events carry the bare name)."""
    name = _INSTR_NAME_RE.match(line)
    parts = _split_instruction(line) if name else None
    if parts is None:
        return None
    return name.group(1), _signature(parts[0], parts[1])


def op_scopes(compiled_or_text, scopes) -> dict:
    """``{instruction name: innermost scope}`` (``unscoped`` where none)
    for every device operation: a view of :func:`scope_map`'s ``paths``,
    the join from a device trace's events to the program's layers."""
    return {name: key.rsplit("/", 1)[-1]
            for key, names in scope_map(compiled_or_text,
                                        scopes)["paths"].items()
            for name in names}


def module_name(compiled_or_text) -> str:
    """The module's name as its text's first line states it
    (``HloModule jit_chunk_step, ...`` -> ``jit_chunk_step``), the name a
    device trace gives the program's executions; ``""`` without one."""
    head = hlo_text(compiled_or_text).lstrip().split("\n", 1)[0]
    if not head.startswith("HloModule "):
        return ""
    return head[len("HloModule "):].split(",", 1)[0].strip()


def _inherit_paths(body) -> dict:
    """``{instruction: scope path}`` for the instructions of one
    computation that carry no ``op_name`` of their own and feed one that
    has a scope — directly or through others like them, so the chain
    ``copy-start`` -> ``copy-done`` -> fusion settles in a few passes —
    or else are fed by one whose scope is its own."""
    paths = {i.name: i.path for i in body if i.path}
    # a tuple gathers values of many scopes (a loop body's root): it may
    # pass its user's path on to what feeds it, never take an operand's
    bare = [(i.name, [] if i.opcode == "tuple" else i.operands)
            for i in body if not i.named and i.opcode != "parameter"]
    if not bare or not paths:
        return {}
    users = {}
    for i in body:
        for arg in i.operands:
            users.setdefault(arg, []).append(i.name)
    got = {}
    for _ in range(8):
        changed = False
        for name, args in bare:
            if name in got:
                continue
            path = next((paths.get(u) or got.get(u)
                         for u in users.get(name, [])
                         if u in paths or u in got), None) \
                or next((paths[a] for a in args if a in paths), None)
            if path:
                got[name] = path
                changed = True
        if not changed:
            break
    return got
