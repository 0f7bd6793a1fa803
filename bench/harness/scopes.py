"""Engine phases and model scopes in a profiler trace.

The program marks its work two ways. Host spans (``ENGINE_SPANS``, one
``jax.profiler.TraceAnnotation`` per phase of an engine tick, nested
inside the harness's ``engine_tick``) say what the host was doing; the
``jax.named_scope`` names of ``SCOPES`` reach the compiled program's HLO
as ``op_name`` metadata and say which part of the model an operation
belongs to.

A device operation is named in the trace by its HLO instruction, so its
scope comes from the program's compiled HLO text (:func:`hlo_scopes`):

* an instruction whose ``op_name`` holds scope names takes the innermost;
* one that only moves data (a copy, bitcast, reshape, transpose, slice or
  update-slice, or a fusion of such), with no scope of its own, takes the
  scope of the first operand that has one: a copy XLA inserted takes the
  scope of what it copies, and the loop's slice of a stacked weight the
  scope of the weight;
* a get-tuple-element of a loop's carry follows the carry to the write
  that produced it in the loop body, or, for a value the loop passes
  through unchanged, to the value that entered the loop;
* a program argument takes the scope its path names (``cache`` and the
  block ``table`` are the KV pool's; ``params['layers']['attn']...`` is
  ``attn``'s, the norm before attention ``ln1`` too).

Everything else is unscoped (``None``). The readers below work on the
lists of :class:`harness.trace.Trace` plus the engine's spans, so they are
checked on a small recorded trace (``bench/tests/data/trace_scopes.json.gz``)
without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import re

from harness.trace import ENCLOSING, busy_intervals, matching, op_label

SCOPES = ("embed", "kv_cache", "attn", "mlp", "head", "sample",
          "itq3_planes")
ENGINE_SPANS = ("serve.admit", "serve.prefill_sync", "serve.decode_prep",
                "serve.decode_dispatch", "serve.decode_sync", "serve.commit")
# words of a program argument's path that name a scope by another name
ARGUMENT_SCOPES = {"cache": "kv_cache", "table": "kv_cache", "ln1": "attn",
                   "ln2": "mlp", "ln_f": "head"}
# operations that only move data: they take the scope of what they move
MOVE_OPS = ("copy", "copy-start", "copy-done", "bitcast", "reshape",
            "transpose", "slice", "dynamic-slice", "dynamic-update-slice")
_FUSED_MOVE_OPS = MOVE_OPS + ("parameter", "constant", "broadcast",
                              "get-tuple-element", "tuple")


def read_engine_spans(logdir: str) -> list:
    """(name, start_ns, dur_ns) of every engine span in the ``.xplane.pb``
    under ``logdir``, from every host thread, sorted by start."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane under {logdir}, "
                           f"found {paths}")
    spans = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.name in ENGINE_SPANS]
    return sorted(spans, key=lambda e: e[1])


# --- HLO text -> scope of each instruction ----------------------------------

@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    operands: list
    op_name: str | None
    calls: list        # computations it calls (fusion, while body, ...)
    index: int | None  # get-tuple-element's index


_COMP = re.compile(r"^(ENTRY )?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|branch_computations"
                    r"|true_computation|false_computation)=\{?([^}\s]*)")


def _matching_paren(s: str, i: int) -> int:
    """Index of the parenthesis closing the one at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] == "(":
            depth += 1
        elif s[j] == ")":
            depth -= 1
            if depth == 0:
                return j
    raise ValueError(f"unbalanced parentheses: {s[:80]!r}")


def parse_hlo(text: str):
    """(computations, entry, roots): every computation of an HLO module's
    text as {name: {instr name: Instr}}, the entry's name, and each
    computation's root instruction."""
    comps: dict = {}
    roots: dict = {}
    entry, cur = None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            cur = comps.setdefault(m.group(2), {})
            if m.group(1):
                entry = m.group(2)
            cname = m.group(2)
            continue
        m = _INSTR.match(line)
        if not m or cur is None:
            continue
        rest = line[m.end():]
        # the result type: a tuple type is parenthesised and may hold spaces
        t_end = _matching_paren(rest, 0) + 1 if rest.startswith("(") \
            else rest.index(" ")
        rest = rest[t_end:].lstrip()
        k = rest.index("(")
        close = _matching_paren(rest, k)
        attrs = rest[close + 1:]
        op = _OP_NAME.search(attrs)
        idx = re.search(r"\bindex=(\d+)", attrs)
        calls = [c.lstrip("%") for grp in _CALLS.findall(attrs)
                 for c in grp.split(",") if c]
        ins = Instr(name=m.group(1), opcode=rest[:k],
                    operands=re.findall(r"%([\w.\-]+)", rest[k:close + 1]),
                    op_name=op.group(1).replace("\\'", "'") if op else None,
                    calls=calls, index=int(idx.group(1)) if idx else None)
        cur[ins.name] = ins
        if line.lstrip().startswith("ROOT "):
            roots[cname] = ins.name
    return comps, entry, roots


def scope_of(op_name: str | None) -> str | None:
    """The innermost scope named in an ``op_name`` path; for one merged
    from several operations (``a/x;b/y``), in the first of them."""
    if not op_name:
        return None
    found = None
    for part in op_name.split(";")[0].split("/"):
        if part in SCOPES:
            found = part
    return found


def argument_scope(op_name: str | None) -> str | None:
    """The scope a program argument's path names: ``cache['attn']['k']``
    is the KV pool, ``params['layers']['mlp']['up']`` belongs to ``mlp``."""
    for w in re.findall(r"\w+", op_name or ""):
        if w in ARGUMENT_SCOPES:
            return ARGUMENT_SCOPES[w]
        if w in SCOPES:
            return w
    return None


def hlo_scopes(text: str) -> dict:
    """{instruction name: scope or None} for every instruction a device
    trace times (those of the entry, loop bodies and branches), following
    the rules of this module's docstring."""
    comps, entry, roots = parse_hlo(text)
    where = {n: c for c, body in comps.items() for n in body}
    ins_of = {n: comps[c][n] for n, c in where.items()}
    # a fusion's body and a reduction's or scatter's combiner run inside
    # one device operation: only other computations' instructions are timed
    inner = {c for body in comps.values() for i in body.values()
             if i.opcode not in ("while", "conditional", "call")
             for c in i.calls}
    loop_of: dict = {}  # while body computation -> the while instruction
    for body in comps.values():
        for i in body.values():
            if i.opcode == "while":
                for c in i.calls:
                    loop_of[c] = i
    memo: dict = {}

    def element(src: Instr | None, idx: int, seen: frozenset):
        """Scope of element ``idx`` of a tuple-shaped value."""
        if src is None:
            return None
        if src.opcode == "parameter" and where[src.name] in loop_of:
            return carried(loop_of[where[src.name]], idx, seen)
        if src.opcode == "while":
            return carried(src, idx, seen)
        if src.opcode == "tuple":
            return (resolve(src.operands[idx], seen)
                    if idx < len(src.operands) else None)
        return resolve(src.name, seen)

    def carried(loop: Instr, idx: int, seen: frozenset):
        """Scope of the value a loop carries at ``idx``: what the body
        writes there, else what entered the loop."""
        for c in loop.calls:
            root = ins_of.get(roots.get(c))
            if root is not None and root.opcode == "tuple":
                s = element(root, idx, seen)
                if s:
                    return s
        return element(ins_of.get(loop.operands[0]), idx, seen) \
            if loop.operands else None

    def first_scoped(names: list, seen: frozenset):
        for n in names:
            s = resolve(n, seen)
            if s:
                return s
        return None

    def fused_scope(ins: Instr, seen: frozenset):
        body = comps.get(ins.calls[0]) if ins.calls else None
        if not body:
            return None
        root = body.get(roots.get(ins.calls[0]))
        for cand in ([root] if root else []) + list(body.values()):
            s = scope_of(cand.op_name)
            if s:
                return s
        if all(i.opcode in _FUSED_MOVE_OPS for i in body.values()):
            return first_scoped(ins.operands, seen)
        return None

    def resolve(name: str, seen: frozenset = frozenset()):
        if name in memo:
            return memo[name]
        ins = ins_of.get(name)
        if ins is None or name in seen:
            return None
        seen = seen | {name}
        if ins.opcode == "parameter":
            comp = where[name]
            s = argument_scope(ins.op_name) if comp == entry else None
        else:
            s = scope_of(ins.op_name)
        if s is None and ins.opcode == "fusion":
            s = fused_scope(ins, seen)
        elif s is None and ins.opcode == "get-tuple-element":
            s = element(ins_of.get(ins.operands[0]), ins.index, seen)
        elif s is None and ins.opcode in MOVE_OPS:
            s = first_scoped(ins.operands, seen)
        if len(seen) == 1:  # an answer from the top, never cut by a cycle
            memo[name] = s
        return s

    return {n: resolve(n) for n, c in where.items() if c not in inner}


# --- readings ------------------------------------------------------------------

def instr_name(event_name: str) -> str:
    """'%copy.290 = s8[...] copy(...)' -> 'copy.290'."""
    return event_name.split(" = ")[0].lstrip("%")


def program_ops(trace, program: str) -> list:
    """The device operations that ran inside a matching program, less the
    control flow that encloses other operations."""
    progs = matching(trace.programs, program)
    out, j = [], 0
    for ev in trace.ops:
        while j < len(progs) and progs[j][1] + progs[j][2] <= ev[1]:
            j += 1
        if (j < len(progs) and progs[j][1] <= ev[1]
                and op_label(ev[0]) not in ENCLOSING):
            out.append(ev)
    return out


def scope_ns(trace, program: str, scopes: dict) -> dict:
    """{scope or None: device ns} over the operations that ran inside a
    matching program; ``scopes`` maps instruction names to scopes
    (:func:`hlo_scopes` of that program). An operation the map does not
    hold counts under ``"?"``."""
    out: dict = {}
    for ev in program_ops(trace, program):
        s = scopes.get(instr_name(ev[0]), "?")
        out[s] = out.get(s, 0.0) + ev[2]
    return out


def unscoped_ops(trace, program: str, scopes: dict, n: int = 10) -> list:
    """[[instruction label, seconds]] of the unscoped operations that took
    most time inside a matching program."""
    tot: dict = {}
    for ev in program_ops(trace, program):
        if scopes.get(instr_name(ev[0]), "?") in (None, "?"):
            label = op_label(ev[0])
            tot[label] = tot.get(label, 0.0) + ev[2]
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                            key=lambda kv: -kv[1])[:n]]


def labelled_gaps(trace, engine_spans: list) -> list:
    """(label, ns) of every idle stretch of the traced window, longest
    first. The label starts with the harness span that overlaps the gap
    most (as :func:`harness.trace.idle_gaps` has it) and adds every engine
    span that covers most of the gap, outermost first: for example
    ``engine_tick>serve.admit>serve.prefill_sync``."""
    lo, hi = trace.window()
    gaps, t = [], lo
    for a, b in busy_intervals(trace.ops, lo, hi) + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    out = []
    for a, b in gaps:
        best, label = 0.0, "none"
        for name, s, d in trace.spans:
            ov = min(b, s + d) - max(a, s)
            if ov > best:
                best, label = ov, name
        inner = sorted((s, -d, name) for name, s, d in engine_spans
                       if 2 * (min(b, s + d) - max(a, s)) > b - a)
        out.append((">".join([label] + [n for *_, n in inner]),
                    float(b - a)))
    out.sort(key=lambda g: -g[1])
    return out


def idle_by_phase(trace, engine_spans: list, n: int = 10) -> list:
    """[[label, seconds]]: the window's idle time summed by
    :func:`labelled_gaps` label."""
    tot: dict = {}
    for label, ns in labelled_gaps(trace, engine_spans):
        tot[label] = tot.get(label, 0.0) + ns
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                            key=lambda kv: -kv[1])[:n]]


def span_share(trace, engine_spans: list, name: str) -> float | None:
    """Share of the traced window inside the named engine spans."""
    lo, hi = trace.window()
    ev = [e for e in engine_spans if e[0] == name]
    if not ev:
        return None
    return sum(b - a for a, b in busy_intervals(ev, lo, hi)) / (hi - lo)
