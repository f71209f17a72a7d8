"""Device time by the program's round scopes; idle gaps by its host spans.

The program runs each phase of a CD-BFL round under a ``jax.named_scope``
(``ROUND_SCOPES``), which reaches the ``op_name`` metadata of every op in
the compiled chunk program, through scan, vmap and grad:
``jit(chunk)/while/body/vmap(transpose(jvp(local_step)))/mul``. The device
trace names an op by its HLO name alone (``fusion.12``), so each op's
scopes are read from the compiled program's text (``op_shares``) and the
trace's device seconds per op (``bench/trace.py``'s ``op_time``) are
summed per scope:

* an op whose own ``op_name`` holds a scope counts in it;
* a fusion counts in the scopes of the ops fused into it, each by its
  share of the fusion's element work (the elements it produces, or a
  reduction's input elements): the Langevin noise draw fused into the
  consensus-error reduction counts mostly as ``langevin_noise``;
* an op without a scope (the copies and loops the compiler adds to change
  a layout, which carry no metadata) takes the scope of the program's
  nearest ``reshape`` or ``transpose`` on its data path, the layout change
  it carries out, else of its nearest scoped neighbour; inside a loop body
  that finds none, the scope of the loop;
* what is left, the scan's own bookkeeping, counts as ``unscoped``.

The program's host spans (``engine.*`` around each chunk, ``serve.*``
inside each serving step) share the trace's clock with the device ops:
``name_gaps`` names each idle gap by the benchmark's span and the innermost
program span that covers most of it (``dispatch_chunk/engine.histories``).
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import NamedTuple

ROUND_SCOPES = ("local_step", "encode", "decode", "mix", "langevin_noise",
                "round_metrics", "sample_batches", "bank_admit")
UNSCOPED = "unscoped"
# a transform wrapper around a path component: vmap(...), jvp(...), ...
WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([^\s,}]+)")
BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
OPERAND = re.compile(r"%([^\s,)]+)")
DIMS = re.compile(r"\[([\d,]*)\]")
# ops that move no data of their own
FREE = frozenset({"parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "after-all"})
REDUCTIONS = frozenset({"reduce", "reduce-window", "select-and-scatter"})
LAYOUT_PRIMITIVES = frozenset({"reshape", "transpose"})
NEIGHBOUR_DEPTH = 8


def _strip(part: str) -> str:
    while (m := WRAPPED.match(part)):
        part = m.group(1)
    return part


def scope_of(op_name: str, scopes=ROUND_SCOPES):
    """The innermost of ``scopes`` on an ``op_name`` path, each component
    taken with its transform wrappers stripped; None if there is none."""
    found = None
    for part in op_name.split("/"):
        if _strip(part) in scopes:
            found = _strip(part)
    return found


class Op(NamedTuple):
    computation: str
    opcode: str
    elements: int            # elements of the result (all of a tuple's)
    operands: tuple
    called: tuple            # computations it calls (fusion, loop, branch)
    op_name: str             # its metadata, "" without


def _split_shape(rest: str):
    """``(shape, text after it)`` of an instruction's right-hand side."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[:i + 1], rest[i + 1:].lstrip()
    return rest, ""


def _elements(shape: str) -> int:
    return sum(math.prod(int(d) for d in dims.split(",") if d)
               for dims in DIMS.findall(shape))


def parse_hlo(hlo_text: str) -> dict:
    """``{op name: Op}`` for every instruction of an HLO module's text."""
    ops, computation = {}, None
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = COMPUTATION.match(line)
            computation = m.group(1) if m else None
            continue
        m = INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        shape, tail = _split_shape(m.group(2))
        opcode, _, args = tail.partition("(")
        depth, end = 1, len(args)
        for i, ch in enumerate(args):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i
                break
        called = CALLED.findall(line)
        for branches in BRANCHES.findall(line):
            called += OPERAND.findall(branches)
        meta = OP_NAME.search(line)
        ops[m.group(1)] = Op(computation, opcode.strip(), _elements(shape),
                             tuple(OPERAND.findall(args[:end])),
                             tuple(called), meta.group(1) if meta else "")
    return ops


def op_shares(hlo_text: str, scopes=ROUND_SCOPES) -> dict:
    """``{op name: {scope: share}}`` for every instruction of an HLO
    module's text, the shares of an op summing to 1; ``{}`` for an op that
    is unscoped (the module docstring gives the rules)."""
    ops = parse_hlo(hlo_text)
    users = defaultdict(list)
    caller = {}
    for name, op in ops.items():
        for o in op.operands:
            users[o].append(name)
        for c in op.called:
            caller.setdefault(c, name)
    bodies = defaultdict(list)
    for name, op in ops.items():
        bodies[op.computation].append(name)

    def work(name):
        op = ops[name]
        if op.opcode in FREE:
            return 0
        if op.opcode in REDUCTIONS and op.operands[0] in ops:
            return ops[op.operands[0]].elements
        return op.elements

    def fused_weights(computation, out):
        for name in bodies.get(computation, ()):
            op = ops[name]
            if op.opcode == "fusion":
                for c in op.called:
                    fused_weights(c, out)
                continue
            scope = scope_of(op.op_name, scopes)
            if scope is not None:
                out[scope] += work(name)
        return out

    own = {}
    for name, op in ops.items():
        if op.opcode == "fusion":
            weights = fused_weights(op.called[0], defaultdict(int)) \
                if op.called else {}
            total = sum(weights.values())
            if total:
                own[name] = {s: w / total for s, w in weights.items() if w}
                continue
        scope = scope_of(op.op_name, scopes)
        if scope is not None:
            own[name] = {scope: 1.0}

    def primitive(name):
        return _strip(ops[name].op_name.rsplit("/", 1)[-1])

    def nearest(name):
        """The shares of the nearest scoped op on ``name``'s data path in
        its computation, a layout primitive first among equally near ones;
        None if there is none."""
        seen, frontier = {name}, [name]
        for _ in range(NEIGHBOUR_DEPTH):
            nxt, found = [], []
            for x in frontier:
                for y in ops[x].operands + tuple(users[x]):
                    if y in seen or y not in ops:
                        continue
                    seen.add(y)
                    (found if y in own else nxt).append(y)
            if found:
                layout = [y for y in found
                          if primitive(y) in LAYOUT_PRIMITIVES]
                return own[(layout or found)[0]]
            frontier = nxt
        return None

    out = {}

    def resolve(name):
        if name not in out:
            out[name] = {}
            shares = own.get(name) or nearest(name)
            if shares is None:
                loop = caller.get(ops[name].computation)
                shares = resolve(loop) if loop is not None else {}
            out[name] = shares
        return out[name]

    for name in ops:
        resolve(name)
    return out


def device_scopes(op_time: dict, shares: dict) -> dict:
    """Device seconds per scope, and ``unscoped`` for the ops with no scope
    or not in ``shares``."""
    out = dict.fromkeys(ROUND_SCOPES, 0.0)
    out[UNSCOPED] = 0.0
    for name, sec in op_time.items():
        for scope, share in (shares.get(name) or {UNSCOPED: 1.0}).items():
            out[scope] += sec * share
    return out


def name_gaps(gaps, bench_spans, program_spans):
    """``gaps``: ``[(start, end)]``; spans: ``[(name, start, end)]``. Each
    gap is named by the benchmark span that overlaps it most (or
    ``untraced host``, as ``bench/trace.py`` names it) and, where a program
    span overlaps it, ``/`` and the innermost (shortest) program span that
    covers more than half of it, else the one that overlaps it most."""
    def overlaps(spans, s, e):
        return [(min(e, he) - max(s, hs), he - hs, name)
                for name, hs, he in spans if min(e, he) > max(s, hs)]
    out = []
    for s, e in gaps:
        bench = overlaps(bench_spans, s, e)
        name = max(bench, key=lambda o: o[0])[2] if bench else "untraced host"
        program = overlaps(program_spans, s, e)
        most = [o for o in program if 2 * o[0] > e - s]
        if most:
            name += "/" + min(most, key=lambda o: o[1])[2]
        elif program:
            name += "/" + max(program, key=lambda o: (o[0], -o[1]))[2]
        out.append(name)
    return out


# -- the training cell's chunk program ----------------------------------------

def chunk_shares(cfg: dict, traffic: dict):
    """``op_shares`` of the training cell's compiled chunk program, built
    as ``bench/train.py`` builds the engine; None where the program offers
    no ``lower_chunk`` (a program without round scopes).

    The compile keys JAX's persistent cache with the program's metadata,
    where the scope names live: without it, an executable cached from a
    program that differs only in its scopes would be loaded, with that
    program's metadata. The first such compile on a cache compiles the
    chunk again; later ones load it."""
    import gc

    import jax

    from bench import train
    from repro.train.engine import ScanRoundEngine
    if not hasattr(ScanRoundEngine, "lower_chunk"):
        return None
    engine, state, key, bank = train.build(cfg, traffic, seed=0)
    lowered = engine.lower_chunk(state, key, bank, int(traffic["chunk"]))
    del engine, state, key, bank
    gc.collect()
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          before)
    shares = op_shares(text)
    if not any(shares.values()):
        raise RuntimeError("the compiled chunk program carries no round "
                           "scope in its op metadata")
    return shares


def train_scope_ms(ctx: dict, scopes) -> float | None:
    """Device ms per round of the named scopes together, in a traced
    training run; None where the trace has nothing or the program has no
    round scopes. The chunk program's shares are built once per run, kept
    in ``ctx``."""
    t, c = ctx["trace"], ctx["counts"]
    if not t or not t["op_time"] or not c.get("rounds"):
        return None
    if "chunk_shares" not in ctx:
        ctx["chunk_shares"] = chunk_shares(ctx["config"], ctx["traffic"])
    if ctx["chunk_shares"] is None:
        return None
    by_scope = device_scopes(t["op_time"], ctx["chunk_shares"])
    return 1e3 * sum(by_scope[s] for s in scopes) / c["rounds"]
