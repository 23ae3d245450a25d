"""Device time by engine phase.

The program runs each phase of the selection engine under one
``jax.named_scope`` (``sel.seed``, ``sel.sweep``, ``sel.compact``,
``sel.probe``, ``sel.sort``).  The scopes reach the compiled program only
as the ``op_name`` metadata of its HLO instructions, and the chip names
each op's trace event by its instruction.  So the map from instruction to
phase is read from the compiled text of the program that ran
(:func:`entry_map`), and each ``xla`` op of the trace is bucketed by its
instruction's phase (:func:`ns_by_scope`).

An instruction's phase is the innermost ``sel.*`` component of its
``op_name``.  XLA emits some instructions with no scope path (a cumsum's
``reduce-window`` and its relayout copies carry a bare
``op_name="reduce_window_sum"`` or none, and under ``shard_map`` the
partitioner names what it rewrites ``jit(f)/shard_map/<op>``); such an
instruction takes the phase that all its scoped users share, failing that
the phase all its scoped operands' producers share, and is ``unscoped``
otherwise.  Users come first because a phase's first op reads the last
phase's results: the sharded finalize's survivor mask reads the loop's
final bracket, and by its producers would be counted as a sweep.  An
instruction still without a phase does not vote.

Times follow ``engine.xla_ms_per_call``: self time of the ``xla`` ops that
start in the window, averaged over chips, so the buckets add up to it.
Kernels and exchanges between chips stay in their own metrics.
"""
from __future__ import annotations

import collections
import re

SCOPES = ("sel.seed", "sel.sweep", "sel.compact", "sel.probe", "sel.sort")
UNSCOPED = "unscoped"

_SCOPE = re.compile(r"\bsel\.(?:seed|sweep|compact|probe|sort)\b")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str):
    """The innermost ``sel.*`` component of an ``op_name`` path
    (``jit(f)/vmap(sel.compact)/gather`` -> ``sel.compact``), or None."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE.search(part)
        if m:
            return m.group(0)
    return None


def parse_hlo(text: str) -> dict:
    """``{instruction: (own scope or None, [operand instructions])}`` for
    every instruction of an HLO module's text, in text order (producers
    before users within a computation)."""
    lines = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            lines[m.group(1)] = m.group(2)
    out = {}
    for name, rest in lines.items():
        body, _, meta = rest.partition("metadata={")
        op = _OP_NAME.search(meta)
        operands = [r for r in _REF.findall(body) if r in lines and r != name]
        out[name] = (scope_of(op.group(1)) if op else None, operands)
    return out


def _shared(scopes):
    s = {x for x in scopes if x is not None}
    return s.pop() if len(s) == 1 else None


def scope_map(text: str) -> dict:
    """``{instruction: phase}`` by the rule in the module docstring; the
    phase is one of :data:`SCOPES` or :data:`UNSCOPED`."""
    instrs = parse_hlo(text)
    scope = {n: s for n, (s, _) in instrs.items()}
    users = collections.defaultdict(list)
    for n, (_, ops) in instrs.items():
        for o in ops:
            users[o].append(n)
    for n in reversed(list(instrs)):  # users before their producers
        if scope[n] is None:
            scope[n] = _shared(scope[u] for u in users[n])
    for n, (_, ops) in instrs.items():  # producers before their users
        if scope[n] is None:
            scope[n] = _shared(scope[o] for o in ops)
    return {n: s or UNSCOPED for n, s in scope.items()}


def names_phases(smap: dict) -> bool:
    """Whether the program names any phase (a program without the scopes
    maps every instruction to ``unscoped``)."""
    return any(s != UNSCOPED for s in smap.values())


def compiled_text(fn, *args) -> str:
    """The optimized HLO text of ``jax.jit`` function ``fn`` on ``args``,
    compiled afresh.  The persistent compilation cache leaves metadata out
    of its key, so a hit may hold the same program compiled from a version
    with other scopes; the instruction names, which metadata does not
    change, still match the program that ran."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


_MAPS = {}


def entry_map(entry) -> dict:
    """The scope map of the program the entry ran (read after the window);
    one per jitted function."""
    key = id(entry.fn)
    if key not in _MAPS:
        _MAPS[key] = (entry.fn, scope_map(
            compiled_text(entry.fn, *entry.args(0, 0))))
    return _MAPS[key][1]


def ns_by_scope(trace, lo: float, hi: float, smap: dict) -> dict:
    """Self time of the ``xla`` ops that start in ``[lo, hi]``, by phase,
    in ns, averaged over chips; every phase of :data:`SCOPES` and
    :data:`UNSCOPED` is a key."""
    from bench import trace as tr

    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for evs in trace.ops.values():
        for e, ns in tr.self_times(evs):
            if lo <= e.start < hi and tr.op_kind(e) == "xla":
                out[smap.get(tr.op_name(e), UNSCOPED)] += ns
    nchips = max(len(trace.ops), 1)
    return {k: v / nchips for k, v in out.items()}


def ms_per_call(ctx, scope: str):
    """Device time per call of one phase's ``xla`` ops, in ms; None where
    the trace holds no op or the program names no phase."""
    if not ctx.trace.ops or not ctx.calls:
        return None
    smap = entry_map(ctx.entry)
    if not names_phases(smap):
        return None
    return ns_by_scope(ctx.trace, ctx.lo, ctx.hi, smap)[scope] * 1e-6 / (
        ctx.calls)
