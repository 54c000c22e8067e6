"""jaxlint concurrency & resource-discipline rules.

The serving stack's correctness rests on hand-enforced disciplines — lock
ordering, "reserve under the lock, transfer outside it", lease/allocation
pairing, bounded metric label sets. These rules turn each discipline into
a whole-program check over the typed call graph (:mod:`.typeinfo`) and
the lock model (:mod:`.locks`):

- ``lock-order-cycle`` — cycles in the program's lock-acquisition-order
  graph (potential ABBA deadlocks);
- ``blocking-call-under-lock`` — I/O, sleeps, device syncs, subprocess,
  ``Event.wait``/``Thread.join`` executed (directly or transitively)
  while a lock is held;
- ``acquire-release`` — allocations/leases released on every path
  including exceptions, context managers actually entered, must-use
  results actually used;
- ``property-vs-call`` — ``@property`` attributes called like methods,
  and bound methods truth-tested without being called (the PR 12
  ``entry.resident()`` drain-bug family, both directions);
- ``metric-docs-drift`` — metric families missing from ``obs/README.md``
  or emitted with diverging label sets across call sites.

All findings ride the normal engine: suppressible per line, SARIF'd,
baselined. Functions that *deliberately* block under a lock opt out with
``# jaxlint: sanction=blocking-call-under-lock`` on their ``def`` line
(see :mod:`.locks` for semantics).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, FrozenSet, Iterator, List, Optional, Set, Tuple

from .engine import FileContext, Finding, Rule
from .locks import get_lock_model
from .rules import register
from .typeinfo import dotted_expr, get_types


@register
class LockOrderCycleRule(Rule):
    """Cycles in the lock-acquisition-order graph.

    If thread 1 takes A then B while thread 2 takes B then A, each can
    hold one lock and wait forever on the other — the classic ABBA
    deadlock, invisible to tests unless the interleaving actually fires.
    The lock model records an edge A -> B whenever a function acquires B
    (directly or through any resolvable callee, across modules) while
    holding A; a cycle among the edges is a potential deadlock. Lock
    identity is nominal — ``module.Class.attr`` — so two instances of one
    class share an identity and self-edges are not reported (an RLock
    re-enter and a two-instance ABBA are indistinguishable statically).
    """

    name = "lock-order-cycle"
    description = ("cycle in the whole-program lock-acquisition graph "
                   "(potential ABBA deadlock)")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        model = get_lock_model(ctx.program)
        for comp in model.cycles():
            in_comp = set(comp)
            edges = sorted(
                (w, a, b) for (a, b), w in model.order_edges.items()
                if a in in_comp and b in in_comp)
            if not edges:
                continue
            (path, line, via), a, b = edges[0]
            if os.path.normpath(path) != os.path.normpath(ctx.path):
                continue
            detail = "; ".join(
                f"{ea} -> {eb} ({wp}:{wl}, {wv})"
                for (wp, wl, wv), ea, eb in edges[:4])
            yield Finding(
                self.name, ctx.path, line, 0,
                f"lock-order cycle between {', '.join(comp)} — threads "
                f"taking these locks in opposite orders can deadlock "
                f"(ABBA). Witnesses: {detail}. Fix by imposing one "
                f"acquisition order or narrowing one critical section")


@register
class BlockingUnderLockRule(Rule):
    """Blocking work executed while a lock is held.

    A lock held across a sleep, a socket round-trip, a device transfer,
    a ``subprocess`` call, or an ``Event.wait``/``Thread.join`` turns one
    slow operation into a stall for *every* thread contending on that
    lock — the registry freeze and watchdog false-positives of PR 8's
    postmortems. The check is transitive over the typed call graph: a
    helper three calls deep that sleeps is charged to the caller holding
    the lock, with the witness chain in the message. ``Condition.wait``
    on the *held* condition is exempt (the wait releases it — the
    sanctioned wait-loop idiom). Deliberately-blocking helpers opt out
    with ``# jaxlint: sanction=blocking-call-under-lock`` plus a written
    justification.
    """

    name = "blocking-call-under-lock"
    description = ("I/O / sleep / device sync / Event.wait / Thread.join "
                   "reachable while a lock is held")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        model = get_lock_model(ctx.program)
        for fi in ctx.module_info.all_funcs:
            if model.sanctioned(fi, self.name):
                continue
            direct = {id(s.node): s for s in model.direct_blocks(fi)}
            callee_at = {id(call): callee
                         for call, callee in model.call_edges.get(fi, ())}
            for ev in model.events(fi):
                if ev[0] != "call":
                    continue
                _, node, held = ev
                if not held:
                    continue
                site = direct.get(id(node))
                if site is not None:
                    eff = [h for h in held if h != site.exempt_lock]
                    if eff:
                        yield self.finding(
                            ctx, node,
                            f"{site.desc} while holding {', '.join(eff)} "
                            f"— every thread contending on the lock stalls "
                            f"behind it; move the blocking work outside "
                            f"the critical section (copy-then-release), "
                            f"or sanction the helper if deliberate")
                    continue
                callee = callee_at.get(id(node))
                if callee is None:
                    continue
                chain = model.block_chain.get(callee)
                if chain and not model.sanctioned(callee, self.name):
                    yield self.finding(
                        ctx, node,
                        f"call blocks while holding {', '.join(held)}: "
                        f"{' -> '.join(chain)} — release the lock before "
                        f"the slow work, or sanction the helper "
                        f"(# jaxlint: sanction={self.name}) with a "
                        f"justification")


#: (class-name suffix, acquire method) -> release method names. Receivers
#: are resolved nominally, so look-alike ``ensure``/``alloc`` methods on
#: unrelated classes never match.
_ACQ_PROTOCOLS: Dict[Tuple[str, str], Tuple[str, ...]] = {
    ("BlockAllocator", "alloc"): ("release",),
    ("SlotPages", "ensure"): ("release",),
}

#: (class-name suffix, method) whose boolean/token result must be used —
#: a bare-statement call silently burns the budget/allocation
_MUST_USE: Set[Tuple[str, str]] = {
    ("RetryBudget", "spend"),
    ("TokenBucket", "take"),
    ("BlockAllocator", "alloc"),
    ("SlotPages", "ensure"),
}


@register
class AcquireReleaseRule(Rule):
    """Resource acquisitions must be released on all paths.

    The PR 12 drain bug's family: a lease/allocation taken and then
    leaked on an early-error path. Three checks, all over nominally
    typed receivers:

    1. an allocation (``BlockAllocator.alloc``, ``SlotPages.ensure``)
       bound to a local must be released (``release``) or have
       its ownership transferred (returned, stored, passed on) — on the
       normal path, on early returns, and when a call between acquire
       and release can raise (release must sit in a ``finally`` or an
       exception handler);
    2. a ``@contextmanager`` callee (``ModelRegistry.lease``) must
       actually be entered with ``with`` — a bare call builds the
       generator and leases nothing;
    3. must-use results (``RetryBudget.spend``, ``TokenBucket.take``)
       discarded as a bare statement are silently burned tokens.
    """

    name = "acquire-release"
    description = ("allocation/lease not released on every path (incl. "
                   "exceptions), contextmanager not entered, or must-use "
                   "result discarded")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        types = get_types(ctx.program)
        mi = ctx.module_info
        for fi in mi.all_funcs:
            yield from self._check_fn(ctx, types, fi)

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _recv_suffix(types, fi, call: ast.Call) -> Optional[str]:
        ci = types.receiver_class(fi, call)
        return ci.name if ci is not None else None

    def _check_fn(self, ctx, types, fi) -> Iterator[Finding]:
        mi = fi.module
        acquisitions = []  # (stmt, name, release names, class name)
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute):
                cname = self._recv_suffix(types, fi, node.value)
                key = (cname, node.value.func.attr)
                if key in _ACQ_PROTOCOLS:
                    acquisitions.append((node, node.targets[0].id,
                                         _ACQ_PROTOCOLS[key], cname))
            elif isinstance(node, ast.Call):
                callee = types.method_callee(fi, node)
                parent = mi.parents.get(node)
                if callee is not None and self._is_ctxmanager(callee):
                    yield from self._check_cm_use(ctx, fi, node, callee,
                                                  parent)
                if isinstance(node.func, ast.Attribute) \
                        and isinstance(parent, ast.Expr):
                    cname = self._recv_suffix(types, fi, node)
                    if (cname, node.func.attr) in _MUST_USE:
                        yield Finding(
                            self.name, ctx.path, node.lineno,
                            node.col_offset,
                            f"result of {cname}.{node.func.attr}() is "
                            f"discarded — the token/allocation is spent "
                            f"either way; branch on the result or bind it")
        for acq_stmt, name, releases, cname in acquisitions:
            yield from self._check_pairing(ctx, fi, acq_stmt, name,
                                           releases, cname)

    @staticmethod
    def _is_ctxmanager(callee) -> bool:
        node = getattr(callee, "node", None)
        if node is None:
            return False
        mi = callee.module
        return any(dotted_expr(mi, d) == "contextlib.contextmanager"
                   for d in node.decorator_list)

    def _check_cm_use(self, ctx, fi, call, callee, parent
                      ) -> Iterator[Finding]:
        mi = fi.module
        if isinstance(parent, ast.withitem):
            return
        if isinstance(parent, ast.Expr):
            yield Finding(
                self.name, ctx.path, call.lineno, call.col_offset,
                f"'{callee.qual}' is a @contextmanager but the call is a "
                f"bare statement — the generator is built and discarded, "
                f"nothing is leased/entered; use `with "
                f"{callee.name}(...):`")
            return
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1 \
                and isinstance(parent.targets[0], ast.Name):
            bound = parent.targets[0].id
            for n in ast.walk(fi.node):
                if isinstance(n, ast.withitem) \
                        and isinstance(n.context_expr, ast.Name) \
                        and n.context_expr.id == bound:
                    return
                if isinstance(n, ast.Call):
                    f = n.func
                    if isinstance(f, ast.Attribute) \
                            and isinstance(f.value, ast.Name) \
                            and f.value.id == bound \
                            and f.attr in ("__enter__",):
                        return
                if isinstance(n, ast.Return) and n.value is not None \
                        and any(isinstance(x, ast.Name) and x.id == bound
                                for x in ast.walk(n.value)):
                    return  # ownership transferred to the caller
            yield Finding(
                self.name, ctx.path, call.lineno, call.col_offset,
                f"'{callee.qual}' is a @contextmanager assigned to "
                f"'{bound}' but never entered with `with` — the lease "
                f"body never runs")

    def _check_pairing(self, ctx, fi, acq_stmt, name, releases, cname
                       ) -> Iterator[Finding]:
        mi = fi.module
        acq_line = acq_stmt.lineno
        release_nodes: List[ast.Call] = []
        escape_nodes: List[ast.AST] = []
        for n in ast.walk(fi.node):
            if getattr(n, "lineno", 0) <= acq_line \
                    and n is not acq_stmt:
                continue
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
                uses = any(isinstance(a, ast.Name) and a.id == name
                           for a in list(n.args)
                           + [k.value for k in n.keywords])
                if not uses:
                    continue
                if n.func.attr in releases:
                    release_nodes.append(n)
                else:
                    escape_nodes.append(n)  # ownership transferred
            elif isinstance(n, (ast.Return, ast.Yield, ast.YieldFrom)) \
                    and n.value is not None:
                if any(isinstance(x, ast.Name) and x.id == name
                       for x in ast.walk(n.value)):
                    escape_nodes.append(n)
            elif isinstance(n, ast.Assign):
                for t in n.targets:
                    if not (isinstance(t, ast.Name) and t.id == name) \
                            and any(isinstance(x, ast.Name)
                                    and x.id == name
                                    for x in ast.walk(n.value)):
                        escape_nodes.append(n)  # aliased/stored
        settled = release_nodes + escape_nodes
        if not settled:
            yield Finding(
                self.name, ctx.path, acq_line, acq_stmt.col_offset,
                f"'{name}' holds a {cname} allocation that is never "
                f"released ({'/'.join(releases)}) nor handed off — the "
                f"blocks leak for the process lifetime")
            return
        first_settle = min(getattr(n, "lineno", 10 ** 9) for n in settled)
        protected = self._exception_protected(fi, acq_stmt, releases, name)
        risky = self._first_risky(fi, acq_stmt, first_settle, settled)
        if risky is not None and not protected:
            what = ("an exception in "
                    f"'{ast.unparse(risky.func) if isinstance(risky, ast.Call) else 'this path'}'"
                    if isinstance(risky, ast.Call) else "a raise")
            yield Finding(
                self.name, ctx.path, risky.lineno, risky.col_offset,
                f"'{name}' ({cname} allocation, line {acq_line}) is "
                f"released on the normal path but leaks if {what} "
                f"propagates before the release — wrap the region in "
                f"try/finally or release in the handler")

    @staticmethod
    def _exception_protected(fi, acq_stmt, releases, name) -> bool:
        """True when a ``try`` at/after the acquisition releases or hands
        off ``name`` in its ``finally`` or an exception handler — covers
        both ``x = alloc()`` inside the try and the standard
        acquire-then-``try`` idiom where the acquisition precedes it."""
        def settles(body) -> bool:
            for n in body:
                for x in ast.walk(n):
                    if isinstance(x, ast.Call) \
                            and isinstance(x.func, ast.Attribute) \
                            and x.func.attr in releases \
                            and any(isinstance(a, ast.Name)
                                    and a.id == name for a in x.args):
                        return True
            return False

        for t in ast.walk(fi.node):
            if not isinstance(t, ast.Try):
                continue
            if t.end_lineno is not None and t.end_lineno < acq_stmt.lineno:
                continue  # the whole try ended before the acquisition
            if settles(t.finalbody) or any(settles(h.body)
                                           for h in t.handlers):
                return True
        return False

    @staticmethod
    def _first_risky(fi, acq_stmt, first_settle: int, settled
                     ) -> Optional[ast.AST]:
        """First call/raise strictly between the acquisition and the
        first release/hand-off — the statement whose exception would
        leak the resource."""
        settled_ids = {id(s) for s in settled}
        best = None
        for n in ast.walk(fi.node):
            ln = getattr(n, "lineno", 0)
            if not (acq_stmt.lineno < ln < first_settle):
                continue
            if id(n) in settled_ids:
                continue
            if isinstance(n, (ast.Call, ast.Raise)):
                if best is None or ln < best.lineno:
                    best = n
        return best


@register
class PropertyVsCallRule(Rule):
    """``@property`` called like a method / bound method used like a value.

    Both directions of the PR 12 drain bug: ``entry.resident()`` raised
    ``TypeError: 'bool' object is not callable`` (400 on every drain)
    because ``resident`` is a property; the mirror bug — ``if
    entry.resident:`` where ``resident`` is a *method* — is always
    truthy and silently disables the branch. Receivers are resolved
    nominally (constructor bindings, annotations, typed returns), so a
    ``resident`` property on one class never taints a same-named method
    elsewhere.
    """

    name = "property-vs-call"
    description = ("@property invoked with (), or zero-arg method "
                   "truth-tested/compared without being called")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        types = get_types(ctx.program)
        for fi in ctx.module_info.all_funcs:
            for node in ast.walk(fi.node):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute):
                    ci = types.class_of(
                        types.type_of(fi, node.func.value))
                    if ci is not None \
                            and node.func.attr in ci.properties:
                        yield self.finding(
                            ctx, node,
                            f"'{node.func.attr}' is a @property of "
                            f"{ci.name} — calling it invokes the "
                            f"*returned value* (TypeError at runtime); "
                            f"drop the parentheses")
                else:
                    for expr in self._bool_contexts(node):
                        yield from self._check_bare(ctx, types, fi, expr)

    @staticmethod
    def _bool_contexts(node: ast.AST) -> Iterator[ast.expr]:
        if isinstance(node, (ast.If, ast.While)):
            yield node.test
        elif isinstance(node, ast.IfExp):
            yield node.test
        elif isinstance(node, ast.Assert):
            yield node.test
        elif isinstance(node, ast.BoolOp):
            yield from node.values
        elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            yield node.operand
        elif isinstance(node, ast.Compare):
            sides = [node.left] + list(node.comparators)
            none = any(isinstance(s, ast.Constant) and s.value is None
                       for s in sides)
            if not none and all(isinstance(op, (ast.Eq, ast.NotEq, ast.Gt,
                                                ast.Lt, ast.GtE, ast.LtE))
                                for op in node.ops):
                yield from sides

    def _check_bare(self, ctx, types, fi, expr) -> Iterator[Finding]:
        if not isinstance(expr, ast.Attribute):
            return
        ci = types.class_of(types.type_of(fi, expr.value))
        if ci is None or expr.attr.startswith("_"):
            return
        m = ci.methods.get(expr.attr)
        if m is not None and not m.params:
            yield self.finding(
                ctx, expr,
                f"'{expr.attr}' is a zero-arg method of {ci.name} — the "
                f"bound method is always truthy, so this test never "
                f"varies; call it: {expr.attr}()")


# --------------------------------------------------------------------------
# metric-docs-drift

_METRIC_FACTORIES = {"counter", "gauge", "histogram"}
_DRIFT_CACHE = "metric-docs-drift:findings"
_MUTATORS = {"update", "setdefault", "pop", "clear"}


def _site_label_keys(mi, call: ast.Call) -> Optional[FrozenSet[str]]:
    """Label keys a metric call site pins down statically: a frozenset
    for literal dicts (possibly via a single un-mutated ``labels = {...}``
    local), the empty frozenset for no-labels calls, None when dynamic
    (helper-built dicts, mutated locals, ** spreads)."""
    cands = list(call.args[1:2]) + [k.value for k in call.keywords
                                    if k.arg == "labels"]
    if not cands:
        return frozenset()

    def keys_of(d: ast.Dict) -> Optional[FrozenSet[str]]:
        out = []
        for k in d.keys:
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                out.append(k.value)
            else:
                return None  # ** spread or computed key
        return frozenset(out)

    e = cands[0]
    if isinstance(e, ast.Dict):
        return keys_of(e)
    if isinstance(e, ast.Name):
        fn = mi.enclosing_function(call)
        if fn is None:
            return None
        assigns = [n for n in ast.walk(fn)
                   if isinstance(n, ast.Assign) and len(n.targets) == 1
                   and isinstance(n.targets[0], ast.Name)
                   and n.targets[0].id == e.id]
        if len(assigns) != 1 or not isinstance(assigns[0].value, ast.Dict):
            return None
        for n in ast.walk(fn):  # conditional labels["model"] = ... etc.
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                tgts = n.targets if isinstance(n, ast.Assign) else [n.target]
                for t in tgts:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == e.id:
                        return None
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                    and isinstance(n.func.value, ast.Name) \
                    and n.func.value.id == e.id \
                    and n.func.attr in _MUTATORS:
                return None
        return keys_of(assigns[0].value)
    return None


def _doc_text(program) -> Optional[str]:
    """Concatenated text of every ``obs/README.md`` reachable by walking
    up from the analyzed files. None when no such file exists on disk
    (single-fixture tests): the documentation check is skipped, label
    consistency still runs."""
    paths = set()
    for mi in program.modules.values():
        d = os.path.dirname(os.path.normpath(mi.path))
        while True:
            cand = os.path.join(d, "obs", "README.md")
            if os.path.isfile(cand):
                paths.add(cand)
            if os.path.basename(d) == "obs":
                cand = os.path.join(d, "README.md")
                if os.path.isfile(cand):
                    paths.add(cand)
            parent = os.path.dirname(d)
            if parent == d:
                break
            d = parent
    if not paths:
        return None
    text = []
    for p in sorted(paths):
        with open(p, "r", encoding="utf-8") as fh:
            text.append(fh.read())
    return "\n".join(text)


def _drift_findings(program) -> List[Tuple[str, int, int, str]]:
    cached = program.cache.get(_DRIFT_CACHE)
    if cached is not None:
        return cached
    sites: Dict[str, List[Tuple[str, int, int,
                                Optional[FrozenSet[str]]]]] = {}
    for mi in sorted(program.modules.values(), key=lambda m: m.path):
        for node in ast.walk(mi.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_FACTORIES
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            fam = node.args[0].value
            sites.setdefault(fam, []).append(
                (mi.path, node.lineno, node.col_offset,
                 _site_label_keys(mi, node)))
    doc = _doc_text(program)
    out: List[Tuple[str, int, int, str]] = []
    for fam in sorted(sites):
        slist = sorted(sites[fam], key=lambda s: (s[0], s[1]))
        if doc is not None and fam not in doc:
            p, ln, col, _ = slist[0]
            out.append((p, ln, col,
                        f"metric family '{fam}' is not documented in "
                        f"obs/README.md — every scraped family needs a "
                        f"row there (name, labels, meaning) or dashboards "
                        f"and alerts drift from the code"))
        keysets = [s for s in slist if s[3] is not None]
        distinct = {s[3] for s in keysets}
        if len(distinct) > 1:
            counts: Dict[FrozenSet[str], int] = {}
            for s in keysets:
                counts[s[3]] = counts.get(s[3], 0) + 1
            majority = max(sorted(distinct, key=lambda k: sorted(k)),
                           key=lambda k: counts[k])
            anchor = next(s for s in keysets if s[3] == majority)
            for p, ln, col, keys in keysets:
                if keys == majority:
                    continue
                out.append((p, ln, col,
                            f"metric family '{fam}' emitted with label "
                            f"set {{{', '.join(sorted(keys))}}} here but "
                            f"{{{', '.join(sorted(majority))}}} at "
                            f"{anchor[0]}:{anchor[1]} — a silent labelset "
                            f"fork; one family must keep one label set"))
    program.cache[_DRIFT_CACHE] = out
    return out


@register
class MetricDocsDriftRule(Rule):
    """Metric families undocumented or with forked label sets.

    ``obs/README.md`` is the contract dashboards and alerts are built
    against; a family the code emits but the README never mentions is
    telemetry nobody can find, and the same family emitted with two
    different label sets (``{model}`` here, ``{model, replica}`` there)
    splits one logical series into disjoint groups that ``sum()`` and
    ``rate()`` silently mis-aggregate. Sites whose label dict is built
    dynamically (helper calls, mutated locals) are skipped for the
    consistency check — only provably-literal forks are reported.
    """

    name = "metric-docs-drift"
    description = ("metric family missing from obs/README.md, or same "
                   "family emitted with diverging label sets")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        norm = os.path.normpath(ctx.path)
        for path, line, col, msg in _drift_findings(ctx.program):
            if os.path.normpath(path) == norm:
                yield Finding(self.name, ctx.path, line, col, msg)
