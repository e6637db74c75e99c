"""Shared jit-region discovery for the JAX-discipline passes.

One module owns the question "which functions in this file run traced
under ``jax.jit``, and with what static arguments?" — extracted from
``hostsync.py`` (which found jit regions but threw the static-argument
information away) so ``recompile.py`` and ``tracerleak.py`` can reason
about *which parameters are traced* and *where jitted callables are
invoked* without re-implementing the discovery.

Recognized jit shapes (the ones the repo actually uses):

- decorated: ``@jax.jit``, ``@jax.jit(...)``,
  ``@partial(jax.jit, static_argnums=..., static_argnames=...)``
  (``named_jit`` reads as ``jax.jit`` throughout);
- passed: ``jax.jit(f, ...)``, ``jax.jit(self.m, ...)``,
  ``jax.jit(partial(self.m, k), ...)`` — partial-bound leading
  positionals are treated as static (they key the jit cache);
- wrappers: ``dp_sharded_sampler(self._sample_impl, mesh)`` — the
  serving pipelines' sharded-jit helper.

The **closure** of an entry (same-module functions it transitively
calls through bare names or ``self.X``/``cls.X``) runs traced too —
identical to hostsync's fixpoint, now shared.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from cassmantle_tpu.analysis.core import call_name, dotted_name

# named_jit(f, "name", **jit_kwargs) is jax.jit under a fixed program
# name (utils/profiling.py): same target position, same static kwargs
JIT_NAMES = {"jax.jit", "jit", "named_jit"}
JIT_WRAPPERS = {"dp_sharded_sampler"}
PARTIAL_NAMES = {"partial", "functools.partial"}


@dataclasses.dataclass
class JitEntry:
    """One function that enters a jit region as the traced entry point.

    ``params`` are the positional parameter names with a leading
    ``self``/``cls`` dropped; ``static_params`` the subset that is NOT
    traced (declared via static_argnums/static_argnames, or bound by a
    ``partial`` before jit saw the function). ``traced_params`` is the
    rest. ``explicit_statics`` records whether any static declaration
    was visible — passes that need to reason about "the author marked
    this static" can distinguish "no statics" from "unknown"."""

    fn: ast.AST
    params: List[str] = dataclasses.field(default_factory=list)
    static_params: Set[str] = dataclasses.field(default_factory=set)
    explicit_statics: bool = False

    @property
    def traced_params(self) -> List[str]:
        return [p for p in self.params if p not in self.static_params]


@dataclasses.dataclass
class JitAlias:
    """A name a jitted callable is reachable through at call sites:
    ``g = jax.jit(f, ...)`` (key ``g``), ``self._x = jax.jit(...)``
    (key ``_x``), or a directly-decorated function (key ``f``).

    ``bound`` is the number of leading positionals a wrapping
    ``partial`` consumed: call-site argument ``i`` maps to
    ``entry.params[bound + i]``, and ``static_argnums`` (from the jit
    call itself) index the partial-reduced signature — i.e. call-site
    positions directly."""

    key: str
    entry: Optional[JitEntry]        # resolved same-module target
    static_argnums: Tuple[int, ...] = ()
    static_argnames: Tuple[str, ...] = ()
    bound: int = 0
    #: this alias's OWN jit site declared statics — callers should then
    #: trust these over the (possibly multi-site-merged) entry's
    explicit: bool = False


def function_table(tree: ast.Module) -> Dict[str, ast.AST]:
    """qual -> node for top-level functions and methods; bare method
    names are also keyed (for ``self.X`` / ``jax.jit(self.X)``
    resolution) when unambiguous enough — first definition wins."""
    fns: Dict[str, ast.AST] = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns.setdefault(node.name, node)
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    fns.setdefault(f"{node.name}.{sub.name}", sub)
                    fns.setdefault(sub.name, sub)
    return fns


def positional_params(fn: ast.AST) -> List[str]:
    """Positional parameter names, leading ``self``/``cls`` dropped
    (jit always sees the bound method)."""
    params = [a.arg for a in fn.args.args]
    if params and params[0] in ("self", "cls"):
        params = params[1:]
    return params


def _int_constants(expr: Optional[ast.expr]) -> Tuple[int, ...]:
    """static_argnums as a tuple of ints (``0`` or ``(0, 5)``);
    anything dynamic resolves to ()."""
    if expr is None:
        return ()
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int):
        return (expr.value,)
    if isinstance(expr, (ast.Tuple, ast.List)):
        out = []
        for e in expr.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return tuple(out)
    return ()


def _str_constants(expr: Optional[ast.expr]) -> Tuple[str, ...]:
    if expr is None:
        return ()
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return (expr.value,)
    if isinstance(expr, (ast.Tuple, ast.List)):
        return tuple(e.value for e in expr.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


def _static_kwargs(call: ast.Call) -> Tuple[Tuple[int, ...],
                                            Tuple[str, ...], bool]:
    nums = names = None
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            nums = kw.value
        elif kw.arg == "static_argnames":
            names = kw.value
    explicit = nums is not None or names is not None
    return _int_constants(nums), _str_constants(names), explicit


def _target_names(expr: ast.expr) -> Tuple[List[str], int]:
    """(function names referenced by a jit(...) argument, number of
    positionals a wrapping ``partial`` binds): a bare name, a
    ``self.X`` attribute, or either inside ``partial``."""
    if isinstance(expr, ast.Name):
        return [expr.id], 0
    if isinstance(expr, ast.Attribute):
        return [expr.attr], 0
    if isinstance(expr, ast.Call) and \
            call_name(expr) in PARTIAL_NAMES and expr.args:
        names, _ = _target_names(expr.args[0])
        return names, len(expr.args) - 1
    return [], 0


def _make_entry(fn: ast.AST, bound_n: int,
                static_argnums: Tuple[int, ...],
                static_argnames: Tuple[str, ...],
                explicit: bool,
                argnums_include_self: bool = False) -> JitEntry:
    all_params = [a.arg for a in fn.args.args]
    has_self = bool(all_params) and all_params[0] in ("self", "cls")
    params = all_params[1:] if has_self else all_params
    if argnums_include_self and has_self:
        # a DECORATED method is jitted unbound: jax counts ``self`` as
        # position 0, so the declared indices shift down by one over
        # the self-dropped list (index 0 names self itself — skip it)
        static_argnums = tuple(i - 1 for i in static_argnums if i >= 1)
    static: Set[str] = set(params[:bound_n])
    rest = params[bound_n:]
    for i in static_argnums:
        if 0 <= i < len(rest):
            static.add(rest[i])
    static |= set(static_argnames) & set(params)
    return JitEntry(fn=fn, params=params, static_params=static,
                    explicit_statics=explicit)


def jit_entries(tree: ast.Module,
                fns: Dict[str, ast.AST]) -> Dict[ast.AST, JitEntry]:
    """fn node -> JitEntry for every function that is jit-compiled as
    an entry point (decorated, passed to jit, or wrapper-jitted)."""
    entries: Dict[ast.AST, JitEntry] = {}

    def add(fn, bound_n, nums, names, explicit, include_self=False):
        made = _make_entry(fn, bound_n, nums, names, explicit,
                           argnums_include_self=include_self)
        if fn in entries:
            # a SECOND jit site for the same function: keep only the
            # statics every site agrees on (intersection) — a union
            # would let one alias's static declarations misclassify
            # another alias's traced call positions
            entries[fn].static_params &= made.static_params
            entries[fn].explicit_statics |= explicit
        else:
            entries[fn] = made

    # decorated: @jax.jit / @jax.jit(...) / @partial(jax.jit, ...) —
    # jitted UNBOUND, so static_argnums count self (include_self)
    for fn in set(fns.values()):
        for dec in getattr(fn, "decorator_list", ()):
            if isinstance(dec, ast.Call):
                dec_name = call_name(dec)
                if dec_name in JIT_NAMES:
                    nums, names, explicit = _static_kwargs(dec)
                    add(fn, 0, nums, names, explicit, include_self=True)
                elif dec_name in PARTIAL_NAMES and dec.args and \
                        dotted_name(dec.args[0]) in JIT_NAMES:
                    nums, names, explicit = _static_kwargs(dec)
                    add(fn, 0, nums, names, explicit, include_self=True)
            elif dotted_name(dec) in JIT_NAMES:
                add(fn, 0, (), (), False, include_self=True)
    # passed: jax.jit(f) / jax.jit(partial(f, k)) /
    # dp_sharded_sampler(self._sample_impl, ...)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        is_jit = name in JIT_NAMES
        is_wrapper = (name or "").rsplit(".", 1)[-1] in JIT_WRAPPERS
        if not (is_jit or is_wrapper) or not node.args:
            continue
        targets, bound_n = _target_names(node.args[0])
        nums, names_, explicit = (_static_kwargs(node) if is_jit
                                  else ((), (), False))
        for target in targets:
            if target in fns:
                add(fns[target], bound_n, nums, names_, explicit)
    return entries


def jit_closure(tree: ast.Module, fns: Dict[str, ast.AST],
                entries: Optional[Set[ast.AST]] = None) -> Set[ast.AST]:
    """Entries plus same-module functions they (transitively) call
    — a helper called from a jit body runs traced too."""
    if entries is None:
        entries = set(jit_entries(tree, fns))
    closure = set(entries)
    queue = list(closure)
    while queue:
        fn = queue.pop()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            target = None
            if isinstance(f, ast.Name) and f.id in fns:
                target = fns[f.id]
            elif (isinstance(f, ast.Attribute)
                  and isinstance(f.value, ast.Name)
                  and f.value.id in ("self", "cls")
                  and f.attr in fns):
                target = fns[f.attr]
            if target is not None and target not in closure:
                closure.add(target)
                queue.append(target)
    return closure


def jit_aliases(tree: ast.Module, fns: Dict[str, ast.AST],
                entries: Optional[Dict[ast.AST, JitEntry]] = None
                ) -> Dict[str, JitAlias]:
    """Call-site names resolving to jitted callables: assignments of a
    jit/wrapper call to a bare name or a ``self.X`` attribute, plus
    directly-decorated functions (callable by their own name). Keys are
    the bare name / attribute name — call sites look up ``g(...)`` and
    ``self._x(...)`` by that key. Pass precomputed ``entries`` to avoid
    re-running discovery."""
    if entries is None:
        entries = jit_entries(tree, fns)
    # None marks a key two different jit signatures fought over —
    # ambiguous, filtered out of the returned map
    aliases: Dict[str, Optional[JitAlias]] = {}
    for fn, entry in entries.items():
        for dec in getattr(fn, "decorator_list", ()):
            is_jit = (dotted_name(dec) in JIT_NAMES
                      or (isinstance(dec, ast.Call)
                          and (call_name(dec) in JIT_NAMES
                               or (call_name(dec) in PARTIAL_NAMES
                                   and dec.args
                                   and dotted_name(dec.args[0])
                                   in JIT_NAMES))))
            if is_jit:
                aliases[getattr(fn, "name", "")] = JitAlias(
                    key=getattr(fn, "name", ""), entry=entry)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not isinstance(value, ast.Call):
            continue
        name = call_name(value)
        if name not in JIT_NAMES and \
                (name or "").rsplit(".", 1)[-1] not in JIT_WRAPPERS:
            continue
        nums, argnames, explicit = (_static_kwargs(value)
                                    if name in JIT_NAMES
                                    else ((), (), False))
        entry = None
        bound = 0
        if value.args:
            targets, bound = _target_names(value.args[0])
            for t in targets:
                if t in fns:
                    entry = entries.get(fns[t])
                    break
        for target in node.targets:
            key = None
            if isinstance(target, ast.Name):
                key = target.id
            elif isinstance(target, ast.Attribute) and \
                    isinstance(target.value, ast.Name) and \
                    target.value.id == "self":
                key = target.attr
            if key is not None:
                alias = JitAlias(key=key, entry=entry,
                                 static_argnums=nums,
                                 static_argnames=argnames,
                                 bound=bound, explicit=explicit)
                prior = aliases.get(key)
                if prior is not None and (
                        prior.entry is not alias.entry
                        or prior.static_argnums != alias.static_argnums
                        or prior.static_argnames != alias.static_argnames
                        or prior.bound != alias.bound):
                    # two classes (or rebinding paths) share the key
                    # with different jit signatures: call sites can't
                    # be attributed safely — drop the alias rather
                    # than check calls against the wrong statics
                    aliases[key] = None
                else:
                    aliases[key] = alias
    return {k: v for k, v in aliases.items() if v is not None}
