"""Metric-name pass: convention + docs-catalog coverage.

The ``tools/check_metrics.py`` lint (PR 3), ported onto the shared
``analysis`` framework — same rules, same CLI, the bespoke file-walking
/ reporting code replaced by :mod:`cassmantle_tpu.analysis.core`.

Walks every module for literal ``metrics.inc/gauge/observe/timer``
names (plain strings and f-strings — interpolated segments become
wildcards) plus ``block_timer(...)`` stage names, and checks:

1. **Convention** — dotted lowercase ``subsystem.metric`` names, at
   least two segments, each ``[a-z0-9_]`` (or a dynamic wildcard);
   histogram names (``observe``/``timer``/``block_timer``) end ``_s``
   (seconds) or ``_size``.
2. **Catalog coverage** — every name matches an entry in the metric
   catalog in ``docs/OBSERVABILITY.md`` (entries use ``<x>``
   placeholders for dynamic segments), so a new metric cannot ship
   without operator documentation. Drift fails tier-1
   (``tests/test_check_metrics.py``).
3. **Type agreement** (ISSUE 9) — the call kind at the emission site
   must match the catalog row's declared type column: ``inc`` is a
   counter, ``gauge`` a gauge, ``observe``/``timer``/``block_timer`` a
   histogram. A site that drifts (a counter quietly becoming a gauge,
   an ``observe`` on a cataloged counter) changes the Prometheus
   exposition shape (``_total`` vs ``_bucket``) and silently breaks
   every recording rule built on it — now a lint error instead of a
   dashboard surprise. Catalog entries whose row has no recognizable
   type column (prose mentions) don't constrain.
"""

from __future__ import annotations

import ast
import pathlib
import re
from typing import Dict, Iterator, List, Optional, Tuple

from cassmantle_tpu.analysis.core import (
    PACKAGE,
    REPO,
    Finding,
    LintPass,
    Module,
    iter_modules,
    run_passes,
)

CATALOG_DOC = REPO / "docs" / "OBSERVABILITY.md"

RULE = "metric-name"

_METHODS = {"inc", "gauge", "observe", "observe_nowait", "timer"}
_SEGMENT = re.compile(r"^[a-z0-9_*]+$")
_CATALOG_NAME = re.compile(r"`([a-z0-9_.<>*]+\.[a-z0-9_.<>*]+)`")


def _literal_name(node: ast.expr) -> Optional[str]:
    """The metric name as a pattern: f-string holes become ``*``.
    None = not a literal (dynamic whole-name pass-through like
    profiling.block_timer's ``name`` arg — its callers are linted)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for value in node.values:
            if isinstance(value, ast.Constant):
                parts.append(str(value.value))
            else:
                parts.append("*")
        return "".join(parts)
    return None


def _is_registry_receiver(expr: ast.expr) -> bool:
    """Does this call receiver look like a Metrics registry? The plain
    ``metrics`` global, any ``*metrics*``/``*registry*``-named variable
    or attribute (``self._registry``, an injected ``registry=``) — so
    modules that take the registry by injection (obs/slo.py,
    obs/process.py) lint like direct emitters instead of escaping the
    catalog."""
    if isinstance(expr, ast.Name):
        tail = expr.id
    elif isinstance(expr, ast.Attribute):
        tail = expr.attr
    else:
        return False
    tail = tail.lower()
    return "metrics" in tail or "registry" in tail


def extract_sites(source: str, path: str) -> List[Tuple[str, str, int]]:
    """(name_pattern, method, lineno) for every literal metrics call —
    ``<registry>.inc/gauge/observe/observe_nowait/timer(...)`` on any
    registry-shaped receiver (the ``metrics`` global, ``self._registry``,
    …) plus
    ``block_timer(...)`` (utils/profiling.py's metric-emitting stage
    timer, linted as an ``observe`` so device-stage names can't drift
    off the catalog) and the two spellings of a timed host region,
    which name the SPAN and observe the histogram ``<span>_s``:
    ``host_span("a.b")`` and ``OrderedLock(..., wait_span="a.b")``."""
    sites = []
    tree = ast.parse(source, filename=path)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            span = _literal_name(kw.value) if kw.arg == "wait_span" else None
            if span is not None:
                sites.append((span + "_s", "observe", node.lineno))
        if not node.args:
            continue
        suffix = ""
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _METHODS
                and _is_registry_receiver(node.func.value)):
            method = node.func.attr
        elif (isinstance(node.func, ast.Name)
                and node.func.id in ("block_timer", "host_span")):
            method = "observe"
            suffix = "_s" if node.func.id == "host_span" else ""
        else:
            continue
        name = _literal_name(node.args[0])
        if name is not None:
            sites.append((name + suffix, method, node.lineno))
    return sites


_WILD = "\x00"


def _segments_match(code_seg: str, cat_seg: str) -> bool:
    """Mutual-wildcard segment match: ``*`` in code (an interpolated
    chunk) and ``<x>`` in the catalog both stand for any value. Both
    sides normalize their wildcard to one token, then each side's
    pattern is tried against the other's text."""
    code_norm = code_seg.replace("*", _WILD)
    cat_norm = re.sub(r"<[a-z0-9_]+>", _WILD, cat_seg)
    cat_re = re.escape(cat_norm).replace(_WILD, ".+")
    code_re = re.escape(code_norm).replace(_WILD, ".+")
    return bool(re.fullmatch(cat_re, code_norm)
                or re.fullmatch(code_re, cat_norm))


def _name_matches(code_name: str, cat_name: str) -> bool:
    code_segs = code_name.split(".")
    cat_segs = cat_name.split(".")
    if len(code_segs) != len(cat_segs):
        return False
    return all(_segments_match(c, k)
               for c, k in zip(code_segs, cat_segs))


def load_catalog() -> List[str]:
    if not CATALOG_DOC.exists():
        return []
    return sorted(set(_CATALOG_NAME.findall(CATALOG_DOC.read_text())))


_TYPES = ("counter", "gauge", "histogram")
# the method -> declared-type contract the type-agreement rule enforces
_TYPE_FOR_METHOD = {"inc": "counter", "gauge": "gauge",
                    "observe": "histogram", "observe_nowait": "histogram",
                    "timer": "histogram"}


def load_catalog_types() -> Dict[str, str]:
    """``{entry: declared_type}`` from the catalog's markdown tables:
    a row whose second cell is exactly counter/gauge/histogram types
    every backticked name in its first cell. Names appearing only in
    prose carry no type and don't constrain."""
    if not CATALOG_DOC.exists():
        return {}
    types: Dict[str, str] = {}
    for line in CATALOG_DOC.read_text().splitlines():
        stripped = line.strip()
        if not stripped.startswith("|"):
            continue
        cells = [c.strip() for c in stripped.strip("|").split("|")]
        if len(cells) >= 2 and cells[1] in _TYPES:
            for name in _CATALOG_NAME.findall(cells[0]):
                types[name] = cells[1]
    return types


class MetricNamePass(LintPass):
    name = "metric-name"
    description = ("metric naming convention + docs/OBSERVABILITY.md "
                   "catalog coverage")

    def __init__(self, catalog: Optional[List[str]] = None,
                 catalog_types: Optional[Dict[str, str]] = None) -> None:
        self._catalog = catalog
        self._catalog_types = catalog_types
        self._warned_empty = False

    @property
    def catalog(self) -> List[str]:
        if self._catalog is None:
            self._catalog = load_catalog()
        return self._catalog

    @property
    def catalog_types(self) -> Dict[str, str]:
        if self._catalog_types is None:
            self._catalog_types = load_catalog_types()
        return self._catalog_types

    def run(self, module: Module) -> Iterator[Finding]:
        catalog = self.catalog
        if not catalog and not self._warned_empty:
            self._warned_empty = True
            yield Finding(RULE, str(CATALOG_DOC), 1,
                          "metric catalog missing or empty")
        for name, method, lineno in extract_sites(module.source,
                                                  module.rel):
            segs = name.split(".")
            if len(segs) < 2:
                yield Finding(
                    RULE, module.rel, lineno,
                    f"{name!r} needs >=2 dotted segments "
                    f"(subsystem.metric)")
                continue
            bad = [s for s in segs if not _SEGMENT.match(s)]
            if bad:
                yield Finding(
                    RULE, module.rel, lineno,
                    f"{name!r} has non-[a-z0-9_] segment(s) {bad}")
                continue
            if method in ("observe", "observe_nowait", "timer") and \
                    not (segs[-1].endswith("_s")
                         or segs[-1].endswith("_size")):
                yield Finding(
                    RULE, module.rel, lineno,
                    f"histogram {name!r} must end _s (seconds) or _size")
                continue
            if catalog:
                matched = [entry for entry in catalog
                           if _name_matches(name, entry)]
                if not matched:
                    yield Finding(
                        RULE, module.rel, lineno,
                        f"{name!r} not in the docs/OBSERVABILITY.md "
                        f"metric catalog")
                    continue
                # type agreement: the site's call kind must match the
                # declared type of at least one matching typed row —
                # a wildcard site matching several rows is fine as long
                # as one of them is the right kind
                expected = _TYPE_FOR_METHOD[method]
                declared = [self.catalog_types[e] for e in matched
                            if e in self.catalog_types]
                if declared and expected not in declared:
                    yield Finding(
                        RULE, module.rel, lineno,
                        f"{name!r} emitted as a {expected} "
                        f"(metrics.{method}) but cataloged as "
                        f"{'/'.join(sorted(set(declared)))} — type "
                        f"drift; fix the site or the catalog row")


def check(root: pathlib.Path = PACKAGE) -> List[str]:
    """All violations as human-readable strings; empty = clean."""
    return [str(f) for f in
            run_passes(iter_modules(root), [MetricNamePass()])]


def main(argv=None) -> int:
    from cassmantle_tpu.analysis.core import main_for

    return main_for([MetricNamePass()], argv, default_root=PACKAGE,
                    prog="check_metrics")
