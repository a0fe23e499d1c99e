"""RTL7xx — fleet-plane consistency: the string-keyed contracts.

The observability/fleet tier is stitched together by names: a serving
replica registers ``ttft_seconds`` under the ``relora_serve`` namespace, the
collector derives ``relora_serve_ttft_seconds_p95`` from scraped bucket
deltas, and the autoscaler and ``tools/fleet_report.py`` consume that exact
string.  None of that is type-checked — a typo on either side silently yields "no data"
instead of an error.  These rules recover the contract statically by
building the produced-name and consumed-name universes over the whole
project (:class:`~relora_tpu.analysis.core.ProjectIndex`, including the
read-only ``tools/``/``tests/`` context files) and diffing
them.

Produced series = metric registrations (``inc``/``set_gauge``/``observe``/
``materialize_histogram`` literals crossed with every known registry
namespace), direct ``add_sample``/``add_samples`` literals, and the
collector's own derivations (literal and f-string subscript stores in
``parse_prometheus``-consuming modules; a leading f-string constant becomes
a prefix wildcard, a trailing one a derivation suffix like ``_per_s`` whose
base must itself be produced).

- RTL701: consumed series name (``*_SERIES`` constant, ``*_COLUMNS`` table
  row, ``latest``/``window_values``/``samples`` literal, ``series=`` kwarg)
  with no producer.
- RTL702: consumed event kind (``*_KINDS`` constant, ``events(kinds=...)``
  literal) that nothing emits; supervisor-routed kinds are matched through
  the ``supervisor_`` prefixing rule.
- RTL703: counter consumed by a collector delta-derivation that is not
  materialized at zero anywhere (``inc(name, ..., 0)`` / ``by=0``) — the
  derived series silently never exists until the first organic hit.
- RTL704: fault-site name (``faults.configure`` literal or a
  ``RELORA_TPU_FAULTS`` env string) with no check site in
  ``relora_tpu`` (``should``/``maybe_fail``/``crash_point``/``perturb``).
- RTL705: event kind emitted by the fleet plane (``add_event`` /
  ``record_supervisor_event``) that no timeline/report/alert surface
  consumes — dead telemetry, warn-level.

Deliberately out of scope: a never-consumed *series* warn (the collector's
generic ``*_per_s`` derivation consumes every counter, so the vice-versa
check for series is all noise).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from relora_tpu.analysis.core import (
    FileContext,
    Finding,
    ProjectIndex,
    catalog,
    dotted_name,
    get_kwarg,
    project_checker,
)

catalog(
    RTL701="consumed fleet series has no producer (typo'd or dropped registration)",
    RTL702="consumed event kind is never emitted anywhere",
    RTL703="delta-derived counter is not materialized at zero",
    RTL704="fault site is configured but has no check site in utils/faults",
    RTL705="event kind is emitted but no report/alert surface consumes it",
)

METRIC_REG_METHODS = {
    "inc": "counter",
    "set_gauge": "gauge",
    "observe": "hist",
    "materialize_histogram": "hist",
}
FAULT_CHECK_METHODS = frozenset(
    {"should", "maybe_fail", "crash_point", "perturb", "active", "tick"}
)
EVENT_EMITTERS_STRICT = frozenset({"add_event", "record_supervisor_event"})
EVENT_EMITTERS_LOOSE = EVENT_EMITTERS_STRICT | frozenset({"_event", "_emit", "deploy_emit"})

_FAULT_SPEC_RE = re.compile(r"^[a-z_][a-z0-9_]*:[a-z0-9_.]+=")

Anchor = Tuple[str, FileContext, ast.AST]  # (name, owning file, anchor node)


def _const_str(node: Optional[ast.AST]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _str_elts(node: Optional[ast.AST]) -> List[str]:
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    s = _const_str(node)
    return [s] if s is not None else []


def _fstring_parts(node: ast.AST) -> Tuple[str, str, bool]:
    """(leading constant, trailing constant, has dynamic part) of a JoinedStr."""
    if not isinstance(node, ast.JoinedStr) or not node.values:
        return "", "", False
    lead = _const_str(node.values[0]) or ""
    tail = _const_str(node.values[-1]) or ""
    dynamic = any(isinstance(v, ast.FormattedValue) for v in node.values)
    return lead, tail, dynamic


class _Facts:
    def __init__(self) -> None:
        # producers
        self.namespaces: Set[str] = set()
        self.metric_bases: Set[str] = set()
        self.metric_fstring_prefixes: Set[str] = set()
        self.zero_counters: Set[str] = set()
        self.series_exact: Set[str] = set()  # add_sample/add_samples/derived
        self.series_prefixes: Set[str] = set()  # f"healthz_{k}" stores
        self.series_suffixes: Set[str] = set()  # f"{name}_per_s" derivations
        self.events_produced: Set[str] = set()  # loose emitter set
        self.events_strict: List[Anchor] = []  # fleet-plane emissions
        self.fault_sites_known: Set[str] = set()
        # consumers
        self.series_consumed: List[Anchor] = []
        self.events_consumed: List[Anchor] = []
        self.event_prefixes_consumed: Set[str] = set()
        self.counters_consumed: List[Anchor] = []
        self.fault_sites_consumed: List[Anchor] = []


class _FileScan(ast.NodeVisitor):
    def __init__(self, ctx: FileContext, facts: _Facts) -> None:
        self.ctx = ctx
        self.facts = facts
        rel = ctx.relpath
        self.in_pkg = rel.startswith("relora_tpu/")
        #: the production universe: series/event producer AND consumer
        #: surfaces are the package plus tools/ — test fixtures neither
        #: satisfy a production consumer nor get their ad-hoc stores checked
        self.consumer = self.in_pkg or rel.startswith("tools/")
        self.producer = self.consumer
        self.pp_module = "parse_prometheus" in ctx.text
        self.faults_env = "RELORA_TPU_FAULTS" in ctx.text

    # -- assignments: constants, tables, derivation stores -------------------

    def visit_Assign(self, node: ast.Assign) -> None:
        for tgt in node.targets:
            if isinstance(tgt, ast.Name) and self.consumer:
                if tgt.id.endswith("_SERIES"):
                    s = _const_str(node.value)
                    if s:
                        self.facts.series_consumed.append((s, self.ctx, node))
                elif tgt.id.endswith("_COLUMNS"):
                    if isinstance(node.value, (ast.Tuple, ast.List)):
                        for row in node.value.elts:
                            if isinstance(row, (ast.Tuple, ast.List)) and len(row.elts) >= 2:
                                s = _const_str(row.elts[1])
                                if s:
                                    self.facts.series_consumed.append((s, self.ctx, row))
                elif tgt.id.endswith("_KINDS"):
                    for s in _str_elts(node.value):
                        self.facts.events_consumed.append((s, self.ctx, node))
            if isinstance(tgt, ast.Subscript) and self.pp_module and self.producer:
                key = tgt.slice
                s = _const_str(key)
                if s:
                    self.facts.series_exact.add(s)
                else:
                    lead, tail, dynamic = _fstring_parts(key)
                    if dynamic and lead:
                        self.facts.series_prefixes.add(lead)
                    elif dynamic and tail:
                        self.facts.series_suffixes.add(tail)
        self.generic_visit(node)

    # -- defaults: MetricsRegistry namespaces --------------------------------

    def _visit_func(self, node) -> None:
        if node.name == "__init__" and self.producer:
            args = node.args
            defaults = args.defaults
            names = [a.arg for a in args.args]
            for name, default in zip(names[len(names) - len(defaults):], defaults):
                if name == "namespace":
                    s = _const_str(default)
                    if s:
                        self.facts.namespaces.add(s)
        self.generic_visit(node)

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- calls: registrations, stores, consumers, faults ---------------------

    def visit_Call(self, node: ast.Call) -> None:
        basename = ""
        if isinstance(node.func, ast.Attribute):
            basename = node.func.attr
        elif isinstance(node.func, ast.Name):
            basename = node.func.id

        if self.producer:
            ns = _const_str(get_kwarg(node, "namespace"))
            if ns:
                self.facts.namespaces.add(ns)

            if basename in METRIC_REG_METHODS:
                name = _const_str(node.args[0]) if node.args else None
                if name:
                    self.facts.metric_bases.add(name)
                    if basename == "inc" and self._inc_is_zero(node):
                        self.facts.zero_counters.add(name)
                elif node.args:
                    lead, _tail, dynamic = _fstring_parts(node.args[0])
                    if dynamic and lead:
                        self.facts.metric_fstring_prefixes.add(lead)

            if basename == "add_sample" and len(node.args) >= 2:
                s = _const_str(node.args[1])
                if s:
                    self.facts.series_exact.add(s)
            elif basename == "add_samples" and len(node.args) >= 2:
                if isinstance(node.args[1], ast.Dict):
                    for k in node.args[1].keys:
                        s = _const_str(k)
                        if s:
                            self.facts.series_exact.add(s)

            if basename in EVENT_EMITTERS_LOOSE and node.args:
                s = _const_str(node.args[0])
                if s:
                    self.facts.events_produced.add(s)
                    if basename in EVENT_EMITTERS_STRICT and self.in_pkg:
                        self.facts.events_strict.append((s, self.ctx, node))

        if self.consumer:
            if basename in ("latest", "window_values", "samples") and len(node.args) >= 2:
                s = _const_str(node.args[1])
                if s:
                    self.facts.series_consumed.append((s, self.ctx, node))
            series_kw = get_kwarg(node, "series")
            s = _const_str(series_kw)
            if s:
                self.facts.series_consumed.append((s, self.ctx, series_kw))
            if basename == "events":
                kinds = get_kwarg(node, "kinds")
                if kinds is None and node.args:
                    kinds = node.args[0]
                for s in _str_elts(kinds):
                    self.facts.events_consumed.append((s, self.ctx, node))
            if basename == "startswith" and isinstance(node.func, ast.Attribute):
                recv_has_event = any(
                    isinstance(n, ast.Constant) and n.value == "_event"
                    for n in ast.walk(node.func.value)
                )
                if recv_has_event and node.args:
                    for s in _str_elts(node.args[0]):
                        self.facts.event_prefixes_consumed.add(s)

        if self.pp_module and self.producer and basename == "endswith" and node.args:
            for s in _str_elts(node.args[0]):
                if s.endswith("_total") and s != "_total":
                    self.facts.counters_consumed.append((s, self.ctx, node))

        if self.in_pkg and basename in FAULT_CHECK_METHODS and node.args:
            s = _const_str(node.args[0])
            if s:
                self.facts.fault_sites_known.add(s)
        if (
            basename == "get"
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "_FAULTS"
            and node.args
        ):
            s = _const_str(node.args[0])
            if s:
                self.facts.fault_sites_known.add(s)
        if basename == "configure" and node.args:
            dotted = dotted_name(node.func)
            if dotted == "configure" or "faults" in dotted:
                s = _const_str(node.args[0])
                if s:
                    self.facts.fault_sites_consumed.append((s, self.ctx, node))

        self.generic_visit(node)

    @staticmethod
    def _inc_is_zero(node: ast.Call) -> bool:
        by = get_kwarg(node, "by")
        if isinstance(by, ast.Constant) and by.value == 0:
            return True
        if len(node.args) >= 2:
            last = node.args[-1]
            if isinstance(last, ast.Constant) and last.value == 0:
                return True
        return False

    # -- `"X_total." in name` membership tests (RTL703 consumers) ------------

    def visit_Compare(self, node: ast.Compare) -> None:
        if self.pp_module and self.producer and any(
            isinstance(op, ast.In) for op in node.ops
        ):
            s = _const_str(node.left)
            if s and s.endswith("_total.") and s != "_total.":
                self.facts.counters_consumed.append((s[:-1], self.ctx, node))
        self.generic_visit(node)

    # -- RELORA_TPU_FAULTS env strings (RTL704 consumers) --------------------

    def visit_Constant(self, node: ast.Constant) -> None:
        if (
            self.faults_env
            and isinstance(node.value, str)
            and _FAULT_SPEC_RE.match(node.value)
        ):
            for part in node.value.split(";"):
                site = part.split(":", 1)[0].strip()
                if site:
                    self.facts.fault_sites_consumed.append((site, self.ctx, node))

    def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
        if self.faults_env:
            lead = _const_str(node.values[0]) if node.values else None
            if lead and _FAULT_SPEC_RE.match(lead):
                site = lead.split(":", 1)[0]
                self.facts.fault_sites_consumed.append((site, self.ctx, node))
        self.generic_visit(node)


def collect_facts(index: ProjectIndex) -> _Facts:
    facts = _Facts()
    for relpath in sorted(index.contexts):
        ctx = index.contexts[relpath]
        _FileScan(ctx, facts).visit(ctx.tree)
    return facts


def _series_produced(facts: _Facts, name: str, _depth: int = 0) -> bool:
    if name in facts.series_exact:
        return True
    namespaced = {
        f"{ns}_{base}" for ns in facts.namespaces for base in facts.metric_bases
    }
    if name in namespaced:
        return True
    prefixes = set(facts.series_prefixes)
    prefixes.update(
        f"{ns}_{p}" for ns in facts.namespaces for p in facts.metric_fstring_prefixes
    )
    prefixes.update(facts.metric_fstring_prefixes)
    if any(name.startswith(p) for p in prefixes):
        return True
    if _depth == 0:
        for suf in facts.series_suffixes:
            if name.endswith(suf) and len(name) > len(suf):
                if _series_produced(facts, name[: -len(suf)], _depth=1):
                    return True
    return False


def _event_produced(facts: _Facts, kind: str) -> bool:
    if kind in facts.events_produced:
        return True
    # the collector's supervisor routing prefixes non-deploy/autoscale kinds
    if kind.startswith("supervisor_") and kind[len("supervisor_"):] in facts.events_produced:
        return True
    return False


def _event_consumed(facts: _Facts, kind: str) -> bool:
    consumed = {k for k, _ctx, _n in facts.events_consumed}
    for k in (kind, f"supervisor_{kind}"):
        if k in consumed:
            return True
        if any(k.startswith(p) for p in facts.event_prefixes_consumed):
            return True
    return False


def fleet_findings(index: ProjectIndex) -> List[Finding]:
    """The full RTL7xx pass over an index; exposed for fixture tests."""
    facts = collect_facts(index)
    findings: List[Finding] = []

    for name, ctx, node in facts.series_consumed:
        if not _series_produced(facts, name):
            findings.append(
                ctx.finding(
                    node,
                    "RTL701",
                    f"series '{name}' is consumed here but no registration, "
                    "gauge, sample store, or collector derivation produces "
                    "it — typo or dropped producer",
                )
            )

    for kind, ctx, node in facts.events_consumed:
        if not _event_produced(facts, kind):
            findings.append(
                ctx.finding(
                    node,
                    "RTL702",
                    f"event kind '{kind}' is consumed here but nothing emits "
                    "it (add_event/record_supervisor_event)",
                )
            )

    for name, ctx, node in facts.counters_consumed:
        if name not in facts.zero_counters:
            findings.append(
                ctx.finding(
                    node,
                    "RTL703",
                    f"counter '{name}' feeds a delta derivation but is never "
                    "materialized at zero (inc(..., 0) / by=0) — the derived "
                    "series does not exist until the first organic hit",
                )
            )

    for site, ctx, node in facts.fault_sites_consumed:
        if site not in facts.fault_sites_known:
            findings.append(
                ctx.finding(
                    node,
                    "RTL704",
                    f"fault site '{site}' is configured but has no "
                    "should/maybe_fail/crash_point/perturb check site in "
                    "relora_tpu — the injection silently never fires",
                )
            )

    seen_warn: Set[str] = set()
    for kind, ctx, node in facts.events_strict:
        if kind in seen_warn:
            continue
        if not _event_consumed(facts, kind):
            seen_warn.add(kind)
            findings.append(
                ctx.finding(
                    node,
                    "RTL705",
                    f"event kind '{kind}' is emitted but no timeline/report/"
                    "alert surface consumes it — dead telemetry (wire it into "
                    "a _KINDS table or drop the emission)",
                )
            )
    return findings


@project_checker
def check_fleet_consistency(index: ProjectIndex) -> List[Finding]:
    return fleet_findings(index)
