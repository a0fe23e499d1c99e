"""Rule engine for the RTL footgun linter (stdlib-``ast``, no deps).

The analysis is organized as *checkers* — functions ``(FileContext) ->
Iterable[Finding]`` registered with :func:`checker` — each of which may emit
findings for one or more rule codes declared in :data:`RULE_CATALOG`.  A
finding is identified for suppression purposes by ``(relpath, code,
stripped source line)``: line *text*, not line *number*, so baselines
survive unrelated edits above the finding.

Two suppression layers:

- inline ``# noqa: RTL###`` (or a bare ``# noqa``) on the offending line,
  for one-off intentional violations that a reader of the code should see;
- the checked-in baseline file (``tools/lint_baseline.txt``) for
  grandfathered findings, one per line with a mandatory justification::

      relora_tpu/train/trainer.py | RTL203 | jax.block_until_ready(...) | merge cadence, timed for logging

  New findings (not baselined, not noqa'd) fail the lint.  Baseline entries
  that no longer match anything are reported as stale so the file must
  shrink as violations are fixed, never silently rot.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, FrozenSet, Iterable, List, Optional, Sequence, Union

# code -> one-line summary; every Finding.code must be declared here
RULE_CATALOG: Dict[str, str] = {}
CHECKERS: List[Callable[["FileContext"], Iterable["Finding"]]] = []
#: project-wide checkers ``(ProjectIndex) -> Iterable[Finding]``; run once per
#: lint_paths invocation when the scan covers the package (see lint_paths)
PROJECT_CHECKERS: List[Callable[["ProjectIndex"], Iterable["Finding"]]] = []

#: sentinel for a bare ``# noqa`` (suppresses every rule on that line)
ALL_CODES: FrozenSet[str] = frozenset({"*"})

_NOQA_RE = re.compile(
    r"#\s*noqa(?::\s*(?P<codes>RTL\d+(?:\s*,\s*RTL\d+)*))?", re.IGNORECASE
)


def catalog(**rules: str) -> None:
    """Declare rule codes (``RTL101="summary"``); called at module import."""
    for code, summary in rules.items():
        RULE_CATALOG[code] = summary


def checker(fn: Callable[["FileContext"], Iterable["Finding"]]):
    CHECKERS.append(fn)
    return fn


def project_checker(fn: Callable[["ProjectIndex"], Iterable["Finding"]]):
    PROJECT_CHECKERS.append(fn)
    return fn


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str  # repo-relative, posix separators
    line: int
    code: str
    message: str
    line_text: str  # stripped source of the offending line (baseline identity)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"


class FileContext:
    """One parsed file plus the per-line suppression map."""

    def __init__(self, path: str, relpath: str, text: str, force_hot: bool = False):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=relpath)
        self.force_hot = force_hot
        self._noqa: Dict[int, FrozenSet[str]] = {}
        for i, line in enumerate(self.lines, 1):
            m = _NOQA_RE.search(line)
            if m:
                codes = m.group("codes")
                self._noqa[i] = (
                    frozenset(c.strip().upper() for c in codes.split(","))
                    if codes
                    else ALL_CODES
                )

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, lineno: int, code: str) -> bool:
        codes = self._noqa.get(lineno)
        return codes is not None and (codes is ALL_CODES or code in codes)

    def finding(self, node: ast.AST, code: str, message: str) -> Finding:
        assert code in RULE_CATALOG, f"undeclared rule code {code}"
        lineno = getattr(node, "lineno", 1)
        return Finding(self.relpath, lineno, code, message, self.line_text(lineno))


# ---------------------------------------------------------------------------
# baseline


@dataclasses.dataclass
class BaselineEntry:
    path: str
    code: str
    snippet: str
    justification: str
    lineno: int  # line in the baseline file (for stale reports)

    def matches(self, f: Finding) -> bool:
        return (
            f.path == self.path and f.code == self.code and f.line_text == self.snippet
        )


def load_baseline(path: str) -> List[BaselineEntry]:
    entries: List[BaselineEntry] = []
    with open(path, encoding="utf-8") as fh:
        for lineno, raw in enumerate(fh, 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            parts = [p.strip() for p in line.split("|", 3)]
            if len(parts) != 4 or not parts[3]:
                raise ValueError(
                    f"{path}:{lineno}: baseline entries are "
                    f"'path | RTL### | source line | justification' "
                    f"(justification is mandatory)"
                )
            entries.append(BaselineEntry(parts[0], parts[1], parts[2], parts[3], lineno))
    return entries


def format_baseline_entry(f: Finding, justification: str = "TODO: justify") -> str:
    return f"{f.path} | {f.code} | {f.line_text} | {justification}"


# ---------------------------------------------------------------------------
# runner


@dataclasses.dataclass
class Report:
    findings: List[Finding]  # everything the rules produced (pre-suppression)
    new: List[Finding]  # not noqa'd, not baselined -> these fail the lint
    noqa_suppressed: int
    baselined: int
    stale_baseline: List[BaselineEntry]
    files_scanned: int
    parse_errors: List[str]

    @property
    def rule_counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = {}
        for f in self.findings:
            counts[f.code] = counts.get(f.code, 0) + 1
        return dict(sorted(counts.items()))


def lint_context(ctx: FileContext) -> List[Finding]:
    found: List[Finding] = []
    for check in CHECKERS:
        found.extend(check(ctx))
    return sorted(found, key=lambda f: (f.path, f.line, f.code))


def lint_text(
    text: str, relpath: str = "<text>", *, force_hot: bool = False
) -> List[Finding]:
    """Lint a source string (fixture/test entry point).  Returns raw
    findings; ``# noqa`` suppression is applied, the baseline is not."""
    ctx = FileContext(relpath, relpath, text, force_hot=force_hot)
    return [f for f in lint_context(ctx) if not ctx.suppressed(f.line, f.code)]


def _iter_py_files(path: str) -> Iterable[str]:
    if os.path.isfile(path):
        yield path
        return
    skip = {".git", "__pycache__", ".venv", "node_modules", "build", "dist"}
    for dirpath, dirnames, filenames in os.walk(path):
        dirnames[:] = sorted(d for d in dirnames if d not in skip)
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_paths(
    paths: Sequence[str],
    *,
    root: Optional[str] = None,
    baseline: Union[str, Sequence[BaselineEntry], None] = None,
    project: Optional[bool] = None,
) -> Report:
    """Lint files/trees; relpaths (finding + baseline identity) are taken
    relative to ``root`` (default: cwd).

    ``project`` controls the whole-repo pass (PROJECT_CHECKERS: call graph +
    RTL7xx fleet consistency).  The default (None) auto-enables it when the
    scan set includes the fleet plane (:data:`PROJECT_SENTINEL`) — i.e. a
    real package scan, not a one-off fixture file — because the consistency
    rules are meaningless against a partial producer/consumer universe."""
    root = os.path.abspath(root or os.getcwd())
    entries: List[BaselineEntry] = []
    if isinstance(baseline, str):
        entries = load_baseline(baseline)
    elif baseline:
        entries = list(baseline)

    all_findings: List[Finding] = []
    new: List[Finding] = []
    noqa_count = 0
    baselined_count = 0
    used = [False] * len(entries)
    files = 0
    parse_errors: List[str] = []
    contexts: Dict[str, FileContext] = {}

    def classify(f: Finding, ctx: Optional[FileContext]) -> None:
        nonlocal noqa_count, baselined_count
        all_findings.append(f)
        if ctx is not None and ctx.suppressed(f.line, f.code):
            noqa_count += 1
            return
        for i, entry in enumerate(entries):
            if entry.matches(f):
                used[i] = True
                baselined_count += 1
                return
        new.append(f)

    for path in paths:
        for fpath in _iter_py_files(path):
            abspath = os.path.abspath(fpath)
            relpath = os.path.relpath(abspath, root)
            try:
                with open(abspath, encoding="utf-8") as fh:
                    text = fh.read()
                ctx = FileContext(abspath, relpath, text)
            except (SyntaxError, UnicodeDecodeError) as e:
                parse_errors.append(f"{relpath}: {e}")
                continue
            files += 1
            contexts[ctx.relpath] = ctx
            for f in lint_context(ctx):
                classify(f, ctx)

    if project is None:
        project = PROJECT_SENTINEL in contexts
    if project and PROJECT_CHECKERS:
        extra: Dict[str, FileContext] = {}
        for name in PROJECT_CONTEXT_GLOBS:
            for fpath in _iter_py_files(os.path.join(root, name)):
                relpath = os.path.relpath(fpath, root).replace(os.sep, "/")
                if relpath in contexts:
                    continue
                try:
                    with open(fpath, encoding="utf-8") as fh:
                        extra[relpath] = FileContext(fpath, relpath, fh.read())
                except (OSError, SyntaxError, UnicodeDecodeError):
                    continue  # context files are best-effort, never fatal
        index = ProjectIndex(contexts, extra)
        by_path = index.contexts
        project_findings: List[Finding] = []
        for check in PROJECT_CHECKERS:
            project_findings.extend(check(index))
        for f in sorted(project_findings, key=lambda f: (f.path, f.line, f.code)):
            classify(f, by_path.get(f.path))

    stale = [e for e, u in zip(entries, used) if not u]
    return Report(
        findings=all_findings,
        new=sorted(new, key=lambda f: (f.path, f.line, f.code)),
        noqa_suppressed=noqa_count,
        baselined=baselined_count,
        stale_baseline=stale,
        files_scanned=files,
        parse_errors=parse_errors,
    )


# ---------------------------------------------------------------------------
# shared AST helpers used by the rule modules


def dotted_name(node: ast.AST) -> str:
    """'jax.random.PRNGKey' for nested Attribute/Name chains, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def target_path(node: ast.AST) -> str:
    """Dotted path for assignable/loadable chains rooted at a Name
    ('self.state.params'); '' for anything else (calls, subscripts...)."""
    return dotted_name(node)


def const_int_set(node: ast.AST) -> Optional[FrozenSet[int]]:
    """The set of ints in a literal int / tuple-or-list-of-ints, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = set()
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                vals.add(elt.value)
            else:
                return None
        return frozenset(vals)
    return None


def const_str_set(node: ast.AST) -> Optional[FrozenSet[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = set()
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                vals.add(elt.value)
            else:
                return None
        return frozenset(vals)
    return None


def get_kwarg(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


JIT_NAMES = frozenset({"jit", "jax.jit", "pjit", "jax.experimental.pjit.pjit"})


def is_jit_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and dotted_name(node.func) in JIT_NAMES


def unwrap_partial(node: ast.AST) -> Optional[ast.Call]:
    """``functools.partial(jax.jit, ...)`` / ``partial(jit, ...)`` as a
    pseudo jit-Call (kwargs of the partial are the jit kwargs)."""
    if (
        isinstance(node, ast.Call)
        and dotted_name(node.func) in ("partial", "functools.partial")
        and node.args
        and dotted_name(node.args[0]) in JIT_NAMES
    ):
        return node
    return None


class QualnameVisitor(ast.NodeVisitor):
    """Base visitor tracking the dotted qualname of the enclosing
    function/class scope ('Trainer.fit.flush_pending')."""

    def __init__(self) -> None:
        self.stack: List[str] = []

    @property
    def qualname(self) -> str:
        return ".".join(self.stack)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def _visit_func(self, node) -> None:
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func


# ---------------------------------------------------------------------------
# module index: per-file symbol table, call graph, thread roots
#
# The interprocedural layer under the RTL6xx/RTL7xx families and the
# one-level RTL2xx propagation.  Resolution is deliberately conservative
# (module-qualified names only, no MRO, no data flow): an unresolved call is
# simply not an edge, so the derived facts (reachability, thread roots) err
# toward missing edges rather than inventing them — precision over recall,
# per docs/static-analysis.md.

THREAD_FACTORIES = frozenset({"threading.Thread", "Thread", "threading.Timer", "Timer"})
LOCK_FACTORIES = frozenset(
    {
        "threading.Lock",
        "threading.RLock",
        "threading.Condition",
        "Lock",
        "RLock",
        "Condition",
    }
)

#: root kinds that run on their own OS thread (vs the main/event-loop thread)
SPAWNED_ROOT_KINDS = frozenset({"thread", "executor"})


@dataclasses.dataclass
class FunctionInfo:
    qualname: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef
    is_async: bool
    owner_class: str  # qualname of the innermost enclosing class, "" if none
    lineno: int


class _ModuleIndexBuilder(QualnameVisitor):
    def __init__(self) -> None:
        super().__init__()
        self.class_stack: List[str] = []
        self.functions: Dict[str, FunctionInfo] = {}
        self.classes: Dict[str, ast.ClassDef] = {}
        # (caller_qualname, dotted_callee, unconditional)
        self.calls_raw: List = []
        # (dotted_target, kind, lineno, registering caller qualname)
        self.root_targets_raw: List = []
        # class qualname -> attr -> dotted factory name of `self.X = Factory()`
        self.attr_types: Dict[str, Dict[str, str]] = {}
        # module-level `name = Factory()`
        self.module_types: Dict[str, str] = {}
        self.imports: Dict[str, str] = {}  # alias -> module dotted path
        self.from_imports: Dict[str, tuple] = {}  # name -> (module, orig name)
        self._branch_depth = 0
        self._func_entry_depth: List[int] = []

    # -- scope bookkeeping ---------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.stack.append(node.name)
        self.class_stack.append(self.qualname)
        self.classes[self.qualname] = node
        self.generic_visit(node)
        self.class_stack.pop()
        self.stack.pop()

    def _visit_func(self, node) -> None:
        self.stack.append(node.name)
        qn = self.qualname
        is_async = isinstance(node, ast.AsyncFunctionDef)
        self.functions[qn] = FunctionInfo(
            qualname=qn,
            node=node,
            is_async=is_async,
            owner_class=self.class_stack[-1] if self.class_stack else "",
            lineno=node.lineno,
        )
        if is_async:
            self.root_targets_raw.append((qn, "async", node.lineno, qn))
        self._func_entry_depth.append(self._branch_depth)
        self.generic_visit(node)
        self._func_entry_depth.pop()
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_If(self, node: ast.If) -> None:
        self._branch_depth += 1
        self.generic_visit(node)
        self._branch_depth -= 1

    # -- facts ---------------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            self.imports[alias.asname or alias.name.split(".")[0]] = alias.name

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module and not node.level:
            for alias in node.names:
                self.from_imports[alias.asname or alias.name] = (
                    node.module,
                    alias.name,
                )

    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Call):
            factory = dotted_name(node.value.func)
            if factory:
                for tgt in node.targets:
                    path = target_path(tgt)
                    if path.startswith("self.") and path.count(".") == 1:
                        cls = self.class_stack[-1] if self.class_stack else ""
                        if cls:
                            self.attr_types.setdefault(cls, {})[
                                path.split(".", 1)[1]
                            ] = factory
                    elif path and "." not in path and not self.stack:
                        self.module_types[path] = factory
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        caller = self.qualname
        dotted = dotted_name(node.func)
        if dotted:
            uncond = (
                bool(self._func_entry_depth)
                and self._branch_depth == self._func_entry_depth[-1]
            )
            self.calls_raw.append((caller, dotted, uncond))
        # thread/executor/signal entry points
        basename = dotted.rsplit(".", 1)[-1] if dotted else (
            node.func.attr if isinstance(node.func, ast.Attribute) else ""
        )
        target: Optional[ast.AST] = None
        kind = ""
        if dotted in THREAD_FACTORIES:
            target, kind = get_kwarg(node, "target"), "thread"
        elif basename == "run_in_executor" and len(node.args) >= 2:
            target, kind = node.args[1], "executor"
        elif dotted == "signal.signal" and len(node.args) >= 2:
            target, kind = node.args[1], "signal"
        elif basename == "add_signal_handler" and len(node.args) >= 2:
            target, kind = node.args[1], "signal"
        if target is not None and kind:
            tgt_dotted = dotted_name(target)
            if tgt_dotted:
                self.root_targets_raw.append((tgt_dotted, kind, node.lineno, caller))
        self.generic_visit(node)


class ModuleIndex:
    """Symbol table + call graph for one parsed module."""

    def __init__(self, ctx: FileContext) -> None:
        b = _ModuleIndexBuilder()
        b.visit(ctx.tree)
        self.ctx = ctx
        self.relpath = ctx.relpath
        self.functions = b.functions
        self.classes = b.classes
        self.attr_types = b.attr_types
        self.module_types = b.module_types
        self.imports = b.imports
        self.from_imports = b.from_imports
        self.calls: Dict[str, set] = {}  # caller -> resolved local callees
        self.uncond_calls: Dict[str, set] = {}
        self.raw_calls: Dict[str, set] = {}  # caller -> dotted callee names
        for caller, dotted, uncond in b.calls_raw:
            self.raw_calls.setdefault(caller, set()).add(dotted)
            resolved = self.resolve_local(dotted, caller)
            if resolved is not None:
                self.calls.setdefault(caller, set()).add(resolved)
                if uncond:
                    self.uncond_calls.setdefault(caller, set()).add(resolved)
        #: qualname -> root kind ("thread" | "executor" | "signal" | "async")
        self.thread_roots: Dict[str, str] = {}
        for tgt, kind, _lineno, caller in b.root_targets_raw:
            if kind == "async":
                self.thread_roots.setdefault(tgt, "async")
                continue
            resolved = self.resolve_local(tgt, caller)
            if resolved is None and tgt in self.functions:
                resolved = tgt
            if resolved is not None:
                self.thread_roots[resolved] = kind

    def resolve_local(self, dotted: str, caller: str) -> Optional[str]:
        """Module-local qualname for a dotted callee, or None.  Handles
        ``self.m``/``cls.m`` (innermost enclosing class of *caller*), bare
        names (lexical scope chain, then module level), and already-qualified
        ``Class.method`` paths."""
        if not dotted:
            return None
        parts = dotted.split(".")
        if parts[0] in ("self", "cls"):
            info = self.functions.get(caller)
            cls = info.owner_class if info else ""
            if cls and len(parts) == 2:
                cand = f"{cls}.{parts[1]}"
                if cand in self.functions:
                    return cand
            return None
        if len(parts) == 1:
            scope = caller
            while scope:
                cand = f"{scope}.{parts[0]}"
                if cand in self.functions:
                    return cand
                scope = scope.rsplit(".", 1)[0] if "." in scope else ""
            return parts[0] if parts[0] in self.functions else None
        return dotted if dotted in self.functions else None

    def reachable(self, roots: Iterable[str]) -> set:
        """Transitive closure over resolved module-local call edges."""
        seen = set()
        work = [r for r in roots if r in self.functions]
        while work:
            qn = work.pop()
            if qn in seen:
                continue
            seen.add(qn)
            work.extend(self.calls.get(qn, ()))
        return seen


def get_module_index(ctx: FileContext) -> ModuleIndex:
    """Build (and cache on the context) the module's symbol table."""
    idx = getattr(ctx, "_module_index", None)
    if idx is None:
        idx = ModuleIndex(ctx)
        ctx._module_index = idx  # type: ignore[attr-defined]
    return idx


# ---------------------------------------------------------------------------
# project index: the whole-repo pass the RTL7xx family runs over


def _module_relpath(dotted: str) -> str:
    return dotted.replace(".", "/") + ".py"


class ProjectIndex:
    """All scanned modules plus read-only *context* modules (tools/,
    tests/): consumer surfaces the fleet-consistency rules must see even
    though only the package itself is being linted.  Findings may anchor in
    either set; ``# noqa`` works in both."""

    def __init__(
        self,
        contexts: Dict[str, FileContext],
        extra: Optional[Dict[str, FileContext]] = None,
    ) -> None:
        self.scanned = dict(contexts)
        self.extra = dict(extra or {})

    @property
    def contexts(self) -> Dict[str, FileContext]:
        merged = dict(self.scanned)
        merged.update(self.extra)
        return merged

    def module(self, relpath: str) -> Optional[ModuleIndex]:
        ctx = self.scanned.get(relpath) or self.extra.get(relpath)
        return get_module_index(ctx) if ctx else None

    def modules(self) -> Iterable[ModuleIndex]:
        for relpath in sorted(self.contexts):
            idx = self.module(relpath)
            if idx is not None:
                yield idx

    def resolve_import(self, relpath: str, dotted: str):
        """Cross-module resolution of ``alias.func`` / from-imported names:
        returns ``(target_relpath, qualname)`` or None."""
        idx = self.module(relpath)
        if idx is None or not dotted:
            return None
        parts = dotted.split(".")
        if parts[0] in idx.from_imports and len(parts) <= 2:
            mod, orig = idx.from_imports[parts[0]]
            target_rel = _module_relpath(mod)
            target = self.module(target_rel)
            qual = ".".join([orig] + parts[1:])
            if target is not None and qual in target.functions:
                return target_rel, qual
        if parts[0] in idx.imports and len(parts) >= 2:
            mod = idx.imports[parts[0]]
            target_rel = _module_relpath(mod)
            target = self.module(target_rel)
            qual = ".".join(parts[1:])
            if target is not None and qual in target.functions:
                return target_rel, qual
        return None

    def call_graph_dump(self) -> str:
        """Debug rendering for ``--call-graph-dump``: thread roots and
        resolved edges per module."""
        out: List[str] = []
        for idx in self.modules():
            if not idx.functions:
                continue
            out.append(f"== {idx.relpath} ==")
            for qn, kind in sorted(idx.thread_roots.items()):
                out.append(f"  root[{kind}] {qn}")
            for caller in sorted(idx.calls):
                for callee in sorted(idx.calls[caller]):
                    out.append(f"  {caller or '<module>'} -> {callee}")
        return "\n".join(out)


def build_project_index(files: Dict[str, str]) -> ProjectIndex:
    """Fixture entry point: build a ProjectIndex from {relpath: source}."""
    contexts = {
        rel: FileContext(rel, rel, text) for rel, text in sorted(files.items())
    }
    return ProjectIndex(contexts)


#: repo-root files/dirs pulled in as read-only context for the project pass
PROJECT_CONTEXT_GLOBS = ("tools", "tests")
#: the project pass only makes sense when the fleet plane is in the scan set
PROJECT_SENTINEL = "relora_tpu/obs/fleet.py"
