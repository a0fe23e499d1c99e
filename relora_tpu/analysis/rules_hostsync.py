"""RTL2xx — host synchronization in hot paths.

JAX dispatch is async: the train/decode loops stay fast only while the host
keeps feeding the device without ever waiting on it.  One ``.item()`` per
step serializes host and device, which is invisible in profiles of either side alone —
exactly the silent LoRA-overhead class measured by Run LoRA Run
(arXiv:2312.03415).  Hot regions are defined in
:mod:`relora_tpu.analysis.hotpaths`.

- RTL201: ``.item()`` in a hot function.
- RTL202: ``float()``/``int()`` on a computed value (call / subscript /
  non-static attribute) in a hot function — scalar device pull.  Plain
  names, literals and ``.shape``/``.size``/``.ndim`` reads are static and
  fine.
- RTL203: ``block_until_ready`` in a hot function.
- RTL204: ``np.asarray`` / ``np.array`` / ``jax.device_get`` in a hot
  function — whole-array device pull.  (``jnp.asarray`` is host-to-device
  and fine.)

The sanctioned fix is to accumulate device values and materialize them in
ONE bulk transfer at a logging/metrics-cadence boundary, in a helper that
lives outside the hot functions (see ``train/trainer._pull_metric_records``).
"""

from __future__ import annotations

import ast
from typing import List

from relora_tpu.analysis.core import (
    FileContext,
    Finding,
    catalog,
    checker,
    dotted_name,
    get_module_index,
)
from relora_tpu.analysis.hotpaths import hot_prefixes, qualname_is_hot

catalog(
    RTL201=".item() in a hot function (per-step device->host round trip)",
    RTL202="float()/int() on a computed value in a hot function (scalar device pull)",
    RTL203="block_until_ready in a hot function (serializes host and device)",
    RTL204="np.asarray/np.array/jax.device_get in a hot function (device->host transfer)",
)

STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size"})
HOST_ONLY_CALLS = frozenset(
    {
        "len",
        "min",
        "max",
        "round",
        "abs",
        "sum",
        "time.time",
        "time.monotonic",
        "time.perf_counter",
        "time.time_ns",
        "os.environ.get",
        "os.getenv",
    }
)
PULL_CALLS = frozenset(
    {"np.asarray", "np.array", "numpy.asarray", "numpy.array", "onp.asarray", "onp.array"}
)


def _is_static_scalar_arg(arg: ast.AST) -> bool:
    """True when float(arg)/int(arg) cannot be a device pull: names,
    literals, static attributes, host-only calls."""
    if isinstance(arg, (ast.Name, ast.Constant)):
        return True
    if isinstance(arg, ast.Attribute) and arg.attr in STATIC_ATTRS:
        return True
    if isinstance(arg, ast.Call) and dotted_name(arg.func) in HOST_ONLY_CALLS:
        return True
    if isinstance(arg, (ast.BinOp, ast.UnaryOp)):
        return all(
            _is_static_scalar_arg(child)
            for child in ast.iter_child_nodes(arg)
            if isinstance(child, ast.expr)
        )
    return False


class _HotVisitor(ast.NodeVisitor):
    def __init__(self, ctx: FileContext, prefixes) -> None:
        self.ctx = ctx
        self.prefixes = prefixes
        self.stack: List[str] = []
        self.findings: List[Finding] = []

    @property
    def hot(self) -> bool:
        return qualname_is_hot(".".join(self.stack), self.prefixes)

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

    def visit_Call(self, node: ast.Call) -> None:
        if self.hot:
            name = dotted_name(node.func)
            if isinstance(node.func, ast.Attribute):
                attr = node.func.attr
                if attr == "item" and not node.args:
                    self.findings.append(
                        self.ctx.finding(
                            node,
                            "RTL201",
                            ".item() in a hot function — per-step host round "
                            "trip; accumulate device-side and pull in bulk at "
                            "the logging cadence",
                        )
                    )
                elif attr == "block_until_ready":
                    self.findings.append(
                        self.ctx.finding(
                            node,
                            "RTL203",
                            "block_until_ready in a hot function — serializes "
                            "host and device every step",
                        )
                    )
            if name in PULL_CALLS or name in ("jax.device_get", "device_get"):
                self.findings.append(
                    self.ctx.finding(
                        node,
                        "RTL204",
                        f"{name} in a hot function — device->host transfer; "
                        "batch reads at the logging/metrics cadence in a "
                        "non-hot helper",
                    )
                )
            elif (
                name in ("float", "int")
                and len(node.args) == 1
                and not _is_static_scalar_arg(node.args[0])
            ):
                self.findings.append(
                    self.ctx.finding(
                        node,
                        "RTL202",
                        f"{name}() on a computed value in a hot function — "
                        "scalar device pull per step; batch reads at the "
                        "logging cadence",
                    )
                )
        self.generic_visit(node)


def _propagated_prefixes(ctx: FileContext, prefixes) -> List[str]:
    """One-level call-graph propagation: a helper invoked *unconditionally*
    from a hot function is hot too (it runs every step).  Conditional calls
    are exempt — that is exactly the sanctioned cadence-gating idiom
    (``if len(pending) >= log_every: self._pull_metric_records(...)``), so
    the gate stays meaningful.  One level only, same module only."""
    mi = get_module_index(ctx)
    extra = set()
    for qualname in mi.functions:
        if not qualname_is_hot(qualname, prefixes):
            continue
        # a closure nested in a hot function only propagates if the closure
        # itself is invoked unconditionally there: a cadence-gated flush
        # closure (`if pending >= log_every: flush()`) must not drag the
        # sanctioned bulk-pull helper into the hot set
        parent = qualname.rsplit(".", 1)[0] if "." in qualname else ""
        if (
            parent in mi.functions
            and qualname_is_hot(parent, prefixes)
            and qualname not in mi.uncond_calls.get(parent, set())
        ):
            continue
        for callee in mi.uncond_calls.get(qualname, ()):
            if not qualname_is_hot(callee, prefixes):
                extra.add(callee)
    return list(prefixes) + sorted(extra)


@checker
def check_hostsync(ctx: FileContext) -> List[Finding]:
    prefixes = hot_prefixes(ctx)
    if not prefixes:
        return []
    if "" not in prefixes:
        prefixes = _propagated_prefixes(ctx, prefixes)
    visitor = _HotVisitor(ctx, prefixes)
    visitor.visit(ctx.tree)
    return visitor.findings
