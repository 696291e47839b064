"""Import-hygiene: ONE declared jax-free module set, enforced two ways.

The repo's host tier — telemetry bookkeeping, the serving policy layer,
the log-reading CLIs — must import without jax/flax: the TTFT bench
bills every worker's import chain, and routers/monitoring boxes have no
accelerator stack. Until now that contract lived as a hand-maintained
probe list in ``tests/test_imports.py``, which every PR had to extend by
hand (and PR 11 did, again). This module is the single source of truth:

- ``JAX_FREE_MODULES`` — modules that must import with no jax/flax/optax
  anywhere in their *static* import closure;
- ``PALLAS_FREE_MODULES`` — modules that may pull jax but must defer
  pallas to first trace (pallas costs ~0.2 s at import).

``tests/test_imports.py`` derives its subprocess probes from these
tuples, and ``accelerate-tpu audit`` additionally *statically* walks the
module-level import graph (AST; function-local and ``TYPE_CHECKING``
imports are lazy by construction and excluded) so a violating import is
a finding with the exact chain that reaches the heavy module — before
any interpreter pays for it.

Stdlib only (ast/os) — this module is a member of its own declared set.
"""

from __future__ import annotations

import ast
import os
from typing import Optional

from .findings import Finding

# modules whose import must never pull any HEAVY_MODULES member. Adding a
# host-side module here is the whole ceremony: the static audit check and
# the subprocess import test both pick it up from this tuple.
JAX_FREE_MODULES = (
    "accelerate_tpu",
    "accelerate_tpu.telemetry",
    "accelerate_tpu.telemetry.requests",
    "accelerate_tpu.telemetry.histograms",
    "accelerate_tpu.telemetry.exporter",
    "accelerate_tpu.telemetry.recorder",
    "accelerate_tpu.telemetry.forensics",
    "accelerate_tpu.telemetry.goodput",
    "accelerate_tpu.telemetry.costs",
    "accelerate_tpu.telemetry.timeline",
    "accelerate_tpu.telemetry.alerts",
    "accelerate_tpu.telemetry.usage",
    "accelerate_tpu.telemetry.fleet",
    "accelerate_tpu.telemetry.canary",
    "accelerate_tpu.telemetry.waterfall",
    "accelerate_tpu.telemetry.scorecard",
    "accelerate_tpu.telemetry.capacity",
    "accelerate_tpu.telemetry.artifacts",
    "accelerate_tpu.telemetry.incidents",
    "accelerate_tpu.serving.pages",
    "accelerate_tpu.serving.tiers",
    "accelerate_tpu.serving.scheduler",
    "accelerate_tpu.serving.faults",
    "accelerate_tpu.serving.router",
    "accelerate_tpu.serving.replica_server",
    "accelerate_tpu.serving.loadgen",
    "accelerate_tpu.serving.autoscaler",
    "accelerate_tpu.commands.trace",
    "accelerate_tpu.commands.incident",
    "accelerate_tpu.commands.report",
    "accelerate_tpu.commands.watch",
    "accelerate_tpu.commands.audit",
    "accelerate_tpu.commands.serve",
    "accelerate_tpu.commands.loadtest",
    "accelerate_tpu.commands.autoscale",
    "accelerate_tpu.analysis",
    "accelerate_tpu.analysis.findings",
    "accelerate_tpu.analysis.hygiene",
    "accelerate_tpu.analysis.host_lint",
)

# modules that import jax by design but must stay pallas-free at import
# time (the decode-kernel _LazyModule contract, PR 8)
PALLAS_FREE_MODULES = (
    "accelerate_tpu.ops",
    "accelerate_tpu.ops.attention",
    "accelerate_tpu.serving.engine",
)

HEAVY_MODULES = ("jax", "flax", "optax")
PALLAS_MARKER = "pallas"


def repo_root() -> str:
    """Directory that holds the ``accelerate_tpu`` package."""
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def module_file(name: str, root: str) -> Optional[str]:
    """Source file of a repo-internal module name (None for externals)."""
    base = os.path.join(root, *name.split("."))
    for cand in (base + ".py", os.path.join(base, "__init__.py")):
        if os.path.isfile(cand):
            return cand
    return None


def _is_type_checking_guard(test: ast.expr) -> bool:
    node = test
    return (isinstance(node, ast.Name) and node.id == "TYPE_CHECKING") or (
        isinstance(node, ast.Attribute) and node.attr == "TYPE_CHECKING"
    )


def imports_of_source(src: str, module: str, is_package: bool) -> list:
    """Absolute dotted names imported when ``module``'s body executes.

    Only statements that run at import time count: module scope, class
    bodies, module-level ``try``/``if`` arms — but not function bodies
    (the PEP 562 lazy idiom) and not ``if TYPE_CHECKING:`` arms. A
    ``from X import Y`` contributes both ``X`` and ``X.Y`` — Y may be a
    submodule, and the resolver keeps whichever exists on disk.
    """
    tree = ast.parse(src)
    out: list = []
    package = module if is_package else module.rsplit(".", 1)[0]

    def walk(body):
        for node in body:
            if isinstance(node, ast.Import):
                out.extend(alias.name for alias in node.names)
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    parts = package.split(".")
                    if node.level > 1:
                        parts = parts[: -(node.level - 1)]
                    base = ".".join(parts + ([node.module] if node.module else []))
                else:
                    base = node.module or ""
                if base:
                    out.append(base)
                    out.extend(
                        f"{base}.{alias.name}" for alias in node.names
                        if alias.name != "*"
                    )
            elif isinstance(node, ast.If):
                if not _is_type_checking_guard(node.test):
                    walk(node.body)
                walk(node.orelse)
            elif isinstance(node, ast.Try):
                walk(node.body)
                for handler in node.handlers:
                    walk(handler.body)
                walk(node.orelse)
                walk(node.finalbody)
            elif isinstance(node, (ast.ClassDef, ast.With)):
                walk(node.body)
    walk(tree.body)
    return out


def module_imports(name: str, root: str) -> list:
    path = module_file(name, root)
    if path is None:
        return []
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    return imports_of_source(src, name, path.endswith("__init__.py"))


def import_closure(name: str, root: str) -> tuple:
    """BFS over the static module-level import graph from ``name``.

    Returns ``(internal, external)``: repo-internal modules reached (each
    mapped to its chain from ``name``) and external dotted names with the
    chain that first reached them. Importing a submodule executes every
    parent package ``__init__`` too, so parents join the frontier.
    """
    internal: dict = {}
    external: dict = {}
    queue = [(name, [name])]
    while queue:
        mod, chain = queue.pop(0)
        if mod in internal:
            continue
        if module_file(mod, root) is None:
            # external (or a from-import of a non-module attribute):
            # record the full dotted name once, with its chain
            external.setdefault(mod, chain)
            continue
        internal[mod] = chain
        targets = list(module_imports(mod, root))
        # a submodule import runs the parent packages' __init__ bodies
        for target in list(targets):
            while "." in target:
                target = target.rsplit(".", 1)[0]
                targets.append(target)
        for target in targets:
            if target not in internal:
                queue.append((target, chain + [target]))
    return internal, external


def heavy_chains(name: str, root: str, heavy=HEAVY_MODULES) -> list:
    """Chains from ``name`` to any heavy import (empty = clean). One
    chain per distinct heavy top-level module, shortest-first."""
    _, external = import_closure(name, root)
    hits = {}
    for ext, chain in external.items():
        top = ext.split(".")[0]
        if top in heavy:
            cur = hits.get(top)
            if cur is None or len(chain) < len(cur):
                hits[top] = chain + [ext] if chain[-1] != ext else chain
    return [hits[t] for t in sorted(hits)]


def pallas_chains(name: str, root: str) -> list:
    """Chains from ``name`` to any static import whose dotted name
    mentions pallas (``jax.experimental.pallas`` and friends)."""
    internal, external = import_closure(name, root)
    out = []
    for ext, chain in sorted(external.items()):
        if PALLAS_MARKER in ext:
            out.append(chain + [ext] if chain[-1] != ext else chain)
    for mod, chain in sorted(internal.items()):
        if PALLAS_MARKER in mod and mod != name:
            out.append(chain)
    return out


def hygiene_findings(root: Optional[str] = None) -> list:
    """The audit pass: every declared module checked against its
    contract, plus declared names that do not resolve (a rename that
    silently dropped a module from enforcement is itself a finding)."""
    root = root or repo_root()
    findings = []
    for name in JAX_FREE_MODULES:
        if module_file(name, root) is None:
            findings.append(Finding(
                check="hygiene-missing-module", severity="P2", target=name,
                message=f"declared jax-free module {name} does not resolve "
                        "under the repo root — rename drift in hygiene.py",
            ))
            continue
        for chain in heavy_chains(name, root):
            findings.append(Finding(
                check="import-hygiene", severity="P1", target=name,
                anchor=chain[-1].split(".")[0],
                message=f"declared jax-free module {name} statically reaches "
                        f"{chain[-1]} via {' -> '.join(chain)}",
                detail={"chain": " -> ".join(chain)},
            ))
    for name in PALLAS_FREE_MODULES:
        if module_file(name, root) is None:
            findings.append(Finding(
                check="hygiene-missing-module", severity="P2", target=name,
                message=f"declared pallas-free module {name} does not resolve "
                        "under the repo root — rename drift in hygiene.py",
            ))
            continue
        for chain in pallas_chains(name, root):
            findings.append(Finding(
                check="import-hygiene-pallas", severity="P1", target=name,
                anchor=chain[-1],
                message=f"pallas-free module {name} statically reaches "
                        f"{chain[-1]} via {' -> '.join(chain)} — the kernel "
                        "import must defer to first trace (_LazyModule)",
                detail={"chain": " -> ".join(chain)},
            ))
    return findings
