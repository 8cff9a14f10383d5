"""Layer 2 — AST lint: repo-specific source checks the jaxpr certifier
cannot see (DESIGN.md §11).

A jaxpr only shows what survives tracing; some constant-time hazards live
in the *Python* that builds the trace.  Three rules, scoped to the hot-path
source tree (``src/repro/{core,kernels,serving}`` by default):

``host-sync``
    Host-synchronising calls inside *hot functions* — functions that are
    ``jax.jit``-decorated, or follow the kernel-body naming convention
    (``_kernel*`` / ``*_body``).  Flagged calls: ``.item()`` / ``.tolist()``
    / ``.block_until_ready()`` / ``.bit_length()`` on expressions,
    ``float(...)`` / ``int(...)`` / ``bool(...)`` casts, ``np.asarray`` /
    ``np.array`` materialisation, and ``jax.device_get``.  Each of these
    either blocks on the device or forces a concretisation error at trace
    time; none belongs on a hot path.  A deliberate host-side computation
    on *static* operands (e.g. deriving the power-of-two extent from a
    static ``n``) is annotated in-line with ``# ct: host-ok`` plus a
    reason, which suppresses the finding on that line.

``bare-int``
    Integer literals outside int32 range used directly in arithmetic /
    bitwise expressions inside hot functions.  Under ``enable_x64`` a bare
    wide literal weak-promotes the whole u32-limb expression to 64-bit —
    exactly the promotion the certifier's ``dtype-closed`` invariant
    rejects, caught here at the line that causes it.  Wrapping the literal
    in an explicit dtype cast (``np.uint32(...)``, ``jnp.uint64(...)``,
    ...) keeps the limb discipline and satisfies the rule.

``config-mutation``
    ``jax.config.update(...)`` / ``jax.config.<flag> = ...`` anywhere in
    library source.  Global config flips belong to tests and tools (the
    certifier itself uses the scoped ``enable_x64`` context manager);
    library code mutating process-global config changes numerics for every
    caller.

The lint is intentionally small and calibrated to this codebase — it is a
tripwire for the specific regressions the roofline work keeps catching in
review, not a general-purpose style checker.
"""
from __future__ import annotations

import ast
import pathlib
import re
from typing import Iterable, Optional

from repro.analysis.report import LintFinding

#: default lint scope, relative to the repo/source root
DEFAULT_SCOPE = ("core", "kernels", "serving")

#: in-line waiver token: a line carrying this comment is exempt
WAIVER_TOKEN = "ct: host-ok"

#: hot-function naming convention (kernel bodies / unrolled trace bodies)
_HOT_NAME = re.compile(r"(^_kernel)|(_body$)")

#: attribute calls that synchronise with (or escape to) the host
_SYNC_ATTRS = {"item", "tolist", "block_until_ready", "bit_length"}

#: builtin casts that force concretisation of a traced value
_SYNC_BUILTINS = {"float", "int", "bool"}

#: np.<attr> calls that materialise on host
_NP_MATERIALISE = {"asarray", "array", "frombuffer"}

#: explicit dtype-cast callables that make a wide literal limb-safe
_CAST_NAMES = {
    "uint8", "uint16", "uint32", "uint64",
    "int8", "int16", "int32", "int64",
    "asarray", "array", "full", "constant",
}

_INT32_MAX = 1 << 31


def _dotted(node: ast.AST) -> str:
    """'np.asarray' for Attribute/Name chains, '' for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_jit_decorated(fn: ast.FunctionDef) -> bool:
    """True for ``@jax.jit`` / ``@jit`` / ``@functools.partial(jax.jit, ...)``
    (and any decorator whose expression mentions a ``jit`` name)."""
    for dec in fn.decorator_list:
        for node in ast.walk(dec):
            name = _dotted(node)
            if name == "jit" or name.endswith(".jit"):
                return True
    return False


def _is_hot(fn: ast.FunctionDef) -> bool:
    return _is_jit_decorated(fn) or bool(_HOT_NAME.search(fn.name))


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str):
        self.path = path
        self.lines = source.splitlines()
        self.findings: list[LintFinding] = []
        self._hot_depth = 0

    # -- helpers ------------------------------------------------------------

    def _line(self, lineno: int) -> str:
        return self.lines[lineno - 1] if 0 < lineno <= len(self.lines) else ""

    def _waived(self, lineno: int) -> bool:
        return WAIVER_TOKEN in self._line(lineno)

    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        if not self._waived(node.lineno):
            self.findings.append(
                LintFinding(
                    path=self.path,
                    line=node.lineno,
                    rule=rule,
                    message=message,
                    source=self._line(node.lineno).strip(),
                )
            )

    # -- traversal ----------------------------------------------------------

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        hot = _is_hot(node)
        self._hot_depth += hot
        self.generic_visit(node)
        self._hot_depth -= hot

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if self._hot_depth:
            if isinstance(node.func, ast.Attribute) and node.func.attr in _SYNC_ATTRS:
                self._emit(
                    node,
                    "host-sync",
                    f".{node.func.attr}() synchronises with the host inside a "
                    "hot function (annotate '# ct: host-ok — <why>' if the "
                    "operand is provably static)",
                )
            elif name in _SYNC_BUILTINS:
                self._emit(
                    node,
                    "host-sync",
                    f"{name}() concretises its operand inside a hot function",
                )
            elif name.startswith("np.") and name[3:] in _NP_MATERIALISE:
                self._emit(
                    node,
                    "host-sync",
                    f"{name}() materialises on host inside a hot function "
                    "(use jnp.asarray for a device-side view)",
                )
            elif name in ("jax.device_get", "device_get"):
                self._emit(node, "host-sync", f"{name}() copies device->host")
        # config mutation is flagged everywhere, hot or not
        if name in ("jax.config.update", "config.update"):
            self._emit(
                node,
                "config-mutation",
                "global jax config mutated in library code — use the scoped "
                "context manager (e.g. jax.enable_x64) or move "
                "the flip to test/tool setup",
            )
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        for target in node.targets:
            dotted = _dotted(target)
            if dotted.startswith(("jax.config.", "config.jax_")):
                self._emit(
                    node,
                    "config-mutation",
                    f"assignment to {dotted} mutates global jax config in "
                    "library code",
                )
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp) -> None:
        if self._hot_depth:
            for side in (node.left, node.right):
                if (
                    isinstance(side, ast.Constant)
                    and type(side.value) is int
                    and not -_INT32_MAX <= side.value < _INT32_MAX
                ):
                    self._emit(
                        node,
                        "bare-int",
                        f"bare literal {side.value:#x} exceeds int32 in limb "
                        "arithmetic — weak-promotes the expression to 64-bit "
                        "under x64; wrap it in an explicit dtype cast "
                        "(np.uint32(...) / jnp.uint64(...))",
                    )
        self.generic_visit(node)

def _strip_casts(tree: ast.AST) -> None:
    """Neutralise wide literals that are *arguments of explicit dtype casts*
    so ``visit_BinOp`` never sees them: ``np.uint32(x & 0xFFFFFFFF)`` is the
    sanctioned idiom (the cast pins the dtype before any limb op runs)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            leaf = name.rsplit(".", 1)[-1]
            if leaf in _CAST_NAMES:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Constant) and type(sub.value) is int:
                        sub.value = 0


def lint_source(source: str, path: str = "<string>") -> list[LintFinding]:
    """Lint one module's source text; returns findings (empty = clean)."""
    tree = ast.parse(source, filename=path)
    _strip_casts(tree)
    linter = _Linter(path, source)
    linter.visit(tree)
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.rule))


def lint_paths(
    root: Optional[pathlib.Path] = None,
    scope: Iterable[str] = DEFAULT_SCOPE,
) -> list[LintFinding]:
    """Lint every ``.py`` under ``root/<scope dirs>`` (root defaults to the
    installed ``repro`` package directory)."""
    if root is None:
        import repro

        root = pathlib.Path(repro.__file__).parent
    findings: list[LintFinding] = []
    for sub in scope:
        base = root / sub
        if not base.is_dir():
            continue
        for py in sorted(base.rglob("*.py")):
            findings.extend(
                lint_source(py.read_text(), str(py.relative_to(root.parent)))
            )
    return findings
