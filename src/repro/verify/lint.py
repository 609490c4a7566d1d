"""jit-safety lint: AST taint analysis over the repro source tree.

The whole repo rests on an unwritten rule: anything a jitted function
computes from its *traced* operands must stay inside jnp/lax -- a Python
``if``/``for``/``int()`` on a traced value either raises a
ConcretizationTypeError at trace time or, worse, silently bakes one
concrete value into the compiled program.  This pass makes the rule
checkable: it walks every module under ``src/repro``, marks traced
parameters, propagates taint through assignments, and flags the
constructs that leak traced values into Python control flow.

What counts as *traced*:

  * parameters of a ``@jax.jit`` / ``functools.partial(jax.jit,
    static_argnames=(...))`` function that are NOT listed static;
  * any parameter annotated ``jax.Array`` (the repo's convention for
    array-path functions, jitted by their callers).

What launders taint back to static:

  * the static metadata attributes ``shape`` / ``ndim`` / ``size`` /
    ``dtype`` (compile-time constants under tracing);
  * ``len(x)`` (always the static leading dim).

Rules:

``traced-branch``     ``if``/``while`` whose test involves a traced value
``traced-ternary``    conditional expression on a traced value
``traced-assert``     ``assert`` on a traced value
``traced-loop``       ``for`` iterating over a traced value
``python-int-cast``   ``int()``/``float()``/``bool()`` of a traced value
``scheduler-state``   a ``Scheduler.schedule`` method writing ``self``
                      attributes -- per-call state breaks the static
                      (cts, n_ops) -> assignment contract the bank's
                      jitted dispatch relies on
``interpret-env``     reading the ``REPRO_INTERPRET`` environment
                      variable anywhere but ``kernels/runtime.py`` -- the one
                      shim that owns interpret-mode resolution; a
                      second reader can disagree with it mid-process
                      and silently mix compiled and interpreted
                      launches
"""
from __future__ import annotations

import ast
import pathlib

from .intervals import Violation

#: attribute reads on a traced array that are static under tracing
STATIC_ATTRS = frozenset({"shape", "ndim", "size", "dtype"})
#: builtins that force a Python scalar out of a traced value
_CASTS = frozenset({"int", "float", "bool"})


def _is_jax_jit(node: ast.expr) -> bool:
    """Matches ``jax.jit`` or bare ``jit`` in an expression position."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    return isinstance(node, ast.Name) and node.id == "jit"


def _jit_static_names(dec: ast.expr):
    """If ``dec`` is a jit decorator, return its static_argnames set
    (empty for plain ``@jax.jit``); else None."""
    if _is_jax_jit(dec):
        return frozenset()
    if isinstance(dec, ast.Call):
        if _is_jax_jit(dec.func):
            return _literal_names(dec.keywords, "static_argnames")
        # functools.partial(jax.jit, static_argnames=(...))
        if isinstance(dec.func, ast.Attribute) and \
                dec.func.attr == "partial" and dec.args and \
                _is_jax_jit(dec.args[0]):
            return _literal_names(dec.keywords, "static_argnames")
    return None


def _literal_names(keywords, key: str) -> frozenset:
    for kw in keywords:
        if kw.arg == key:
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return frozenset()
            if isinstance(val, str):
                return frozenset({val})
            return frozenset(v for v in val if isinstance(v, str))
    return frozenset()


def _is_jax_array_annotation(ann) -> bool:
    if ann is None:
        return False
    if isinstance(ann, ast.Attribute) and ann.attr == "Array":
        return True
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        return ann.value.replace(" ", "").endswith("jax.Array")
    return False


def _traced_params(fn: ast.FunctionDef) -> set:
    """Parameter names of ``fn`` that carry traced arrays."""
    static = None
    for dec in fn.decorator_list:
        names = _jit_static_names(dec)
        if names is not None:
            static = names
            break
    traced = set()
    args = fn.args
    for a in list(args.posonlyargs) + list(args.args) + \
            list(args.kwonlyargs):
        if static is not None:
            if a.arg not in static and a.arg != "self":
                traced.add(a.arg)
        elif _is_jax_array_annotation(a.annotation):
            traced.add(a.arg)
    return traced


class _TaintWalker(ast.NodeVisitor):
    """One function body: propagate taint, record rule violations."""

    def __init__(self, path: str, fn: ast.FunctionDef):
        self.path = path
        self.fn = fn
        self.tainted = _traced_params(fn)
        self.violations = []

    # ------------------------------------------------------ taint queries
    def _expr_tainted(self, node) -> bool:
        """Does evaluating ``node`` yield a traced value?"""
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False              # static metadata launders taint
            return self._expr_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self._expr_tainted(node.value)
        if isinstance(node, ast.Call):
            fname = node.func
            if isinstance(fname, ast.Name) and fname.id == "len":
                return False              # len() is the static batch dim
            parts = [node.func] + list(node.args) + \
                [kw.value for kw in node.keywords]
            return any(self._expr_tainted(p) for p in parts)
        if isinstance(node, (ast.BinOp,)):
            return self._expr_tainted(node.left) or \
                self._expr_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self._expr_tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self._expr_tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return self._expr_tainted(node.left) or \
                any(self._expr_tainted(c) for c in node.comparators)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self._expr_tainted(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return (self._expr_tainted(node.body) or
                    self._expr_tainted(node.orelse) or
                    self._expr_tainted(node.test))
        if isinstance(node, ast.Starred):
            return self._expr_tainted(node.value)
        return False

    def _flag(self, rule: str, node, detail: str) -> None:
        self.violations.append(Violation(
            "lint", rule,
            f"{self.path}:{node.lineno} in {self.fn.name}", detail))

    # ------------------------------------------------- taint propagation
    def _assign_targets(self, target, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._assign_targets(elt, tainted)
        # subscript/attribute targets mutate an existing binding: the
        # base name's taint already reflects it

    def visit_Assign(self, node: ast.Assign) -> None:
        tainted = self._expr_tainted(node.value)
        for t in node.targets:
            self._assign_targets(t, tainted)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if self._expr_tainted(node.value):
            self._assign_targets(node.target, True)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._assign_targets(node.target,
                                 self._expr_tainted(node.value))
        self.generic_visit(node)

    # ------------------------------------------------------------- rules
    def visit_If(self, node: ast.If) -> None:
        if self._expr_tainted(node.test):
            self._flag("traced-branch", node,
                       "`if` on a traced value: trace-time "
                       "ConcretizationTypeError (or a silently baked-in "
                       "constant); use jnp.where / lax.cond")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        if self._expr_tainted(node.test):
            self._flag("traced-branch", node,
                       "`while` on a traced value; use lax.while_loop")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        if self._expr_tainted(node.test):
            self._flag("traced-ternary", node,
                       "conditional expression on a traced value; use "
                       "jnp.where")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        if self._expr_tainted(node.test):
            self._flag("traced-assert", node,
                       "assert on a traced value; use "
                       "checkify or a shape/static assert")
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        if self._expr_tainted(node.iter):
            self._flag("traced-loop", node,
                       "Python `for` over a traced value unrolls (or "
                       "fails) at trace time; use lax.scan/fori_loop")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if isinstance(node.func, ast.Name) and node.func.id in _CASTS \
                and node.args and self._expr_tainted(node.args[0]):
            self._flag("python-int-cast", node,
                       f"{node.func.id}() forces a traced value to a "
                       f"Python scalar at trace time")
        self.generic_visit(node)

    # nested defs get their own walker; don't descend with parent taint
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        if node is not self.fn:
            return
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


def _scheduler_state_writes(tree: ast.Module, path: str) -> list:
    """Flag ``self.x = ...`` inside any ``schedule`` method."""
    out = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        for fn in cls.body:
            if not isinstance(fn, ast.FunctionDef) or \
                    fn.name != "schedule":
                continue
            for node in ast.walk(fn):
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    if isinstance(t, ast.Attribute) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == "self":
                        out.append(Violation(
                            "lint", "scheduler-state",
                            f"{path}:{node.lineno} in "
                            f"{cls.name}.schedule",
                            f"schedule() writes self.{t.attr}: per-call "
                            f"state makes the (cts, n_ops) -> assignment "
                            f"map non-static and breaks jitted dispatch"))
    return out


#: interpret-mode env vars only ``kernels/runtime.py`` may read
_INTERPRET_ENV = frozenset({"REPRO_INTERPRET"})


def _reads_environ(node: ast.expr) -> str:
    """The interpret-env key ``node`` reads, or None.

    Matches ``os.environ[K]``, ``os.environ.get(K, ...)`` and
    ``os.getenv(K, ...)`` for K in :data:`_INTERPRET_ENV` (any base
    object named/ending in ``environ``/``getenv``, so aliased imports
    are caught too).
    """
    def key_of(expr) -> str:
        if isinstance(expr, ast.Constant) and \
                isinstance(expr.value, str) and \
                expr.value in _INTERPRET_ENV:
            return expr.value
        return None

    def names_environ(expr) -> bool:
        return (isinstance(expr, ast.Attribute)
                and expr.attr == "environ") or \
               (isinstance(expr, ast.Name) and expr.id == "environ")

    if isinstance(node, ast.Subscript) and names_environ(node.value):
        return key_of(node.slice)
    if isinstance(node, ast.Call) and node.args:
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "get" and \
                names_environ(f.value):
            return key_of(node.args[0])
        if (isinstance(f, ast.Attribute) and f.attr == "getenv") or \
                (isinstance(f, ast.Name) and f.id == "getenv"):
            return key_of(node.args[0])
    return None


def _interpret_env_reads(tree: ast.Module, path: str) -> list:
    """Flag interpret-mode env reads outside the runtime shim."""
    norm = path.replace("\\", "/")
    if norm.endswith("kernels/runtime.py"):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Subscript, ast.Call)):
            continue
        key = _reads_environ(node)
        if key is not None:
            out.append(Violation(
                "lint", "interpret-env", f"{path}:{node.lineno}",
                f"reads {key} directly; interpret-mode resolution "
                f"belongs to repro.kernels.runtime (a second reader "
                f"can disagree with the shim and mix compiled and "
                f"interpreted launches)"))
    return out


def lint_source(source: str, path: str = "<string>") -> list:
    """Lint one module's source text; returns Violations."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Violation("lint", "syntax-error", f"{path}:{e.lineno}",
                          str(e))]
    out = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            walker = _TaintWalker(path, node)
            walker.visit(node)
            out.extend(walker.violations)
    out.extend(_scheduler_state_writes(tree, path))
    out.extend(_interpret_env_reads(tree, path))
    return out


def lint_file(path) -> list:
    p = pathlib.Path(path)
    return lint_source(p.read_text(), str(p))


def lint_tree(root) -> list:
    """Lint every ``*.py`` under ``root`` (deterministic order)."""
    rootp = pathlib.Path(root)
    out = []
    for p in sorted(rootp.rglob("*.py")):
        out.extend(lint_file(p))
    return out
