"""AST lints over ``src/repro``: the four static rules.

* ``traced-cond`` — Python ``if``/``while`` whose test references a
  traced value, inside a **traced region** (a function passed to
  ``jax.jit`` / ``lax.scan`` / ``lax.cond`` / ``vmap`` /
  ``pallas_call`` / ``shard_map`` ..., decorated with one, or nested in
  one).  "Traced value" is a static approximation: the function's
  parameters (minus its ``static_argnames`` and any names bound
  statically through ``functools.partial`` at the tracing call site),
  names tuple-unpacked from them, and names assigned from
  ``jnp.``/``jax.lax.`` calls.  Identity tests (``is None``),
  ``isinstance``/``len``/``callable`` and shape/dtype attribute reads
  are Python-static and never flagged.

* ``host-sync`` — ``.item()`` / ``.tobytes()`` / ``float()`` / ``int()``
  / ``bool()`` / ``np.asarray`` / ``np.array`` / ``jax.device_get`` /
  ``block_until_ready`` call sites, classified against the serve /
  superstep **hot-path inventory** (``HOT_PATHS``):

  - ``finding`` — on a hot path, outside any tracer guard;
  - ``guarded`` — on a hot path but inside ``if tracer is not None:``
    (or after an early ``if tracer is None: return`` fast path) — the
    observability contract: sync only when someone is watching;
  - ``cold-path`` — everywhere else (compile/boot/layout-build time);
    reported as counts, never as findings.

  Casts of static values (``int(x.shape[0])``, ``int(<static arg>)``)
  are Python-level and skipped.

* ``static-arg-array`` — array values meeting ``jax.jit`` static
  arguments: an array-valued default on a static-named parameter, an
  array literal/constructor passed to a static-named kwarg at a call
  site, or a ``functools.partial`` binding an array to a static name.

* ``tracer-gate`` — a function that accepts a ``tracer`` and calls
  ``tracer.span(...)`` / ``tracer.block(...)`` with no ``tracer is
  None`` branch anywhere in its body (``maybe_span`` is the sanctioned
  alternative and never flagged).

Suppression: a trailing ``# analysis: ignore[rule]`` on the finding's
line (or the line above) reclassifies it as ``suppressed`` — the
inline acknowledgment for intentional sites.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path

from repro.analysis.findings import Finding

# Entry points whose function-valued arguments become traced regions.
_TRACING_ENTRY = {
    "jit", "vmap", "pmap", "scan", "cond", "while_loop", "fori_loop",
    "switch", "pallas_call", "shard_map", "grad", "value_and_grad",
    "checkpoint", "remat", "eval_shape",
}

# Calls whose results are traced arrays inside a traced region.
_ARRAY_ROOTS = ("jnp", "lax", "pl", "pltpu")
_ARRAY_JAX_SUBMODULES = ("lax", "numpy", "nn", "random")

# Python-static predicates: never a traced branch.
_SAFE_CALLS = {
    "isinstance", "hasattr", "callable", "len", "issubclass", "getattr",
    "type", "id", "repr", "str",
}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

# Host-sync method / function names.
_SYNC_METHODS = {"item", "tobytes", "block_until_ready"}
_SYNC_DOTTED = {
    ("np", "asarray"), ("np", "array"), ("numpy", "asarray"),
    ("numpy", "array"), ("jax", "block_until_ready"),
    ("jax", "device_get"),
}
_SYNC_BARE = {"float", "int", "bool"}

# The serve / superstep hot-path inventory: module-relative path suffix
# -> qualname prefixes.  A call site is "hot" when its file matches and
# its enclosing qualname extends one of these (closures included:
# ``_execute.<locals>._call`` is hot because ``_execute`` is).
HOT_PATHS: dict[str, tuple[str, ...]] = {
    "core/serving.py": (
        "CompiledAlgorithm.run", "CompiledAlgorithm.run_batch",
        "CompiledAlgorithm._execute", "signature", "_initial_msg_sig",
        "_query_sig", "_canon_query", "_build_local_executable",
        "_build_distributed_executable",
    ),
    "core/engine.py": (
        "deliver", "superstep_pair", "compute", "compute_batch",
        "batch_halting_scan",
    ),
    "serve/frontend.py": (
        "Frontend.submit", "Frontend.pump", "Frontend._worker",
        "Frontend._serve_loop", "Frontend._run_flush",
        "Frontend._execute_requests", "Frontend._attempt",
        "Frontend._requeue_after_crash", "Frontend._fail",
        "_stack", "_unstack", "_block",
    ),
    "serve/queue.py": (
        "CoalescingBatcher.submit", "CoalescingBatcher.poll",
        "CoalescingBatcher._take", "AdaptiveDelay.observe",
    ),
    "serve/replica.py": (
        "_serve_replica", "ProcessReplica.poll_messages",
        "ProcessReplica.send",
    ),
    "serve/router.py": (
        "Router.submit", "Router.pump", "Router._admit", "Router._route",
        "Router._dispatch", "Router._on_message", "Router._mark_dead",
        "Router._apply", "Router._fail_pending_if_hopeless",
    ),
    "kernels/deliver/xla.py": ("deliver_ell_leaf",),
}

_SUPPRESS_RE = re.compile(r"#\s*analysis:\s*ignore(?:\[([a-z\-,\s]+)\])?")

# Broad exception classes a handler may catch without naming the real
# failure; and the faults-taxonomy / error-forwarding names whose
# presence in a handler body means the error was routed, not swallowed.
_BROAD_EXC = {"Exception", "BaseException"}
_ERROR_ROUTES = {
    "FaultError", "InjectedFault", "TransientExecuteError",
    "DeadlineExceeded", "FrontendClosed", "PoisonQuery", "CircuitOpen",
    "CorruptCacheEntry", "CheckpointError", "ReplicaLost", "Overloaded",
    "is_transient", "set_exception",
}


def _broad_handler(h: ast.ExceptHandler) -> bool:
    if h.type is None:
        return True
    elts = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
    return any(
        (_dotted(e) or "").rsplit(".", 1)[-1] in _BROAD_EXC for e in elts
    )


def _handler_routes(h: ast.ExceptHandler) -> bool:
    """Does the handler re-raise, forward the bound exception, or reach
    into the faults taxonomy?  Any of these counts as routing."""
    for node in ast.walk(h):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Name):
            if node.id in _ERROR_ROUTES:
                return True
            if (
                h.name is not None
                and node.id == h.name
                and isinstance(node.ctx, ast.Load)
            ):
                return True
        if isinstance(node, ast.Attribute) and node.attr in _ERROR_ROUTES:
            return True
    return False


def _dotted(node: ast.expr) -> str | None:
    """``a.b.c`` -> "a.b.c" for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_partial(call: ast.Call) -> bool:
    return _dotted(call.func) in ("partial", "functools.partial")


def _static_argnames(keywords) -> set[str]:
    static: set[str] = set()
    for kw in keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            static |= set(_const_str_tuple(kw.value))
    return static


def _const_str_tuple(node: ast.expr | None) -> tuple[str, ...]:
    """Constant strings out of ``static_argnames=("a", "b")`` forms."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(
            elt.value for elt in node.elts
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str)
        )
    return ()


def _is_array_expr(node: ast.expr) -> bool:
    """Array literal or constructor call: a value jit can't hash."""
    if isinstance(node, (ast.List, ast.Set)):
        return True
    if isinstance(node, ast.Call):
        name = _dotted(node.func) or ""
        leaf = name.rsplit(".", 1)[-1]
        root = name.split(".", 1)[0]
        return root in ("np", "numpy", "jnp", "jax") and leaf in (
            "asarray", "array", "zeros", "ones", "full", "arange",
            "empty", "linspace",
        )
    return False


def _is_static_expr(node: ast.expr) -> bool:
    """Shape/len reads: host ints by construction, cast-safe."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_ATTRS:
            return True
        if isinstance(sub, ast.Call):
            name = _dotted(sub.func) or ""
            if name == "len" or name.endswith(".shape"):
                return True
    return False


class _Suppressions:
    """Per-file ``# analysis: ignore[rule]`` index.  A marker covers
    its own line (trailing comment) or, when it sits in a comment-only
    block, every line of that block plus the next source line."""

    def __init__(self, source: str):
        self.by_line: dict[int, set[str] | None] = {}
        lines = source.splitlines()
        for i, text in enumerate(lines, start=1):
            m = _SUPPRESS_RE.search(text)
            if not m:
                continue
            rules = m.group(1)
            parsed = (
                {r.strip() for r in rules.split(",")} if rules else None
            )
            covered = [i]
            if text.lstrip().startswith("#"):
                # comment-only marker: extend through the rest of the
                # comment block to the first source line below
                j = i
                while j < len(lines) and lines[j].lstrip().startswith("#"):
                    j += 1
                    covered.append(j)
                covered.append(j + 1)
            for ln in covered:
                prev = self.by_line.get(ln, set())
                if parsed is None or prev is None:
                    self.by_line[ln] = None   # None = all rules
                else:
                    self.by_line[ln] = prev | parsed

    def covers(self, line: int, rule: str) -> bool:
        rules = self.by_line.get(line, ())
        return rules is None or (rules != () and rule in rules)


# --------------------------------------------------------------------------
# module index: which names are traced / statically jitted
# --------------------------------------------------------------------------

class _ModuleIndex(ast.NodeVisitor):
    """Which local function names are traced regions, which of their
    parameters are static, and which names are jitted with static args
    (the static-arg-array call-site map)."""

    def __init__(self):
        self.traced_names: set[str] = set()
        self.static_names: dict[str, set[str]] = {}
        self.static_jitted: dict[str, set[str]] = {}

    def _note(self, name: str, static: set[str]) -> None:
        self.traced_names.add(name)
        self.static_names.setdefault(name, set()).update(static)

    def _fn_arg(self, node: ast.expr, static: set[str]) -> None:
        """One function-valued argument of a tracing entry point."""
        if isinstance(node, ast.Name):
            self._note(node.id, static)
        elif isinstance(node, ast.IfExp):
            self._fn_arg(node.body, static)
            self._fn_arg(node.orelse, static)
        elif isinstance(node, ast.Call) and _is_partial(node):
            bound = {kw.arg for kw in node.keywords if kw.arg}
            if node.args and isinstance(node.args[0], ast.Name):
                self._note(node.args[0].id, static | bound)

    def _note_jit_call(self, args, static: set[str]) -> None:
        for arg in args:
            self._fn_arg(arg, static)
            if static and isinstance(arg, ast.Name):
                self.static_jitted.setdefault(arg.id, set()).update(static)

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        leaf = (name or "").rsplit(".", 1)[-1]
        if leaf in _TRACING_ENTRY:
            static = _static_argnames(node.keywords)
            if leaf == "jit":
                self._note_jit_call(node.args, static)
            else:
                for arg in node.args:
                    self._fn_arg(arg, static)
        elif isinstance(node.func, ast.Call) and _is_partial(node.func):
            # partial(jax.jit, static_argnames=...)(fn)
            inner = (
                _dotted(node.func.args[0]) if node.func.args else None
            ) or ""
            if inner.rsplit(".", 1)[-1] in _TRACING_ENTRY:
                static = _static_argnames(node.func.keywords)
                self._note_jit_call(node.args, static)
        self.generic_visit(node)


def _decorator_trace_info(fn: ast.AST) -> tuple[bool, set[str]]:
    """(is the def decorated into a traced region, its static names)."""
    static: set[str] = set()
    traced = False
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = _dotted(target) or ""
        leaf = name.rsplit(".", 1)[-1]
        if leaf in _TRACING_ENTRY:
            traced = True
            if isinstance(dec, ast.Call):
                static |= _static_argnames(dec.keywords)
        elif leaf == "partial" and isinstance(dec, ast.Call):
            # @functools.partial(jax.jit, static_argnames=...)
            inner = (_dotted(dec.args[0]) if dec.args else None) or ""
            if inner.rsplit(".", 1)[-1] in _TRACING_ENTRY:
                traced = True
                static |= _static_argnames(dec.keywords)
    return traced, static


def _param_names(fn) -> list[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return [n for n in names if n not in ("self", "cls")]


def _collect_traced_locals(fn, params: set[str]) -> set[str]:
    """Names plausibly holding traced values in ``fn``'s body: the
    params, names unpacked/derived from them, jnp/lax call results."""
    traced = set(params)
    for _ in range(2):  # second pass catches unpack -> derive chains
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            src_traced = False
            if isinstance(v, ast.Name) and v.id in traced:
                src_traced = True
            elif isinstance(v, ast.Subscript):
                if isinstance(v.value, ast.Name) and v.value.id in traced:
                    src_traced = True
            elif isinstance(v, ast.Call):
                name = _dotted(v.func) or ""
                root = name.split(".", 1)[0]
                sub = name.split(".")
                if root in _ARRAY_ROOTS:
                    src_traced = True
                elif root == "jax" and len(sub) > 1 and (
                    sub[1] in _ARRAY_JAX_SUBMODULES
                ):
                    src_traced = True
            if not src_traced:
                continue
            for tgt in node.targets:
                for elt in ast.walk(tgt):
                    if isinstance(elt, ast.Name):
                        traced.add(elt.id)
    return traced


def _test_uses_traced(node: ast.expr, traced: set[str]) -> bool:
    """Does a branch test reference a traced value in a way Python
    must concretize?  Static predicates are excluded."""
    if isinstance(node, ast.BoolOp):
        return any(_test_uses_traced(v, traced) for v in node.values)
    if isinstance(node, ast.UnaryOp):
        return _test_uses_traced(node.operand, traced)
    if isinstance(node, ast.Compare):
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False
        return _test_uses_traced(node.left, traced) or any(
            _test_uses_traced(c, traced) for c in node.comparators
        )
    if isinstance(node, ast.Call):
        name = _dotted(node.func) or ""
        if name.rsplit(".", 1)[-1] in _SAFE_CALLS:
            return False
        return any(_test_uses_traced(a, traced) for a in node.args)
    if isinstance(node, ast.Attribute):
        # attribute reads are config/shape access until proven traced —
        # direct Name references are the signal this lint keys on.
        return False
    if isinstance(node, ast.Subscript):
        return _test_uses_traced(node.value, traced)
    if isinstance(node, ast.BinOp):
        return (_test_uses_traced(node.left, traced)
                or _test_uses_traced(node.right, traced))
    if isinstance(node, ast.Name):
        return node.id in traced
    return False


def _tracer_exprs(node: ast.expr) -> bool:
    """Does an expression read a tracer (``tracer`` name or ``*.tracer``
    attribute)?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id == "tracer":
            return True
        if isinstance(sub, ast.Attribute) and sub.attr == "tracer":
            return True
    return False


def _is_tracer_none_test(test: ast.expr) -> tuple[bool, bool]:
    """(is a ``tracer is None``-family test, truthy-branch-means-absent).

    Compound ``and`` tests (``tracer is not None and timing``) count as
    guards: their truthy branch can only run with a tracer present.
    """
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        for v in test.values:
            ok, absent = _is_tracer_none_test(v)
            if ok:
                return ok, absent
        return False, False
    if not isinstance(test, ast.Compare) or len(test.ops) != 1:
        return False, False
    if not isinstance(test.ops[0], (ast.Is, ast.IsNot)):
        return False, False
    comp = test.comparators[0]
    if not (isinstance(comp, ast.Constant) and comp.value is None):
        return False, False
    if not _tracer_exprs(test.left):
        return False, False
    return True, isinstance(test.ops[0], ast.Is)


def _sync_call_kind(node: ast.Call, safe_names: set[str]) -> str | None:
    """The host-sync pattern this call matches, or None."""
    if isinstance(node.func, ast.Attribute):
        if node.func.attr in _SYNC_METHODS:
            return f".{node.func.attr}()"
        name = _dotted(node.func)
        if name and tuple(name.split(".")) in _SYNC_DOTTED:
            return name
    elif isinstance(node.func, ast.Name) and node.func.id in _SYNC_BARE:
        if not node.args:
            return None
        arg = node.args[0]
        if isinstance(arg, ast.Constant) or _is_static_expr(arg):
            return None
        if isinstance(arg, ast.Name) and arg.id in safe_names:
            return None
        return f"{node.func.id}()"
    return None


def _returns(body: list[ast.stmt]) -> bool:
    return any(isinstance(s, (ast.Return, ast.Raise)) for s in body)


def _early_tracer_return_line(fn) -> int | None:
    """Line of a top-level ``if tracer is None: return`` fast path."""
    for stmt in fn.body:
        if isinstance(stmt, ast.If):
            ok, absent = _is_tracer_none_test(stmt.test)
            if ok and absent and _returns(stmt.body):
                return stmt.lineno
    return None


def _hot_prefixes(rel_path: str) -> tuple[str, ...]:
    for suffix, prefixes in HOT_PATHS.items():
        if rel_path.endswith(suffix):
            return prefixes
    return ()


def _is_hot(qualname: str, prefixes: tuple[str, ...]) -> bool:
    return any(
        qualname == p or qualname.startswith(p + ".") for p in prefixes
    )


# --------------------------------------------------------------------------
# per-file linter
# --------------------------------------------------------------------------

class _Ctx:
    """Walk context: enclosing qualname, traced-region state, active
    tracer guards, and names safe to cast (static args)."""

    __slots__ = ("qual", "traced", "traced_locals", "guards", "safe_names")

    def __init__(self, qual="", traced=False, traced_locals=frozenset(),
                 guards=(), safe_names=frozenset()):
        self.qual = qual
        self.traced = traced
        self.traced_locals = traced_locals
        self.guards = guards
        self.safe_names = safe_names

    def with_(self, **kw) -> "_Ctx":
        new = _Ctx(self.qual, self.traced, self.traced_locals,
                   self.guards, self.safe_names)
        for k, v in kw.items():
            setattr(new, k, v)
        return new


class _FileLinter:
    def __init__(self, rel_path: str, source: str, tree: ast.Module):
        self.rel = rel_path
        self.tree = tree
        self.suppress = _Suppressions(source)
        self.index = _ModuleIndex()
        self.index.visit(tree)
        self.hot = _hot_prefixes(rel_path)
        self.findings: list[Finding] = []

    def run(self) -> list[Finding]:
        ctx = _Ctx()
        for stmt in self.tree.body:
            self._walk_stmt(stmt, ctx)
        return self.findings

    # -- emit --------------------------------------------------------------

    def _emit(self, rule, node, scope, message, classification="finding"):
        line = getattr(node, "lineno", 0)
        if classification == "finding" and self.suppress.covers(line, rule):
            classification = "suppressed"
        self.findings.append(Finding(
            rule=rule, path=self.rel, line=line, scope=scope,
            message=message, classification=classification,
        ))

    # -- traversal ---------------------------------------------------------

    def _walk_stmt(self, stmt: ast.stmt, ctx: _Ctx) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._enter_function(stmt, ctx)
            return
        if isinstance(stmt, ast.ClassDef):
            inner = ctx.with_(qual=self._join(ctx.qual, stmt.name),
                              traced_locals=frozenset())
            for s in stmt.body:
                self._walk_stmt(s, inner)
            return
        if ctx.traced and isinstance(stmt, (ast.If, ast.While)):
            if _test_uses_traced(stmt.test, ctx.traced_locals):
                kind = "while" if isinstance(stmt, ast.While) else "if"
                names = sorted({
                    n.id for n in ast.walk(stmt.test)
                    if isinstance(n, ast.Name)
                    and n.id in ctx.traced_locals
                })
                self._emit(
                    "traced-cond", stmt, ctx.qual or "<module>",
                    f"`{kind}` on traced value(s) {', '.join(names)} "
                    "inside a traced region",
                )
        if isinstance(stmt, ast.Try):
            self._check_swallowed(stmt, ctx)
        if isinstance(stmt, ast.If):
            is_tracer, absent = _is_tracer_none_test(stmt.test)
            if is_tracer and not absent:
                # truthy branch runs only with a tracer present
                self._walk_expr(stmt.test, ctx)
                on = ctx.with_(guards=ctx.guards + ("tracer",))
                for s in stmt.body:
                    self._walk_stmt(s, on)
                for s in stmt.orelse:
                    self._walk_stmt(s, ctx)
                return
        self._walk_children(stmt, ctx)

    def _walk_children(self, node: ast.AST, ctx: _Ctx) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._enter_function(child, ctx)
            elif isinstance(child, ast.ClassDef):
                self._walk_stmt(child, ctx)
            elif isinstance(child, ast.stmt):
                self._walk_stmt(child, ctx)
            elif isinstance(child, ast.expr):
                self._walk_expr(child, ctx)
            else:  # withitem, ExceptHandler, keyword, arguments, ...
                self._walk_children(child, ctx)

    def _walk_expr(self, node: ast.expr, ctx: _Ctx) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self._check_sync_call(sub, ctx)
                self._check_static_arg_call(sub, ctx)

    # -- host-sync ---------------------------------------------------------

    def _check_sync_call(self, node: ast.Call, ctx: _Ctx) -> None:
        kind = _sync_call_kind(node, ctx.safe_names)
        if kind is None:
            return
        scope = ctx.qual or "<module>"
        if not self.hot or not _is_hot(scope, self.hot):
            self._emit("host-sync", node, scope, f"{kind} (cold path)",
                       classification="cold-path")
        elif "tracer" in ctx.guards:
            self._emit("host-sync", node, scope,
                       f"{kind} inside a tracer guard",
                       classification="guarded")
        else:
            self._emit(
                "host-sync", node, scope,
                f"{kind} on hot path `{scope}` outside any tracer guard",
            )

    # -- swallowed-error ---------------------------------------------------

    def _check_swallowed(self, stmt: ast.Try, ctx: _Ctx) -> None:
        """Bare/broad ``except`` that discards the error.  On the serve /
        superstep hot paths this is a finding (a fault silently eaten
        there breaks the every-request-resolves invariant); elsewhere
        it is reported as a cold-path count."""
        scope = ctx.qual or "<module>"
        hot = bool(self.hot) and _is_hot(scope, self.hot)
        for h in stmt.handlers:
            if not _broad_handler(h) or _handler_routes(h):
                continue
            what = (
                "bare `except:`" if h.type is None
                else "broad `except`"
            )
            if hot:
                self._emit(
                    "swallowed-error", h, scope,
                    f"{what} on hot path `{scope}` discards the error "
                    "without routing it through the faults taxonomy",
                )
            else:
                self._emit(
                    "swallowed-error", h, scope, f"{what} (cold path)",
                    classification="cold-path",
                )

    # -- static-arg-array --------------------------------------------------

    def _check_static_arg_call(self, node: ast.Call, ctx: _Ctx) -> None:
        scope = ctx.qual or "<module>"
        fname = node.func.id if isinstance(node.func, ast.Name) else None
        static = self.index.static_jitted.get(fname or "", set())
        for kw in node.keywords:
            if kw.arg in static and _is_array_expr(kw.value):
                self._emit(
                    "static-arg-array", node, scope,
                    f"array value for static argument `{kw.arg}` of "
                    f"jitted `{fname}`",
                )
        if _is_partial(node) and node.args:
            target = node.args[0]
            tname = target.id if isinstance(target, ast.Name) else None
            tstatic = self.index.static_jitted.get(tname or "", set())
            for kw in node.keywords:
                if kw.arg in tstatic and _is_array_expr(kw.value):
                    self._emit(
                        "static-arg-array", node, scope,
                        f"partial binds array to static argument "
                        f"`{kw.arg}` of jitted `{tname}`",
                    )

    # -- function entry ----------------------------------------------------

    def _enter_function(self, fn, ctx: _Ctx) -> None:
        fq = self._join(ctx.qual, fn.name)
        dec_traced, static = _decorator_trace_info(fn)
        static = set(static) | self.index.static_names.get(fn.name, set())
        traced = (
            ctx.traced or dec_traced or fn.name in self.index.traced_names
        )
        params = set(_param_names(fn)) - static
        traced_locals = (
            frozenset(_collect_traced_locals(fn, params))
            if traced else frozenset()
        )

        # array defaults feeding static args
        defaults = fn.args.defaults
        if static and defaults:
            with_defaults = (fn.args.posonlyargs + fn.args.args)
            with_defaults = with_defaults[-len(defaults):]
            for p, d in zip(with_defaults, defaults):
                if p.arg in static and _is_array_expr(d):
                    self._emit(
                        "static-arg-array", d, fq,
                        f"array default on static argument `{p.arg}`",
                    )
        for p, d in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
            if d is not None and p.arg in static and _is_array_expr(d):
                self._emit(
                    "static-arg-array", d, fq,
                    f"array default on static argument `{p.arg}`",
                )

        self._check_tracer_gate(fn, fq)

        inner = ctx.with_(
            qual=fq, traced=traced, traced_locals=traced_locals,
            guards=(), safe_names=frozenset(static | ctx.safe_names),
        )
        guard_line = _early_tracer_return_line(fn)
        if guard_line is None:
            for s in fn.body:
                self._walk_stmt(s, inner)
            return
        # `if tracer is None: return ...` — everything after runs
        # tracer-present.
        guarded = inner.with_(guards=("tracer",))
        for s in fn.body:
            self._walk_stmt(s, inner if s.lineno <= guard_line else guarded)

    def _check_tracer_gate(self, fn, fq: str) -> None:
        if "tracer" not in {
            p.arg for p in (
                fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
            )
        }:
            return
        span_calls = []
        has_guard = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = _dotted(node.func) or ""
                if name in ("tracer.span", "tracer.block"):
                    span_calls.append(node)
                if name.rsplit(".", 1)[-1] == "maybe_span":
                    has_guard = True
            if isinstance(node, ast.If):
                ok, _ = _is_tracer_none_test(node.test)
                has_guard = has_guard or ok
        if span_calls and not has_guard:
            self._emit(
                "tracer-gate", span_calls[0], fq,
                "calls tracer.span/block with no `tracer is None` "
                "fast path",
            )

    @staticmethod
    def _join(qual: str, name: str) -> str:
        return f"{qual}.{name}" if qual else name


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------

def lint_file(path: str | Path, root: str | Path | None = None
              ) -> list[Finding]:
    path = Path(path).resolve()
    rel = str(path.relative_to(root)) if root else str(path)
    source = path.read_text()
    try:
        tree = ast.parse(source)
    except SyntaxError as err:
        return [Finding(
            rule="traced-cond", path=rel, line=err.lineno or 0,
            scope="<module>", message=f"unparseable: {err.msg}",
        )]
    return _FileLinter(rel, source, tree).run()


def lint_tree(root: str | Path) -> list[Finding]:
    """Lint every ``.py`` under ``root`` (paths reported relative to the
    repo root when ``root`` sits inside one)."""
    root = Path(root)
    repo = _repo_root(root)
    findings: list[Finding] = []
    for path in sorted(root.rglob("*.py")):
        if "_vendor" in path.parts:
            continue
        findings.extend(lint_file(path, root=repo))
    return findings


def _repo_root(start: Path) -> Path | None:
    p = start.resolve()
    for cand in (p, *p.parents):
        if (cand / ".git").exists() or (cand / "pyproject.toml").exists():
            return cand
    return None
