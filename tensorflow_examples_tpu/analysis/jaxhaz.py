"""JAX hazard pass (graftlint pass 2, ISSUE 14 tentpole).

Three hazard families, all tuned to this repo's serving/training
idioms (the engine's AOT-warmed ladder of ``jax.jit(...,
donate_argnums=...)`` programs, the ``_run_compiled`` donation-recovery
funnel, the one-bulk-sync-per-step decode hot path):

**(a) Traced-value branching** — inside functions reachable from a
``jax.jit`` entry point (decorated, passed directly, or bound through
``functools.partial``), a Python ``if``/``while``/ternary on a traced
parameter recompiles per value or fails at trace time. The pass
resolves partial-bound leading arguments as static (the engine's
``partial(self._impl, bucket)`` ladder idiom), honors
``static_argnums``/``static_argnames``, treats ``del X  # static`` as
a static declaration, and skips the obviously-host-side shapes
(``is None`` checks, comparisons against string constants,
``isinstance``) plus config-ish parameter names. Reachability is a
same-module call-graph closure (depth-capped), matched by bare name —
heuristic on purpose; the fixtures pin exactly what it must catch.

**(b) Implicit host syncs** — ``.item()``, ``np.asarray``/``np.array``,
``jax.device_get`` and ``float()/int()/bool()`` on traced values force
a device->host transfer (or a trace-time concretization error). Inside
jit-reachable code they are always flagged; on the host side they are
flagged inside functions carrying the ``# graftlint: hot-path`` marker
comment on their ``def`` line — the decode/verify host entries, where
every sync beyond the accepted one-bulk-``np.asarray``-per-step shows
up directly in TPOT. The accepted syncs live in the committed
baseline: explicit and counted.

**(c) Use-after-donate** — an argument passed at a donated position of
a ``donate_argnums`` program is consumed; reading it afterwards is the
"Array has been deleted" heisenbug. The pass registers donating
callables (``F = jax.jit(fn, donate_argnums=(1,))``, including the
engine's ``self._fns = {b: sentinel.wrap(jax.jit(...), ...)}`` ladder
dicts) and — repo-natively — sees through
``self._run_compiled(kind, fn, *args)``, the engine's one donation
funnel, mapping ``donate_argnums`` onto ``args``. After a donating
call, any read of the same expression (a name or dotted attribute
chain) before it is reassigned flags. The engine's own pattern passes
by construction (``InferenceEngine._call``): the pool's
``self.pool.kv_state()`` — a fresh tuple of the per-layer
``[NB, BS, H*D]`` arrays, built in the call and never read again —
replaced by ``self.pool.set_kv_state(kv)`` from the outputs.
"""

from __future__ import annotations

import ast

from tensorflow_examples_tpu.analysis import common

# Parameter names that are host-side configuration by strong repo
# convention: branching on them is static dispatch, not traced control
# flow.
_STATIC_NAMEISH = frozenset({
    "self", "cls", "cfg", "config", "model_cfg", "impl", "mesh",
    "dtype", "axis", "axis_name", "name", "kind", "bucket", "mode",
})

_HOT_PATH_MARK = "graftlint: hot-path"
_SYNC_MODULES = {"np", "numpy"}


# --------------------------------------------------------------- roots


class _JitRoot:
    def __init__(self, func_name: str, bound: int, static: set[str],
                 donate: tuple[int, ...],
                 static_nums: tuple[int, ...] = (),
                 donate_names: tuple[str, ...] = ()):
        self.func_name = func_name  # bare function/method name
        self.bound = bound          # leading positional args bound by partial
        self.static = static        # statically-known parameter names
        self.donate = donate        # donate_argnums of the WRAPPED callable
        self.donate_names = donate_names  # donate_argnames: resolved to
        #                                   indices against the def in
        #                                   _collect_roots_and_donors
        self.static_nums = static_nums  # static_argnums: indices into
        #                                 the wrapped callable's args,
        #                                 resolved against the def in
        #                                 _reachable (self excluded,
        #                                 partial binds offset)


def _const_int_tuple(node: ast.AST | None) -> tuple[int, ...]:
    if node is None:
        return ()
    try:
        v = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return ()
    if isinstance(v, int):
        return (v,)
    if isinstance(v, (tuple, list)) and all(
        isinstance(i, int) for i in v
    ):
        return tuple(v)
    return ()


def _const_str_tuple(node: ast.AST | None) -> tuple[str, ...]:
    if node is None:
        return ()
    try:
        v = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return ()
    if isinstance(v, str):
        return (v,)
    if isinstance(v, (tuple, list)) and all(
        isinstance(i, str) for i in v
    ):
        return tuple(v)
    return ()


def _is_jit_callable(node: ast.AST) -> bool:
    """``jax.jit`` / bare ``jit`` as a call target."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    return (
        isinstance(node, ast.Attribute) and node.attr == "jit"
        and isinstance(node.value, ast.Name) and node.value.id == "jax"
    )


def _is_partial(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "partial"
    return isinstance(node, ast.Attribute) and node.attr == "partial"


def _target_name(node: ast.AST) -> tuple[str, int] | None:
    """Resolve a jit() first argument to (bare name, n bound leading
    args): ``f`` -> (f, 0); ``self._impl`` -> (_impl, 0);
    ``partial(self._impl, b)`` / ``functools.partial(f, a, b)`` ->
    (name, len(bound)). Sees through a call that hands its first
    argument back decorated (the engine's ``_named(partial(...), tag)``,
    which only sets ``__name__``)."""
    if isinstance(node, ast.Name):
        return node.id, 0
    if isinstance(node, ast.Attribute):
        return node.attr, 0
    if isinstance(node, ast.Call) and node.args:
        inner = _target_name(node.args[0])
        if inner is None:
            return None
        if _is_partial(node.func):
            return inner[0], inner[1] + len(node.args) - 1
        return inner
    return None


def _find_jit_call(node: ast.AST) -> ast.Call | None:
    """The jax.jit(...) call inside ``node`` (sees through wrapper
    calls like ``sentinel.wrap(jax.jit(...), label)`` and dict/list
    comprehensions)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _is_jit_callable(sub.func):
            return sub
    return None


def _jit_root_from_call(call: ast.Call) -> _JitRoot | None:
    if not call.args:
        return None
    resolved = _target_name(call.args[0])
    if resolved is None:
        return None
    name, bound = resolved
    static: set[str] = set()
    static_nums: tuple[int, ...] = ()
    donate: tuple[int, ...] = ()
    donate_names: tuple[str, ...] = ()
    for kw in call.keywords:
        if kw.arg in ("static_argnames",):
            static.update(_const_str_tuple(kw.value))
        elif kw.arg == "static_argnums":
            static_nums = _const_int_tuple(kw.value)
        elif kw.arg == "donate_argnums":
            donate = _const_int_tuple(kw.value)
        elif kw.arg == "donate_argnames":
            donate_names = _const_str_tuple(kw.value)
    if not name:
        return None
    return _JitRoot(name, bound, static, donate, static_nums,
                    donate_names)


def _collect_roots_and_donors(src: common.SourceFile):
    """(roots by function name, donating callables).

    Donating callables maps a call-site spelling — the bare final name
    of the assigned target (``_decode_fns``, ``step_fn``) — to the
    wrapped program's donate_argnums."""
    roots: dict[str, _JitRoot] = {}
    donors: dict[str, tuple[int, ...]] = {}
    params_by_name: dict[str, list[str]] = {}
    for node in ast.walk(src.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            params_by_name.setdefault(
                node.name, [a.arg for a in node.args.args]
            )
    for node in ast.walk(src.tree):
        # @jax.jit / @partial(jax.jit, ...) decorated defs
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                call = None
                if isinstance(dec, ast.Call) and _is_jit_callable(dec.func):
                    call = dec
                elif isinstance(dec, ast.Call) and _is_partial(dec.func) \
                        and dec.args and _is_jit_callable(dec.args[0]):
                    call = dec
                elif _is_jit_callable(dec):
                    roots.setdefault(
                        node.name, _JitRoot(node.name, 0, set(), ())
                    )
                    continue
                if call is None:
                    continue
                static: set[str] = set()
                donate: tuple[int, ...] = ()
                params = [a.arg for a in node.args.args]
                for kw in call.keywords:
                    if kw.arg == "static_argnames":
                        static.update(_const_str_tuple(kw.value))
                    elif kw.arg == "static_argnums":
                        for i in _const_int_tuple(kw.value):
                            if 0 <= i < len(params):
                                static.add(params[i])
                    elif kw.arg == "donate_argnums":
                        donate = _const_int_tuple(kw.value)
                    elif kw.arg == "donate_argnames":
                        donate = donate + tuple(
                            params.index(n)
                            for n in _const_str_tuple(kw.value)
                            if n in params
                        )
                roots[node.name] = _JitRoot(node.name, 0, static, donate)
                if donate:
                    # A decorated donating def is called by its own
                    # name — it is a donor exactly like an assigned
                    # jitted callable (the docs advertise decorators
                    # as pass-(c) roots).
                    donors[node.name] = donate
        elif isinstance(node, ast.Assign):
            call = _find_jit_call(node.value)
            if call is None:
                continue
            root = _jit_root_from_call(call)
            if root is None:
                continue
            if root.donate_names:
                # donate_argnames name the WRAPPED callable's params;
                # a call site donates at position (param index, minus
                # self, minus any partial-bound leading args).
                params = params_by_name.get(root.func_name, [])
                base = 1 if params[:1] == ["self"] else 0
                root.donate = root.donate + tuple(
                    j for j in (
                        params.index(n) - base - root.bound
                        for n in root.donate_names if n in params
                    ) if j >= 0
                )
            # static_argnums indexes the wrapped callable's params —
            # resolved later against the def; record the root.
            existing = roots.get(root.func_name)
            if existing is None or root.donate:
                roots[root.func_name] = root
            if root.donate:
                for t in node.targets:
                    tail = None
                    if isinstance(t, ast.Name):
                        tail = t.id
                    elif isinstance(t, ast.Attribute):
                        tail = t.attr
                    if tail:
                        donors[tail] = root.donate
    return roots, donors


# --------------------------------------------------------- reachability


def _index_functions(src: common.SourceFile):
    fns: dict[str, list[ast.FunctionDef]] = {}
    for node in ast.walk(src.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns.setdefault(node.name, []).append(node)
    return fns


def _called_names(fn: ast.FunctionDef) -> set[str]:
    out: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name):
                out.add(node.func.id)
            elif isinstance(node.func, ast.Attribute) and isinstance(
                node.func.value, ast.Name
            ) and node.func.value.id == "self":
                out.add(node.func.attr)
    return out


def _reachable(roots: dict[str, "_JitRoot"], fns, max_depth: int = 3):
    """{function name: static param names} closure from the jit roots.
    Non-root reachable functions get an empty static set (everything
    they receive may be traced)."""
    seen: dict[str, set[str]] = {}
    root_static: dict[str, set[str]] = {}
    frontier: list[tuple[str, int, set[str]]] = []
    for name, root in roots.items():
        defs = fns.get(name, [])
        static = set(root.static)
        for d in defs:
            params = [a.arg for a in d.args.args]
            base = 1 if params[:1] == ["self"] else 0
            static.update(params[base:base + root.bound])
            # static_argnums index the WRAPPED callable's positional
            # args — i.e. past `self` and past any partial-bound
            # leading args.
            for i in root.static_nums:
                j = base + root.bound + i
                if 0 <= j < len(params):
                    static.add(params[j])
        root_static[name] = static
        frontier.append((name, 0, static))
    while frontier:
        name, depth, static = frontier.pop()
        if name in seen:
            seen[name] &= static  # keep only commonly-static names
            continue
        seen[name] = set(static)
        if depth >= max_depth:
            continue
        for d in fns.get(name, []):
            for callee in _called_names(d):
                if callee in fns and callee not in seen:
                    frontier.append((callee, depth + 1, set()))
    # A root's OWN static declaration is authoritative for its body:
    # when the BFS reached it first as some other root's callee (empty
    # static set), the intersection above clobbered the declared
    # statics and manufactured traced-branch findings on host-dispatch
    # branches the jit boundary makes concrete.
    for name, static in root_static.items():
        if name in seen:
            seen[name] |= static
    return seen


# ------------------------------------------------------------- checks


def _traced_params(fn: ast.FunctionDef, static: set[str],
                   src: common.SourceFile) -> set[str]:
    args = fn.args
    names = [a.arg for a in (
        args.posonlyargs + args.args + args.kwonlyargs
    )]
    traced = {
        n for n in names
        if n not in static and n not in _STATIC_NAMEISH
    }
    # `del bucket  # static: ...` — the repo's static-marker idiom.
    for node in ast.walk(fn):
        if isinstance(node, ast.Delete) and "static" in src.comment(
            node.lineno
        ):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    traced.discard(t.id)
    return traced


def _names_in(node: ast.AST) -> set[str]:
    return {
        n.id for n in ast.walk(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


def _branch_names(test: ast.AST) -> set[str]:
    """Names a branch condition actually *traces* on: every Name load
    except those only ever passed to ``len()`` — ``len`` of a pytree
    tuple (``if len(kv) == 4:``) or of a traced array is host-side
    structure/shape, the repo's quantized-vs-f32 dispatch idiom."""
    all_names: dict[str, int] = {}
    len_names: dict[str, int] = {}
    for n in ast.walk(test):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
            all_names[n.id] = all_names.get(n.id, 0) + 1
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                and n.func.id == "len":
            for arg in n.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name) and isinstance(
                        sub.ctx, ast.Load
                    ):
                        len_names[sub.id] = len_names.get(sub.id, 0) + 1
    return {
        name for name, count in all_names.items()
        if count > len_names.get(name, 0) and name != "len"
    }


def _static_shaped_test(test: ast.AST) -> bool:
    """Conditions that are host-side dispatch even when they mention a
    parameter: None checks, string-constant comparisons, isinstance."""
    if isinstance(test, ast.Compare):
        if any(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return True
        operands = [test.left] + list(test.comparators)
        if any(
            isinstance(o, ast.Constant) and isinstance(o.value, str)
            for o in operands
        ):
            return True
    if isinstance(test, ast.Call) and isinstance(test.func, ast.Name) \
            and test.func.id in ("isinstance", "callable", "hasattr"):
        return True
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _static_shaped_test(test.operand)
    if isinstance(test, ast.BoolOp):
        return all(_static_shaped_test(v) for v in test.values)
    return False


def _sync_call_kind(node: ast.Call, traced: set[str] | None) -> str | None:
    """Classify a call as a host sync. ``traced=None`` means "flag
    regardless of the argument" (hot-path mode for the unambiguous
    syncs); otherwise float/int/bool only flag on traced names."""
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "item" and not node.args:
            return ".item()"
        if f.attr in ("asarray", "array") and isinstance(
            f.value, ast.Name
        ) and f.value.id in _SYNC_MODULES:
            if traced is None or (
                node.args and _names_in(node.args[0]) & traced
            ):
                return f"np.{f.attr}"
        if f.attr == "device_get":
            return "jax.device_get"
    if isinstance(f, ast.Name) and f.id in ("float", "int", "bool"):
        # Only a sync when applied to a TRACED value — in hot-path
        # mode (traced=None) the argument's host/device nature is
        # unknowable statically, and int() over host lists/ints is the
        # bread and butter of the decode loop, so only the unambiguous
        # syncs flag there.
        if traced is not None and node.args and isinstance(
            node.args[0], ast.Name
        ) and node.args[0].id in traced:
            return f"{f.id}()"
    return None


def _walk_shallow(fn):
    """Walk ``fn``'s body WITHOUT descending into nested def/lambda
    subtrees — ``ast.walk`` does not prune, and a nested function's
    parameters shadow the outer traced set (its body is its own,
    separately-reached scope)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _check_jitted_fn(src, fn, static, findings) -> None:
    traced = _traced_params(fn, static, src)
    scope = src.scope_of(fn) or "-"
    scope = f"{scope}.{fn.name}" if scope != "-" else fn.name
    for node in _walk_shallow(fn):
        test = None
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            test = node.test
        if test is not None and not _static_shaped_test(test):
            hits = sorted(_branch_names(test) & traced)
            if hits and not src.ignored(node.lineno):
                findings.append(common.Finding(
                    pass_name="jax", path=src.rel, line=node.lineno,
                    scope=scope,
                    detail=f"traced-branch:{','.join(hits)}",
                    message=(
                        "python branch on traced value(s) "
                        f"{', '.join(hits)} inside a jit-reachable "
                        "function (use lax.cond/select, or mark the "
                        "argument static)"
                    ),
                ))
        if isinstance(node, ast.Call):
            kind = _sync_call_kind(node, traced)
            if kind and not src.ignored(node.lineno):
                findings.append(common.Finding(
                    pass_name="jax", path=src.rel, line=node.lineno,
                    scope=scope, detail=f"traced-sync:{kind}",
                    message=(
                        f"host sync {kind} inside a jit-reachable "
                        "function (concretizes a traced value)"
                    ),
                ))


def _check_hot_path_fn(src, fn, findings) -> None:
    scope = src.scope_of(fn) or "-"
    scope = f"{scope}.{fn.name}" if scope != "-" else fn.name
    for node in _walk_shallow(fn):
        if isinstance(node, ast.Call):
            kind = _sync_call_kind(node, traced=None)
            if kind and not src.ignored(node.lineno):
                findings.append(common.Finding(
                    pass_name="jax", path=src.rel, line=node.lineno,
                    scope=scope, detail=f"host-sync:{kind}",
                    message=(
                        f"host sync {kind} on the marked hot path "
                        "(each one stalls the decode/verify loop; "
                        "batch syncs, or baseline the accepted one)"
                    ),
                ))


# ----------------------------------------------------- use-after-donate


def _expr_text(node: ast.AST) -> str | None:
    """A trackable donated-argument spelling: a bare name or a dotted
    attribute chain (``kv``, ``self.pool.k``). Calls/subscripts are
    untrackable -> None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _expr_text(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _donating_call(node: ast.Call, donors: dict[str, tuple[int, ...]]
                   ) -> list[ast.AST]:
    """Donated argument expressions of this call (empty when it is not
    a donating call). Sees through the engine's ``_run_compiled(kind,
    fn, *args)`` funnel: donate_argnums of ``fn`` index into ``args``."""
    f = node.func
    tail = None
    if isinstance(f, ast.Name):
        tail = f.id
    elif isinstance(f, ast.Attribute):
        tail = f.attr
    elif isinstance(f, ast.Subscript):  # self._fns[bucket](...)
        inner = f.value
        if isinstance(inner, ast.Attribute):
            tail = inner.attr
        elif isinstance(inner, ast.Name):
            tail = inner.id
    if tail == "_run_compiled" and len(node.args) >= 2:
        fn_expr = node.args[1]
        inner_tail = None
        if isinstance(fn_expr, ast.Subscript):
            fn_expr = fn_expr.value
        if isinstance(fn_expr, ast.Attribute):
            inner_tail = fn_expr.attr
        elif isinstance(fn_expr, ast.Name):
            inner_tail = fn_expr.id
        donate = donors.get(inner_tail or "", ())
        rest = node.args[2:]
        return [rest[i] for i in donate if i < len(rest)]
    donate = donors.get(tail or "", ())
    return [node.args[i] for i in donate if i < len(node.args)]


def _assign_targets_text(node: ast.AST) -> set[str]:
    out: set[str] = set()
    targets: list[ast.AST] = []
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
    elif isinstance(node, ast.For):
        targets = [node.target]
    for t in targets:
        for sub in ast.walk(t):
            text = _expr_text(sub)
            if text:
                out.add(text)
    return out


def _check_use_after_donate(src, fn, donors, findings) -> None:
    scope = src.scope_of(fn) or "-"
    scope = f"{scope}.{fn.name}" if scope != "-" else fn.name
    events: list[tuple[tuple[int, int], str, object]] = []
    # _walk_shallow, like the branch/sync checks: a nested def's
    # parameters are fresh bindings, not reads of the outer (possibly
    # donated) names.
    for node in _walk_shallow(fn):
        pos = (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))
        if isinstance(node, ast.Call):
            donated = [
                t for t in map(_expr_text, _donating_call(node, donors))
                if t
            ]
            if donated:
                # The donation takes effect at the call's END: the
                # call's own argument reads (including the donated
                # expression itself) evaluate first and are the
                # donation, not a use-after — while a SECOND donating
                # call re-passing the same buffer sorts after the
                # first call's end and flags (the classic
                # double-donate "Array has been deleted").
                end = (
                    getattr(node, "end_lineno", pos[0]) or pos[0],
                    getattr(node, "end_col_offset", pos[1]) or pos[1],
                )
                events.append((end, "donate", (node, donated)))
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign,
                             ast.For)):
            texts = _assign_targets_text(node)
            if texts:
                # Assignments clear at the END of the statement — the
                # RHS evaluates first, so `kv = kv + 1` after a
                # donation is a real read of the deleted array and
                # must flag (clearing at statement START masked it).
                # The engine's donate-and-reassign-in-one-statement
                # idiom stays clean: its donating call also ends
                # before the statement does, and the donate event's
                # enclosing-statement target check exempts it anyway.
                # A `for` clears at its TARGET (the header binds the
                # name before each body iteration), not at the end of
                # the whole loop body.
                anchor = node.target if isinstance(node, ast.For) else node
                end = (
                    getattr(anchor, "end_lineno", pos[0]) or pos[0],
                    getattr(anchor, "end_col_offset", pos[1]) or pos[1],
                )
                events.append((end, "assign", texts))
        if isinstance(node, (ast.Name, ast.Attribute)) and isinstance(
            getattr(node, "ctx", None), ast.Load
        ):
            text = _expr_text(node)
            if text:
                events.append((pos, "read", (node, text)))
    events.sort(key=lambda e: e[0])
    dead: dict[str, int] = {}  # expr text -> donate line
    for pos, kind, payload in events:
        if kind == "assign":
            for text in payload:
                dead.pop(text, None)
        elif kind == "read":
            node, text = payload
            line = dead.get(text)
            if line is not None and not src.ignored(node.lineno):
                findings.append(common.Finding(
                    pass_name="jax", path=src.rel, line=node.lineno,
                    scope=scope, detail=f"use-after-donate:{text}",
                    message=(
                        f"read of {text!r} after it was passed at a "
                        f"donated position (line {line}) — the buffer "
                        "was consumed; reassign from the program's "
                        "outputs first"
                    ),
                ))
        elif kind == "donate":
            node, texts = payload
            # Same-statement reassignment (targets of the enclosing
            # Assign) already cleared via the assign event at the same
            # position sorting earlier is NOT guaranteed; resolve by
            # checking the enclosing statement's targets explicitly.
            parent = src.parent(node)
            while parent is not None and not isinstance(
                parent,
                (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Expr,
                 ast.Return),
            ):
                parent = src.parent(parent)
            cleared = _assign_targets_text(parent) if parent else set()
            for text in texts:
                if text not in cleared:
                    dead[text] = node.lineno


def _hot_path_marked(src, fn) -> bool:
    """Marker comment on the ``def`` line or anywhere in the
    contiguous comment block right above the function — where "the
    function" starts at its FIRST decorator (``fn.lineno`` is the
    ``def`` line, so a scan from there would stop at the decorator
    and silently exempt decorated hot paths)."""
    if _HOT_PATH_MARK in src.comment(fn.lineno):
        return True
    start = min(
        [fn.lineno] + [d.lineno for d in fn.decorator_list]
    )
    line = start - 1
    while line > 0 and src.comment(line):
        if _HOT_PATH_MARK in src.comment(line):
            return True
        line -= 1
    return False


# ---------------------------------------------------------------- main


def check_file(src: common.SourceFile) -> list[common.Finding]:
    findings: list[common.Finding] = []
    roots, donors = _collect_roots_and_donors(src)
    fns = _index_functions(src)
    reach = _reachable(roots, fns)
    for name, static in sorted(reach.items()):
        for fn in fns.get(name, []):
            _check_jitted_fn(src, fn, static, findings)
    for defs in fns.values():
        for fn in defs:
            if _hot_path_marked(src, fn):
                _check_hot_path_fn(src, fn, findings)
            if donors and fn.name not in reach:
                _check_use_after_donate(src, fn, donors, findings)
    return findings


def run(paths, repo_root) -> list[common.Finding]:
    findings: list[common.Finding] = []
    for path in common.iter_python_files(paths):
        src = common.load_source(path, repo_root)
        if src is not None:
            findings.extend(check_file(src))
    return findings
