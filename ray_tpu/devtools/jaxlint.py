"""jaxlint — the JAX-aware raylint checks (static half of the compile-churn
and host-sync tier; ``ray_tpu.devtools.jitcheck`` is the runtime half).

raylint (``ray_tpu.devtools.lint``) covers locks, RPC contracts and
resource lifecycles but is blind to the JAX side of the tree, where the
costly mistakes are invisible to every functional test: a ``jax.jit``
constructed per call compiles from scratch every time, one stray
``.item()`` in the decode loop serializes the device pipeline, a reused
PRNG key silently correlates samples, and reading a donated buffer after
the call is garbage on real accelerators. These four checks run as extra
phases inside :class:`ray_tpu.devtools.lint.Linter` — same AST cache,
same ``# raylint: ignore[...]`` pragmas, same fingerprint/baseline
machinery, same ``ray-tpu-lint`` CLI and CI gate.

Checks
======
``jit-churn``
    A ``jax.jit(...)`` / ``functools.partial(jax.jit, ...)`` constructed
    in FUNCTION scope (so: re-executed per call) whose result neither
    escapes (returned / yielded — the one-shot builder pattern), nor is
    cached (assigned to a ``self.`` / module attribute or container
    slot), nor is handed to another call (registered elsewhere). Each
    call to the enclosing function then pays a fresh trace + XLA compile.
    Also: call sites that feed DATA-DERIVED Python scalars (``len(x)``,
    ``x.shape[i]``, ``int(...)``, ``x.size`` and arithmetic on them)
    into ``static_argnums`` / ``static_argnames`` positions of a
    resolved jitted callable — one full compile per distinct value.
``host-sync``
    Inside the declared hot-path scopes (:data:`HOT_SCOPES` — the engine
    step/decode path, the token generator, the RL sample/update loops;
    coverage-guarded so a rename can't silently retire a scope), any
    implicit device→host synchronization on a value the intra-function
    taint walk proves device-resident: ``np.asarray`` / ``np.array``,
    ``float()`` / ``int()`` / ``bool()`` coercion, ``.item()`` /
    ``.tolist()``, and truthiness tests. The sanctioned exit is an
    EXPLICIT batched ``jax.device_get`` — its results are host values
    and untainted.
``key-reuse``
    Intra-function dataflow: a PRNG key binding (``jax.random.key`` /
    ``PRNGKey`` / ``split`` / ``fold_in`` result, or a parameter named
    like a key) consumed by ≥ 2 ``jax.random.*`` calls with no
    intervening ``split`` / reassignment — the second draw repeats the
    first's randomness. ``fold_in(key, i)`` is the sanctioned
    derive-many pattern and does not count as consumption.
``donate-uaf``
    A binding passed at a ``donate_argnums`` position of a resolved
    jitted callable and READ again afterwards without rebinding. The
    donated buffer is dead after dispatch on real accelerators;
    ``x = f(x)`` (rebind-through) is the sanctioned shape.

All findings fingerprint without line numbers (baseline-stable) and obey
the standard pragma on the finding line or the comment lines above it.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ray_tpu.devtools.lint import Finding

__all__ = ["JAX_CHECKS", "HOT_SCOPES", "DEVICE_FN_NAMES",
           "check_jit_churn", "check_host_sync", "check_key_reuse",
           "check_donate_uaf"]

JAX_CHECKS = ("jit-churn", "host-sync", "key-reuse", "donate-uaf")

#: The hot-path scopes host-sync patrols: scan-root-relative path suffix →
#: function/method names that constitute the per-step / per-token path.
#: Coverage-guarded: when the file is in the scan set, every named scope
#: must exist, so a rename retires the declaration loudly, not silently
#: (the PR 6 hot-module discipline).
HOT_SCOPES: Dict[str, Tuple[str, ...]] = {
    "serve/llm.py": ("_step_inner", "_deliver", "_run_decode"),
    "models/generate.py": ("generate",),
    "rllib/env_runner.py": ("sample",),
    "rllib/learner.py": ("update",),
    "rllib/inference.py": ("_run_batch",),
}

#: Method names whose call results are device values wherever they appear
#: (the model forward surface used by the RL stack).
DEVICE_FN_NAMES = {"forward_inference", "forward_train", "sample_action",
                   "init_params"}

#: Dotted-call prefixes that produce device-resident values.
_TAINT_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "jax.random.",
                   "jax.nn.", "jax.scipy.", "jax.ops.")

#: jax.* calls that return HOST values (never taint).
_JAX_HOST_SAFE = {
    "jax.device_get", "jax.device_count", "jax.local_device_count",
    "jax.devices", "jax.local_devices", "jax.process_index",
    "jax.process_count", "jax.default_backend", "jax.eval_shape",
}

_NP_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                  "onp.asarray", "onp.array"}

_KEY_MAKERS = {"key", "PRNGKey", "split", "fold_in", "wrap_key_data",
               "clone"}


def _dotted(node: ast.expr) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jax_jit(node: ast.expr) -> bool:
    """node is the callable ``jax.jit`` (or bare ``jit`` imported from
    jax)."""
    d = _dotted(node)
    return d in ("jax.jit", "jit")


def _jit_call(node: ast.expr) -> Optional[ast.Call]:
    """The ``jax.jit(...)`` call if node is one, directly or through
    ``functools.partial(jax.jit, ...)``. Returns the call whose keywords
    carry static/donate info."""
    if not isinstance(node, ast.Call):
        return None
    if _is_jax_jit(node.func):
        return node
    d = _dotted(node.func)
    if d in ("partial", "functools.partial") and node.args \
            and _is_jax_jit(node.args[0]):
        return node
    return None


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _int_tuple(node: Optional[ast.expr]) -> Tuple[int, ...]:
    """Literal ints out of ``(0, 2)`` / ``0`` / ``[1]``; () if dynamic."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
        return tuple(out)
    return ()


def _str_tuple(node: Optional[ast.expr]) -> Tuple[str, ...]:
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


def _walk_functions(tree: ast.Module):
    """Yield (qualname, func_node, at_module_level) for every function/
    method, in source order, including nested defs."""
    def rec(node, prefix: str, module_level: bool):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                yield q, child, module_level
                yield from rec(child, q, False)
            elif isinstance(child, ast.ClassDef):
                q = f"{prefix}.{child.name}" if prefix else child.name
                yield from rec(child, q, module_level)
    yield from rec(tree, "", True)


def _is_data_derived(node: ast.expr) -> bool:
    """Expression yields a Python scalar computed FROM runtime data —
    ``len(x)``, ``int(x)``, ``x.shape[i]``, ``x.size``, ``x.ndim``, and
    arithmetic over those. One distinct value = one XLA compile when fed
    to a static argument."""
    if isinstance(node, ast.Call):
        d = _dotted(node.func)
        if d in ("len", "int"):
            return True
        if isinstance(node.func, ast.Attribute) and node.func.attr in (
                "item", "__len__"):
            return True
        return False
    if isinstance(node, ast.Attribute):
        return node.attr in ("size", "ndim", "nbytes")
    if isinstance(node, ast.Subscript):
        return (isinstance(node.value, ast.Attribute)
                and node.value.attr == "shape")
    if isinstance(node, ast.BinOp):
        return _is_data_derived(node.left) or _is_data_derived(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_data_derived(node.operand)
    return False


# ---------------------------------------------------------------------------
# jit-churn
# ---------------------------------------------------------------------------


class _JittedBinding:
    """A resolved jitted callable visible at module scope (or a decorated
    def): call sites can be checked against its static/donate positions."""

    __slots__ = ("name", "static_nums", "static_names", "donate_nums",
                 "self_offset")

    def __init__(self, name: str, call: ast.Call, self_offset: int = 0):
        self.name = name
        self.static_nums = _int_tuple(_kw(call, "static_argnums"))
        self.static_names = _str_tuple(_kw(call, "static_argnames"))
        self.donate_nums = _int_tuple(_kw(call, "donate_argnums"))
        self.self_offset = self_offset


def _collect_jitted_bindings(tree: ast.Module) -> Dict[str, _JittedBinding]:
    """name → binding for jitted callables resolvable by name: module-level
    ``f = jax.jit(g, ...)`` and ``@jax.jit`` / ``@partial(jax.jit, ...)``
    decorated defs (any nesting — resolution at call sites is by bare
    name, which is how the tree calls them)."""
    out: Dict[str, _JittedBinding] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            call = _jit_call(node.value)
            if call is not None:
                out[node.targets[0].id] = _JittedBinding(
                    node.targets[0].id, call)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                call = _jit_call(dec) if isinstance(dec, ast.Call) else None
                if call is None and _is_jax_jit(dec):
                    call = ast.Call(func=dec, args=[], keywords=[])
                if call is not None:
                    out[node.name] = _JittedBinding(node.name, call)
    return out


def check_jit_churn(linter, parsed: Sequence[Tuple[str, ast.Module, str]],
                    ) -> None:
    for rel, tree, _src in parsed:
        bindings = _collect_jitted_bindings(tree)
        for qual, fn, _mod in _walk_functions(tree):
            _jit_churn_in_function(linter, rel, qual, fn)
            _static_arg_calls(linter, rel, qual, fn, bindings)


def _jit_churn_in_function(linter, rel: str, qual: str, fn) -> None:
    """Per-call jit constructions inside ``fn`` whose result never
    escapes."""
    # name → construction line for local `n = jax.jit(...)` bindings
    local: Dict[str, int] = {}
    escaped: Set[str] = set()
    nested_defs = {c for c in ast.walk(fn)
                   if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef))
                   and c is not fn}

    def in_nested(node) -> bool:
        return any(node in ast.walk(d) for d in nested_defs)

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            call = _jit_call(node.value)
            if call is None:
                continue
            tgt = node.targets[0] if len(node.targets) == 1 else None
            if isinstance(tgt, ast.Name) and not in_nested(node):
                local[tgt.id] = node.lineno
            # self.x = jax.jit(...) / cache[k] = jax.jit(...): cached.
        elif isinstance(node, ast.Call):
            inner = _jit_call(node.func)
            if inner is not None:
                # jax.jit(f)(args): compiled and thrown away, every call.
                linter.add(Finding(
                    "jit-churn", rel, node.lineno, qual,
                    "jax.jit(...) constructed and called in one expression"
                    " — a fresh trace+compile on every call of this"
                    " function; cache the jitted callable",
                    f"immediate-jit-call:{_dotted(inner.args[0].func) if inner.args and isinstance(inner.args[0], ast.Call) else ast.dump(inner.args[0]) if inner.args else '?'}"))
            # name escaping into another call exempts it
            for arg in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(arg, ast.Name) and arg.id in local:
                    escaped.add(arg.id)
        elif isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
            val = node.value
            if val is None:
                continue
            if _jit_call(val) is not None:
                continue  # `return jax.jit(...)`: the one-shot builder shape
            # a name escapes if returned ITSELF; `return [fwd(x) ...]`
            # only returns call results — fwd still dies with the frame
            func_pos = {id(sub.func) for sub in ast.walk(val)
                        if isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Name)}
            for sub in ast.walk(val):
                if isinstance(sub, ast.Name) and sub.id in local \
                        and id(sub) not in func_pos:
                    escaped.add(sub.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            for dec in node.decorator_list:
                if _is_jax_jit(dec) or (isinstance(dec, ast.Call)
                                        and _jit_call(dec) is not None):
                    local[node.name] = node.lineno

    # names stored into attributes / subscripts (caches) also escape
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, (ast.Attribute, ast.Subscript)):
                    for sub in ast.walk(node.value):
                        if isinstance(sub, ast.Name) and sub.id in local:
                            escaped.add(sub.id)

    for name, line in sorted(local.items(), key=lambda kv: kv[1]):
        if name in escaped:
            continue
        linter.add(Finding(
            "jit-churn", rel, line, qual,
            f"'{name}' rebuilds jax.jit on every call of this function"
            " (the compile cache dies with the binding); cache it on"
            " self/module or return it from a builder",
            f"local-jit:{name}"))


def _static_arg_calls(linter, rel: str, qual: str, fn,
                      bindings: Dict[str, _JittedBinding]) -> None:
    """Call sites of resolved jitted callables feeding data-derived
    scalars into static positions."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = node.func.id if isinstance(node.func, ast.Name) else None
        if name is None and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "self":
            name = node.func.attr
        b = bindings.get(name) if name else None
        if b is None:
            continue
        for pos in b.static_nums:
            i = pos - b.self_offset
            if 0 <= i < len(node.args) and _is_data_derived(node.args[i]):
                linter.add(Finding(
                    "jit-churn", rel, node.lineno, qual,
                    f"data-derived scalar fed to static_argnums position"
                    f" {pos} of '{b.name}' — one full XLA compile per"
                    " distinct value; bucket it or make the arg traced",
                    f"static-data:{b.name}:{pos}"))
        for k in node.keywords:
            if k.arg in b.static_names and _is_data_derived(k.value):
                linter.add(Finding(
                    "jit-churn", rel, node.lineno, qual,
                    f"data-derived scalar fed to static argname"
                    f" '{k.arg}' of '{b.name}' — one full XLA compile per"
                    " distinct value; bucket it or make the arg traced",
                    f"static-data:{b.name}:{k.arg}"))


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


class _TaintWalk:
    """Linear taint walk over one hot function: which bindings hold
    device-resident values, and where they leak to the host implicitly.
    Loops are walked twice so cross-iteration flows surface; findings
    dedupe on (line, kind)."""

    def __init__(self, linter, rel: str, qual: str,
                 device_methods: Optional[Set[str]] = None):
        self.linter = linter
        self.rel = rel
        self.qual = qual
        self.taints: Set[str] = set()
        self.jit_names: Set[str] = set()
        self.seen: Set[Tuple[int, str]] = set()
        #: self-method names whose results are device values: the file's
        #: other hot scopes (`self._run_decode(...)`) plus every attr the
        #: class caches a jax.jit under (`self._sample_many = jax.jit(...)`)
        self.device_methods = device_methods or set()

    # -- tokens -------------------------------------------------------------

    @staticmethod
    def _token(node: ast.expr) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute) and isinstance(node.value,
                                                          ast.Name) \
                and node.value.id == "self":
            return f"self.{node.attr}"
        return None

    # -- taint evaluation ---------------------------------------------------

    def tainted(self, node: Optional[ast.expr]) -> bool:
        if node is None:
            return False
        tok = self._token(node)
        if tok is not None:
            return tok in self.taints
        if isinstance(node, ast.Call):
            return self._call_taints(node)
        if isinstance(node, ast.Attribute):
            # array metadata is host-resident — reading it never syncs
            if node.attr in ("shape", "dtype", "ndim", "size", "nbytes",
                             "sharding"):
                return False
            return self.tainted(node.value)
        if isinstance(node, (ast.Subscript, ast.Starred)):
            return self.tainted(node.value)
        if isinstance(node, ast.BinOp):
            return self.tainted(node.left) or self.tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.tainted(node.operand)
        if isinstance(node, ast.Compare):
            return self.tainted(node.left) or any(
                self.tainted(c) for c in node.comparators)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.tainted(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self.tainted(node.body) or self.tainted(node.orelse)
        if isinstance(node, ast.NamedExpr):
            return self.tainted(node.value)
        return False

    def _call_taints(self, node: ast.Call) -> bool:
        d = _dotted(node.func)
        if d is not None:
            if d in _JAX_HOST_SAFE or d in _NP_SYNC_CALLS:
                return False
            if d.startswith(_TAINT_PREFIXES) or d in ("jax.jit", "jax.vmap",
                                                      "jax.pmap",
                                                      "jax.grad"):
                return True
        if isinstance(node.func, ast.Name) and (
                node.func.id in self.jit_names
                or node.func.id in self.taints):
            # a call of a tainted binding: `df = self._pg.decode_fn(c)`
            # then `df(...)` — the callable came off the device path
            return True
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            # self._decode_fn(...), self._fns[b](...), model.forward_*(...)
            if attr.endswith("_fn") or attr in DEVICE_FN_NAMES:
                return True
            if attr in self.device_methods and isinstance(
                    node.func.value, ast.Name) \
                    and node.func.value.id == "self":
                return True
            if attr in ("item", "tolist"):
                return False  # host scalars (flagged as sinks separately)
            # method call on a tainted object stays on device
            return self.tainted(node.func.value)
        if isinstance(node.func, ast.Subscript):
            base = self._token(node.func.value)
            if base is not None and (base.endswith("_fns")
                                     or base.endswith("_fn")):
                return True
            return self.tainted(node.func.value)
        return False

    # -- findings -----------------------------------------------------------

    def _emit(self, line: int, kind: str, message: str) -> None:
        if (line, kind) in self.seen:
            return
        self.seen.add((line, kind))
        self.linter.add(Finding("host-sync", self.rel, line, self.qual,
                                message, kind))

    def check_sinks(self, expr: Optional[ast.expr]) -> None:
        if expr is None:
            return
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d in _NP_SYNC_CALLS and node.args \
                        and self.tainted(node.args[0]):
                    self._emit(node.lineno, f"np-sync:{d}",
                               f"{d}() on a device value inside a hot scope"
                               " — an implicit blocking sync; batch into"
                               " one jax.device_get per step")
                elif d in ("float", "int", "bool", "complex") and node.args \
                        and self.tainted(node.args[0]):
                    self._emit(node.lineno, f"coerce:{d}",
                               f"{d}() coercion of a device value inside a"
                               " hot scope syncs the pipeline; device_get"
                               " once, then coerce on host")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("item", "tolist") \
                        and self.tainted(node.func.value):
                    self._emit(node.lineno, f"item:{node.func.attr}",
                               f".{node.func.attr}() on a device value"
                               " inside a hot scope syncs the pipeline;"
                               " device_get once, then read on host")
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                self._comprehension(node)

    def _comprehension(self, node) -> None:
        added: List[str] = []
        for gen in node.generators:
            if self.tainted(gen.iter):
                for sub in ast.walk(gen.target):
                    tok = self._token(sub)
                    if tok and tok not in self.taints:
                        self.taints.add(tok)
                        added.append(tok)
            for cond in gen.ifs:
                self.truthiness(cond)
        if isinstance(node, ast.DictComp):
            self.check_sinks(node.key)
            self.check_sinks(node.value)
        else:
            self.check_sinks(node.elt)
        for tok in added:
            self.taints.discard(tok)

    def truthiness(self, test: ast.expr) -> None:
        self.check_sinks(test)
        probe = test
        while isinstance(probe, ast.UnaryOp) and isinstance(probe.op,
                                                            ast.Not):
            probe = probe.operand
        if isinstance(probe, ast.BoolOp):
            for v in probe.values:
                self.truthiness(v)
            return
        if self.tainted(probe):
            self._emit(test.lineno, "truthiness",
                       "truthiness test on a device value inside a hot"
                       " scope forces a blocking sync; device_get first")

    # -- statement walk -----------------------------------------------------

    def assign_to(self, target: ast.expr, is_tainted: bool) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self.assign_to(elt, is_tainted)
            return
        if isinstance(target, ast.Starred):
            self.assign_to(target.value, is_tainted)
            return
        tok = self._token(target)
        if tok is None:
            return
        if is_tainted:
            self.taints.add(tok)
        else:
            self.taints.discard(tok)

    def block(self, stmts: Sequence[ast.stmt]) -> None:
        for stmt in stmts:
            self.stmt(stmt)

    def stmt(self, s: ast.stmt) -> None:  # noqa: C901 — a dispatch table
        if isinstance(s, ast.Assign):
            self.check_sinks(s.value)
            t = self.tainted(s.value)
            if isinstance(s.value, ast.Call):
                if _jit_call(s.value) is not None and s.targets \
                        and isinstance(s.targets[0], ast.Name):
                    self.jit_names.add(s.targets[0].id)
            for tgt in s.targets:
                self.assign_to(tgt, t)
        elif isinstance(s, ast.AnnAssign) and s.value is not None:
            self.check_sinks(s.value)
            self.assign_to(s.target, self.tainted(s.value))
        elif isinstance(s, ast.AugAssign):
            self.check_sinks(s.value)
            if self.tainted(s.value):
                self.assign_to(s.target, True)
        elif isinstance(s, ast.Expr):
            self.check_sinks(s.value)
        elif isinstance(s, ast.Return):
            self.check_sinks(s.value)
        elif isinstance(s, ast.If):
            self.truthiness(s.test)
            self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, ast.While):
            for _ in range(2):
                self.truthiness(s.test)
                self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, ast.For):
            self.check_sinks(s.iter)
            it_tainted = self.tainted(s.iter)
            for _ in range(2):
                self.assign_to(s.target, it_tainted)
                self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self.check_sinks(item.context_expr)
                if item.optional_vars is not None:
                    self.assign_to(item.optional_vars,
                                   self.tainted(item.context_expr))
            self.block(s.body)
        elif isinstance(s, ast.Try):
            self.block(s.body)
            for h in s.handlers:
                self.block(h.body)
            self.block(s.orelse)
            self.block(s.finalbody)
        elif isinstance(s, ast.Assert):
            self.truthiness(s.test)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def (e.g. the generator closure inside `generate`):
            # walk it with the closure's taint state — per-token reads in
            # the inner loop are exactly what this check is for.
            self.block(s.body)
        # Import/Pass/Break/Continue/Raise/Delete/Global: nothing to taint


def _jit_cache_attrs(tree: ast.Module) -> Set[str]:
    """Attr names the file's classes cache jitted callables under:
    ``self.X = jax.jit(...)`` / ``partial(jax.jit, ...)`` anywhere."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _jit_call(node.value) is not None:
            for tgt in node.targets:
                if isinstance(tgt, ast.Attribute) \
                        and isinstance(tgt.value, ast.Name) \
                        and tgt.value.id == "self":
                    out.add(tgt.attr)
    return out


def check_host_sync(linter, parsed: Sequence[Tuple[str, ast.Module, str]],
                    ) -> None:
    for rel, tree, _src in parsed:
        scopes = None
        for key, names in HOT_SCOPES.items():
            if rel == key or rel.endswith("/" + key):
                scopes = set(names)
                break
        if scopes is None:
            continue
        device_methods = scopes | _jit_cache_attrs(tree)
        found: Set[str] = set()
        for qual, fn, _mod in _walk_functions(tree):
            if fn.name not in scopes:
                continue
            found.add(fn.name)
            walk = _TaintWalk(linter, rel, qual, device_methods)
            walk.block(fn.body)
        for missing in sorted(scopes - found):
            linter.add(Finding(
                "host-sync", rel, 1, "<file>",
                f"declared hot scope '{missing}' not found — update"
                " jaxlint.HOT_SCOPES so the decode path stays patrolled",
                f"hot-scope-missing:{missing}"))


# ---------------------------------------------------------------------------
# key-reuse
# ---------------------------------------------------------------------------


class _KeyWalk:
    """Count jax.random.* consumptions per key binding; ≥ 2 without an
    intervening split/rebind is reuse. Loop bodies run twice so
    once-per-iteration draws from a key bound OUTSIDE the loop flag."""

    def __init__(self, linter, rel: str, qual: str):
        self.linter = linter
        self.rel = rel
        self.qual = qual
        self.uses: Dict[str, int] = {}
        self.flagged: Set[str] = set()

    @staticmethod
    def _token(node: ast.expr) -> Optional[str]:
        return _TaintWalk._token(node)

    @staticmethod
    def _random_fn(call: ast.Call) -> Optional[str]:
        d = _dotted(call.func)
        if d is None:
            return None
        if d.startswith("jax.random.") or d.startswith("jrandom.") \
                or d.startswith("random_jax."):
            return d.rsplit(".", 1)[1]
        return None

    def _key_maker(self, value: ast.expr) -> bool:
        if isinstance(value, ast.Call):
            fn = self._random_fn(value)
            return fn in _KEY_MAKERS
        return False

    def bind(self, target: ast.expr) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self.bind(elt)
            return
        if isinstance(target, ast.Starred):
            self.bind(target.value)
            return
        tok = self._token(target)
        if tok is not None:
            self.uses[tok] = 0
            self.flagged.discard(tok)

    def unbind(self, target: ast.expr) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self.unbind(elt)
            return
        tok = self._token(target)
        if tok is not None:
            self.uses.pop(tok, None)

    def consume(self, call: ast.Call) -> None:
        fn = self._random_fn(call)
        if fn is None or fn == "fold_in":
            # fold_in(key, i) is the sanctioned derive-many pattern
            return
        for arg in call.args:
            tok = self._token(arg)
            if tok is None or tok not in self.uses:
                continue
            self.uses[tok] += 1
            if self.uses[tok] >= 2 and tok not in self.flagged:
                self.flagged.add(tok)
                self.linter.add(Finding(
                    "key-reuse", self.rel, call.lineno, self.qual,
                    f"PRNG key '{tok}' consumed by ≥2 jax.random calls"
                    " with no intervening split — the second draw repeats"
                    " the first's randomness",
                    f"key-reuse:{tok}"))

    def scan_calls(self, expr: Optional[ast.expr]) -> None:
        if expr is None:
            return
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                self.consume(node)

    def block(self, stmts: Sequence[ast.stmt]) -> None:
        for s in stmts:
            self.stmt(s)

    def stmt(self, s: ast.stmt) -> None:
        if isinstance(s, (ast.Assign, ast.AnnAssign)):
            value = s.value
            targets = s.targets if isinstance(s, ast.Assign) else [s.target]
            self.scan_calls(value)
            if value is not None and self._key_maker(value):
                for tgt in targets:
                    self.bind(tgt)
            else:
                for tgt in targets:
                    self.unbind(tgt)
        elif isinstance(s, ast.Expr):
            self.scan_calls(s.value)
        elif isinstance(s, ast.Return):
            self.scan_calls(s.value)
        elif isinstance(s, ast.If):
            # mutually exclusive branches: one draw per branch is NOT
            # reuse — walk each from the same snapshot, keep the max
            self.scan_calls(s.test)
            snap = dict(self.uses)
            self.block(s.body)
            after_body = self.uses
            self.uses = dict(snap)
            self.block(s.orelse)
            merged = dict(self.uses)
            for tok, n in after_body.items():
                merged[tok] = max(merged.get(tok, 0), n)
            self.uses = merged
        elif isinstance(s, (ast.While, ast.For)):
            if isinstance(s, ast.For):
                self.scan_calls(s.iter)
            for _ in range(2):
                if isinstance(s, ast.While):
                    self.scan_calls(s.test)
                self.block(s.body)
            self.block(s.orelse)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self.scan_calls(item.context_expr)
            self.block(s.body)
        elif isinstance(s, ast.Try):
            self.block(s.body)
            for h in s.handlers:
                self.block(h.body)
            self.block(s.orelse)
            self.block(s.finalbody)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # inline the nested def, but its parameters SHADOW outer keys
            # (`def nrm(key, ...)` gets a fresh key per call); closure
            # reads of non-shadowed keys still count.
            params = {a.arg for a in (s.args.posonlyargs + s.args.args
                                      + s.args.kwonlyargs)}
            shadowed = {tok: self.uses.pop(tok) for tok in list(self.uses)
                        if tok in params}
            self.block(s.body)
            for tok in params:
                self.uses.pop(tok, None)
            self.uses.update(shadowed)


_KEY_PARAM_HINTS = ("key", "rng")


def check_key_reuse(linter, parsed: Sequence[Tuple[str, ast.Module, str]],
                    ) -> None:
    for rel, tree, _src in parsed:
        for qual, fn, _mod in _walk_functions(tree):
            walk = _KeyWalk(linter, rel, qual)
            for arg in (fn.args.posonlyargs + fn.args.args
                        + fn.args.kwonlyargs):
                low = arg.arg.lower()
                if low in _KEY_PARAM_HINTS or low.endswith("_key") \
                        or low.endswith("_rng"):
                    walk.uses[arg.arg] = 0
            walk.block(fn.body)


# ---------------------------------------------------------------------------
# donate-uaf
# ---------------------------------------------------------------------------


def check_donate_uaf(linter, parsed: Sequence[Tuple[str, ast.Module, str]],
                     ) -> None:
    for rel, tree, _src in parsed:
        bindings = {n: b for n, b in _collect_jitted_bindings(tree).items()
                    if b.donate_nums}
        if not bindings:
            continue
        for qual, fn, _mod in _walk_functions(tree):
            _donate_in_function(linter, rel, qual, fn, bindings)


def _donate_in_function(linter, rel: str, qual: str, fn,
                        bindings: Dict[str, _JittedBinding]) -> None:
    stmts = list(fn.body)
    flat: List[ast.stmt] = []

    def flatten(block):
        for s in block:
            flat.append(s)
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(s, field, None)
                if sub:
                    flatten(sub)
            for h in getattr(s, "handlers", ()) or ():
                flatten(h.body)

    flatten(stmts)

    for i, s in enumerate(flat):
        for call in [n for n in ast.walk(s)
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Name)
                     and n.func.id in bindings]:
            b = bindings[call.func.id]
            rebound_here: Set[str] = set()
            if isinstance(s, ast.Assign):
                for tgt in s.targets:
                    for sub in ast.walk(tgt):
                        if isinstance(sub, ast.Name):
                            rebound_here.add(sub.id)
            for pos in b.donate_nums:
                if not (0 <= pos < len(call.args)):
                    continue
                arg = call.args[pos]
                if not isinstance(arg, ast.Name):
                    continue
                if arg.id in rebound_here:
                    continue  # x = f(x): rebind-through, the sanctioned shape
                _scan_after(linter, rel, qual, flat[i + 1:], arg.id,
                            b.name, call.lineno)


def _scan_after(linter, rel: str, qual: str, rest: Sequence[ast.stmt],
                name: str, callee: str, call_line: int) -> None:
    for s in rest:
        if isinstance(s, ast.Assign):
            # a full rebind of the name kills the dangling reference —
            # but only if the VALUE doesn't read it first
            reads_in_value = any(isinstance(n, ast.Name) and n.id == name
                                 and isinstance(n.ctx, ast.Load)
                                 for n in ast.walk(s.value))
            if reads_in_value:
                linter.add(Finding(
                    "donate-uaf", rel, s.lineno, qual,
                    f"'{name}' was donated to '{callee}'"
                    " (donate_argnums) and read afterwards — the buffer"
                    " is dead after dispatch on real accelerators",
                    f"donate-uaf:{callee}:{name}"))
                return
            for tgt in s.targets:
                if isinstance(tgt, ast.Name) and tgt.id == name:
                    return
                if isinstance(tgt, (ast.Tuple, ast.List)) and any(
                        isinstance(e, ast.Name) and e.id == name
                        for e in tgt.elts):
                    return
            continue
        for n in ast.walk(s):
            if isinstance(n, ast.Name) and n.id == name \
                    and isinstance(n.ctx, ast.Load):
                linter.add(Finding(
                    "donate-uaf", rel, n.lineno, qual,
                    f"'{name}' was donated to '{callee}' (donate_argnums)"
                    " and read afterwards — the buffer is dead after"
                    " dispatch on real accelerators",
                    f"donate-uaf:{callee}:{name}"))
                return
            if isinstance(n, ast.Name) and n.id == name \
                    and isinstance(n.ctx, ast.Store):
                return
