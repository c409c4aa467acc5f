"""JAX-aware AST rules for ``repro.check lint``.

The analysis is module-local and deliberately under-approximate: it resolves
names through each module's own import aliases (``import numpy as np``,
``from jax import random``), traces function reachability only through
same-module calls/references, and never follows values across modules.
A rule therefore fires only on evidence visible inside one file — which is
exactly the precision/recall point a pre-merge gate wants: no finding is a
guess, and the dynamic sanitizer (``repro.check.dynamic``) backstops what
static analysis cannot see.

Traced-scope detection (the substrate for R001/R003/R004): a function is
*traced* when it is decorated with / passed to one of the JAX tracing
entry points (``jit``, ``vmap``, ``pmap``, ``grad``, ``lax.scan`` /
``fori_loop`` / ``while_loop`` / ``cond`` / ``switch``, ``pallas_call``,
``shard_map``, ``eval_shape``, ``checkify``, ``custom_vjp``...), including
through ``functools.partial``, or when it is called or referenced from the
body of an already-traced same-module function (``Trainer._superstep``
calling ``self._op_sample``).
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.check.report import Finding

# call/decorator names that trace their function argument(s)
_TRACERS = {"jit", "vmap", "pmap", "grad", "value_and_grad", "pallas_call",
            "shard_map", "eval_shape", "checkify", "custom_vjp",
            "custom_jvp", "named_call", "kernel"}
# lax-style control flow: trace callables but the bare name is generic, so
# require a jax/lax/pl rooted chain OR a single-name import from jax
_LAX_TRACERS = {"scan", "fori_loop", "while_loop", "cond", "switch", "map",
                "associative_scan"}

# canonical (post-alias) chain prefixes that are host-impure (R001)
_IMPURE_PREFIXES: Tuple[Tuple[str, ...], ...] = (
    ("time", "time"), ("time", "time_ns"), ("time", "monotonic"),
    ("time", "monotonic_ns"), ("time", "perf_counter"),
    ("time", "perf_counter_ns"),
    ("datetime", "datetime", "now"), ("datetime", "datetime", "utcnow"),
    ("datetime", "datetime", "today"), ("datetime", "date", "today"),
    ("numpy", "random"),
    ("os", "urandom"), ("os", "getrandom"),
    ("uuid",), ("secrets",), ("random",),
)

# jax.random functions that REBIND rather than merely consume (R002): the
# result is a fresh key, so `k = fold_in(k, i)` is the sanctioned pattern
_KEY_REBINDERS = {"split", "fold_in", "clone"}
# jax.random attrs that create/convert keys without consuming one
_KEY_CREATORS = {"key", "PRNGKey", "wrap_key_data", "key_data", "key_impl"}

# calls whose result is a HOST value even though the chain is jax-rooted
_SANITIZERS = {"device_get"}

# jax/jnp functions that inspect static structure (shapes, dtypes) — their
# result is a Python value, never a tracer, so branching on them is fine
_STATIC_JAX = {"issubdtype", "result_type", "ndim", "shape", "size",
               "isdtype", "canonicalize_dtype", "eval_shape", "tree_all",
               "tree_structure"}
# attribute reads that are static even on a tracer
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}

# numpy entry points + builtins that force a device->host sync when handed
# a device value (R004)
_SYNC_BUILTINS = {"float", "int", "bool", "complex"}


def _chain(node) -> Optional[Tuple[str, ...]]:
    """``a.b.c`` -> ("a","b","c"); None for non-name-rooted expressions."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


class _Imports:
    """Per-module import-alias resolution: local chain -> canonical chain."""

    def __init__(self, tree: ast.Module):
        self.alias: Dict[str, Tuple[str, ...]] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    parts = tuple(a.name.split("."))
                    # `import jax.numpy as jnp` binds jnp to the full path;
                    # `import jax.numpy` binds only the root name `jax`
                    if a.asname:
                        self.alias[a.asname] = parts
                    else:
                        self.alias[parts[0]] = parts[:1]
            elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                    and node.module:
                base = tuple(node.module.split("."))
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.alias[a.asname or a.name] = base + (a.name,)

    def canon(self, chain: Tuple[str, ...]) -> Tuple[str, ...]:
        if chain and chain[0] in self.alias:
            return self.alias[chain[0]] + chain[1:]
        return chain


@dataclasses.dataclass
class _Scope:
    """One function-like AST scope (def / async def / lambda)."""
    node: ast.AST
    name: str
    qualname: str
    owner: Optional[str]       # enclosing class qualname, if a method
    parent: Optional["_Scope"]
    traced: bool = False

    @property
    def params(self) -> Set[str]:
        a = self.node.args
        names = [p.arg for p in
                 a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return {n for n in names if n not in ("self", "cls")}


class ModuleAnalysis:
    """Parsed module + scope graph + traced-reachability fixpoint."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.imports = _Imports(self.tree)
        self.scopes: List[_Scope] = []
        self._by_node: Dict[int, _Scope] = {}
        self.methods: Dict[str, Dict[str, _Scope]] = {}  # class -> name->sc
        self.top: Dict[str, _Scope] = {}                 # module-level defs
        self._collect(self.tree, qual="", owner=None, parent=None)
        self._mark_traced()

    # ------------------------------------------------------ scope collection
    def _collect(self, node, qual, owner, parent):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{qual}.{child.name}" if qual else child.name
                sc = _Scope(child, child.name, q, owner, parent)
                self._register(sc, owner, parent)
                self._collect(child, q, owner, sc)
            elif isinstance(child, ast.Lambda):
                q = f"{qual}.<lambda>" if qual else "<lambda>"
                sc = _Scope(child, "<lambda>", q, owner, parent)
                self._register(sc, owner, parent)
                self._collect(child, q, owner, sc)
            elif isinstance(child, ast.ClassDef):
                q = f"{qual}.{child.name}" if qual else child.name
                self.methods.setdefault(q, {})
                self._collect(child, q, owner=q, parent=parent)
            else:
                self._collect(child, qual, owner, parent)

    def _register(self, sc: _Scope, owner, parent):
        self.scopes.append(sc)
        self._by_node[id(sc.node)] = sc
        if owner is not None and parent is None:
            self.methods.setdefault(owner, {})[sc.name] = sc
        if owner is None and parent is None:
            self.top[sc.name] = sc

    # --------------------------------------------------- traced reachability
    def _is_tracing_call(self, call: ast.Call) -> bool:
        chain = _chain(call.func)
        if chain is None:
            return False
        last = chain[-1]
        if last in _TRACERS:
            return True
        if last in _LAX_TRACERS:
            canon = self.imports.canon(chain)
            return canon[0] in ("jax", "lax", "pl", "pallas", "plgpu") \
                or canon != chain  # resolved through a from-import
        return False

    def _callable_args(self, call: ast.Call) -> Iterable[ast.AST]:
        for a in list(call.args) + [k.value for k in call.keywords]:
            yield a
            # functools.partial(fn, ...) wrapping inside the tracing call
            if isinstance(a, ast.Call):
                ch = _chain(a.func)
                if ch and ch[-1] == "partial":
                    yield from a.args
                    yield from (k.value for k in a.keywords)

    def _resolve(self, node, from_scope: Optional[_Scope]
                 ) -> Optional[_Scope]:
        """A Name/Attribute reference -> the module-local scope it names."""
        if isinstance(node, ast.Lambda) or isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return self._by_node.get(id(node))
        chain = _chain(node)
        if chain is None:
            return None
        if len(chain) == 1:
            # nearest enclosing def, then module level
            sc = from_scope
            while sc is not None:
                for cand in self.scopes:
                    if cand.parent is sc and cand.name == chain[0]:
                        return cand
                sc = sc.parent
            return self.top.get(chain[0])
        if chain[0] in ("self", "cls") and len(chain) == 2:
            owner = from_scope.owner if from_scope else None
            if owner is not None:
                return self.methods.get(owner, {}).get(chain[1])
        if len(chain) == 2 and chain[0] in self.methods:
            return self.methods[chain[0]].get(chain[1])
        return None

    def _enclosing_scope(self, stack: List[ast.AST]) -> Optional[_Scope]:
        for node in reversed(stack):
            sc = self._by_node.get(id(node))
            if sc is not None:
                return sc
        return None

    def _walk_with_scope(self):
        """Yield (node, innermost enclosing _Scope or None)."""
        stack: List[ast.AST] = []

        def rec(node):
            sc = self._by_node.get(id(node))
            if sc is not None:
                stack.append(node)
            for child in ast.iter_child_nodes(node):
                yield child, self._enclosing_scope(stack)
                yield from rec(child)
            if sc is not None:
                stack.pop()

        yield from rec(self.tree)

    def _mark_traced(self):
        # seed: decorators + callables handed to tracing calls
        work: List[_Scope] = []

        def seed(sc: _Scope):
            if not sc.traced:
                sc.traced = True
                work.append(sc)

        for sc in self.scopes:
            for dec in getattr(sc.node, "decorator_list", []):
                target = dec.func if isinstance(dec, ast.Call) else dec
                chain = _chain(target)
                if chain and chain[-1] in _TRACERS:
                    seed(sc)
                elif isinstance(dec, ast.Call):
                    # @partial(jax.jit, ...) and custom_vjp.defvjp chains
                    for a in dec.args:
                        ch = _chain(a)
                        if ch and ch[-1] in _TRACERS:
                            seed(sc)
        for node, sc in self._walk_with_scope():
            if isinstance(node, ast.Call) and self._is_tracing_call(node):
                for arg in self._callable_args(node):
                    target = self._resolve(arg, sc)
                    if target is not None:
                        seed(target)
        # fixpoint: anything called/referenced from a traced body is traced
        while work:
            sc = work.pop()
            for node in self._body_walk(sc):
                if isinstance(node, (ast.Name, ast.Attribute, ast.Lambda)):
                    target = self._resolve(node, sc)
                    if target is not None and not target.traced:
                        target.traced = True
                        work.append(target)

    def _body_walk(self, sc: _Scope):
        """Walk a scope's own body, excluding nested def/lambda subtrees
        (their traced status is tracked separately)."""

        def rec(node):
            for child in ast.iter_child_nodes(node):
                yield child
                if id(child) not in self._by_node:
                    yield from rec(child)

        yield from rec(sc.node)

    # ----------------------------------------------------------- utilities
    def snippet(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule: str, node, message: str, hint: str) -> Finding:
        line = getattr(node, "lineno", 1)
        return Finding(rule=rule, file=self.path, line=line,
                       message=message, hint=hint,
                       snippet=self.snippet(line))

    def _jax_rooted(self, call: ast.Call) -> bool:
        """True for calls resolving under jax/jnp that return device
        values (``jax.device_get`` and friends sanitize)."""
        chain = _chain(call.func)
        if chain is None:
            return False
        canon = self.imports.canon(chain)
        return canon[0] == "jax" and canon[-1] not in _SANITIZERS

    def _array_like_names(self, sc: _Scope) -> Set[str]:
        """Names used as bare arguments to jax-rooted numeric calls in this
        scope — local evidence that the name holds an array. Static config
        parameters (``causal``, ``backend``, block sizes) never appear this
        way, which is what keeps R003 from flagging them."""
        names: Set[str] = set()
        for node in self._body_walk(sc):
            if not isinstance(node, ast.Call) or not self._jax_rooted(node):
                continue
            ch = _chain(node.func)
            if ch and ch[-1] in _STATIC_JAX:
                continue
            for a in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(a, ast.Name):
                    names.add(a.id)
        return names

    def _tainted_names(self, sc: _Scope) -> Set[str]:
        """Names assigned (in this scope) from jax-rooted calls — the
        tracer/device-value evidence for R003/R004."""
        tainted: Set[str] = set()
        for node in self._body_walk(sc):
            if isinstance(node, ast.Assign):
                has_jax = any(isinstance(n, ast.Call) and self._jax_rooted(n)
                              for n in ast.walk(node.value))
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            if has_jax:
                                tainted.add(n.id)
                            else:
                                tainted.discard(n.id)
        return tainted


# ------------------------------------------------------------------- rules

def r001_host_impurity(mod: ModuleAnalysis) -> List[Finding]:
    """Host-impure calls (wall clock, numpy RNG, uuid...) reachable from
    traced code run at TRACE time — their value is baked into the compiled
    program (silent nondeterminism) or re-executes per trace."""
    out = []
    for sc in mod.scopes:
        if not sc.traced:
            continue
        for node in mod._body_walk(sc):
            if not isinstance(node, ast.Call):
                continue
            chain = _chain(node.func)
            if chain is None:
                continue
            canon = mod.imports.canon(chain)
            for pref in _IMPURE_PREFIXES:
                if canon[:len(pref)] == pref and len(canon) >= len(pref):
                    # bare module references (`random`) are not calls of it
                    if len(canon) == len(pref) == 1:
                        continue
                    out.append(mod.finding(
                        "R001", node,
                        f"host-impure call {'.'.join(chain)}() inside "
                        f"traced function '{sc.qualname}'",
                        "traced code executes this once at trace time and "
                        "bakes the value into the compiled program; hoist "
                        "it out of the jitted scope or pass the value in "
                        "as an argument"))
                    break
    return out


def _name_of(node) -> Optional[str]:
    chain = _chain(node)
    return ".".join(chain) if chain else None


def r002_key_reuse(mod: ModuleAnalysis) -> List[Finding]:
    """A PRNG key consumed by two ``jax.random.*`` calls without an
    intervening rebind produces correlated randomness.

    Flow-aware over if/else: consumption in mutually exclusive branches is
    not reuse; after the If, both branches' consumptions carry forward
    (minus branches that return/raise). Loop bodies are walked twice so
    cross-iteration reuse of an un-rebound key is caught."""
    out = []

    def expr_calls(node) -> Iterable[ast.Call]:
        """Calls in an expression, innermost (evaluated) first, skipping
        nested function scopes."""
        found: List[ast.Call] = []

        def rec(n):
            if id(n) in mod._by_node and not isinstance(
                    n, (ast.Name, ast.Attribute)):
                return
            for c in ast.iter_child_nodes(n):
                rec(c)
            if isinstance(n, ast.Call):
                found.append(n)

        rec(node)
        return found

    def consume(call: ast.Call, consumed: Dict[str, int],
                sc: _Scope) -> None:
        chain = _chain(call.func)
        if chain is None:
            return
        canon = mod.imports.canon(chain)
        if not (len(canon) >= 3 and canon[0] == "jax"
                and canon[1] == "random"):
            return
        fn = canon[2]
        if fn in _KEY_CREATORS or not call.args:
            return
        key = _name_of(call.args[0])
        if key is None:
            return
        if key in consumed:
            out.append(mod.finding(
                "R002", call,
                f"PRNG key '{key}' reused by jax.random.{fn} "
                f"(first consumed at line {consumed[key]}) in "
                f"'{sc.qualname}'",
                "a consumed key must be rebound before reuse: "
                "k1, k2 = jax.random.split(key) or "
                "key = jax.random.fold_in(key, step)"))
        else:
            consumed[key] = call.lineno

    def rebind(target, consumed: Dict[str, int]) -> None:
        for n in ast.walk(target):
            nm = _name_of(n)
            if nm:
                for k in [c for c in consumed
                          if c == nm or c.startswith(nm + ".")]:
                    consumed.pop(k)

    def walk(stmts, consumed: Dict[str, int], sc: _Scope) -> bool:
        """Interpret a statement list; returns True if it always leaves
        (return/raise/break/continue) so consumption doesn't escape."""
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue  # separate scope (analyzed as its own _Scope)
            if isinstance(st, (ast.Return, ast.Raise)):
                if st.value if isinstance(st, ast.Return) else st.exc:
                    for c in expr_calls(st.value if isinstance(
                            st, ast.Return) else st.exc):
                        consume(c, consumed, sc)
                return True
            if isinstance(st, (ast.Break, ast.Continue)):
                return True
            if isinstance(st, ast.If):
                for c in expr_calls(st.test):
                    consume(c, consumed, sc)
                a, b = dict(consumed), dict(consumed)
                ta = walk(st.body, a, sc)
                tb = walk(st.orelse, b, sc)
                if ta and tb:
                    continue
                if ta:
                    consumed.clear(); consumed.update(b)
                elif tb:
                    consumed.clear(); consumed.update(a)
                else:
                    merged = dict(a); merged.update(b)
                    consumed.clear(); consumed.update(merged)
                continue
            if isinstance(st, (ast.For, ast.AsyncFor, ast.While)):
                header = st.iter if isinstance(st, (ast.For, ast.AsyncFor)) \
                    else st.test
                for c in expr_calls(header):
                    consume(c, consumed, sc)
                if isinstance(st, (ast.For, ast.AsyncFor)):
                    rebind(st.target, consumed)
                body = dict(consumed)
                walk(st.body, body, sc)
                walk(st.body, body, sc)  # 2nd pass: cross-iteration reuse
                consumed.update(body)
                walk(st.orelse, consumed, sc)
                continue
            if isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    for c in expr_calls(item.context_expr):
                        consume(c, consumed, sc)
                if walk(st.body, consumed, sc):
                    return True
                continue
            if isinstance(st, ast.Try):
                body = dict(consumed)
                walk(st.body, body, sc)
                consumed.update(body)
                for h in st.handlers:
                    hc = dict(consumed)
                    walk(h.body, hc, sc)
                    consumed.update(hc)
                walk(st.orelse, consumed, sc)
                walk(st.finalbody, consumed, sc)
                continue
            if isinstance(st, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                if st.value is not None:
                    for c in expr_calls(st.value):
                        consume(c, consumed, sc)
                targets = st.targets if isinstance(st, ast.Assign) \
                    else [st.target]
                for t in targets:
                    rebind(t, consumed)
                continue
            for c in expr_calls(st):
                consume(c, consumed, sc)
        return False

    for sc in mod.scopes:
        body = getattr(sc.node, "body", None)
        if isinstance(body, list):
            walk(body, {}, sc)
    return out


def r003_tracer_branch(mod: ModuleAnalysis) -> List[Finding]:
    """Python ``if``/``while``/``assert`` on a tracer either crashes at
    trace time (ConcretizationTypeError) or — via callbacks — forces a
    hidden sync. ``is``/``is None`` identity tests are static and exempt."""
    out = []
    for sc in mod.scopes:
        if not sc.traced:
            continue
        params = sc.params & mod._array_like_names(sc)
        tainted = mod._tainted_names(sc)

        def is_static_test(test) -> bool:
            return isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)

        def tracer_evidence(test) -> Optional[str]:
            """Walk the test skipping static subtrees (.shape/.dtype reads,
            jnp.issubdtype-style predicates)."""
            hits: List[str] = []

            def rec(n):
                if isinstance(n, ast.Attribute) \
                        and n.attr in _STATIC_ATTRS:
                    return
                if isinstance(n, ast.Call):
                    ch = _chain(n.func)
                    if ch and ch[-1] in _STATIC_JAX:
                        return
                    if ch and mod._jax_rooted(n):
                        hits.append(f"jax call {'.'.join(ch)}(...)")
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                    if n.id in params:
                        hits.append(f"array parameter '{n.id}' of traced "
                                    f"function")
                    elif n.id in tainted:
                        hits.append(f"'{n.id}' (assigned from a jax call)")
                for c in ast.iter_child_nodes(n):
                    rec(c)

            rec(test)
            return hits[0] if hits else None

        for node in mod._body_walk(sc):
            if isinstance(node, (ast.If, ast.While)):
                test = node.test
            elif isinstance(node, ast.Assert):
                test = node.test
            else:
                continue
            if is_static_test(test):
                continue
            ev = tracer_evidence(test)
            if ev:
                kind = type(node).__name__.lower()
                out.append(mod.finding(
                    "R003", node,
                    f"Python {kind} branches on {ev} in traced "
                    f"function '{sc.qualname}'",
                    "inside traced code, branch with jax.lax.cond/"
                    "jnp.where/lax.select, or hoist the decision to the "
                    "host before tracing"))
    return out


def r004_host_sync(mod: ModuleAnalysis, loop_module: bool) -> List[Finding]:
    """Hidden device->host syncs: ``.item()``, ``float()/int()``,
    ``np.asarray`` on device values. Checked inside loop-body modules (the
    superstep path, where a sync serializes the pipeline) and inside traced
    scopes everywhere (where it breaks tracing outright)."""
    out = []
    hint = ("an implicit device->host transfer blocks the dispatch "
            "pipeline; fetch at an explicit barrier with jax.device_get "
            "in the chunk epilogue instead")
    for sc in mod.scopes:
        if not (loop_module or sc.traced):
            continue
        tainted = mod._tainted_names(sc)

        def device_evidence(arg) -> bool:
            if isinstance(arg, ast.Name) and arg.id in tainted:
                return True
            return isinstance(arg, ast.Call) and mod._jax_rooted(arg)

        for node in mod._body_walk(sc):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                out.append(mod.finding(
                    "R004", node,
                    f".item() host sync in '{sc.qualname}'", hint))
                continue
            chain = _chain(node.func)
            if chain is None or not node.args:
                continue
            canon = mod.imports.canon(chain)
            is_np = canon[0] == "numpy"
            is_builtin = len(chain) == 1 and chain[0] in _SYNC_BUILTINS
            if (is_np or is_builtin) \
                    and any(device_evidence(a) for a in node.args):
                out.append(mod.finding(
                    "R004", node,
                    f"{'.'.join(chain)}(...) forces a device->host sync "
                    f"on a jax value in '{sc.qualname}'", hint))
    return out


def r006_spec_validation(mod: ModuleAnalysis) -> List[Finding]:
    """Every field of a ``*Spec`` dataclass must be covered by a
    ``validate``/``__post_init__`` check (the PR-4 SpecError machinery):
    un-validated fields fail deep inside jit instead of at construction.

    Coverage is textual but closure-aware: a field counts as covered when
    its name appears in the validator, in any same-class method the
    validator calls, or in a module-level constant the validator references
    (the ``_SECTIONS``-table pattern)."""
    out = []
    # module-level constant assignments, for table-driven validators
    consts: Dict[str, str] = {}
    for node in mod.tree.body:
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target] if isinstance(node, ast.AnnAssign) \
            and node.value is not None else []
        for t in targets:
            if isinstance(t, ast.Name):
                consts[t.id] = ast.get_source_segment(
                    mod.source, node) or ""

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ClassDef) \
                or not node.name.endswith("Spec"):
            continue
        if not any((_chain(d.func if isinstance(d, ast.Call) else d)
                    or ("",))[-1] == "dataclass"
                   for d in node.decorator_list):
            continue
        fields = [s.target.id for s in node.body
                  if isinstance(s, ast.AnnAssign)
                  and isinstance(s.target, ast.Name)]
        methods = {s.name: s for s in node.body
                   if isinstance(s, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))}
        validators = [m for name, m in methods.items()
                      if name in ("__post_init__", "validate")]
        if not validators:
            if fields:
                out.append(mod.finding(
                    "R006", node,
                    f"dataclass {node.name} has no "
                    f"__post_init__/validate — none of its "
                    f"{len(fields)} fields are checked",
                    "add a __post_init__ that rejects invalid values "
                    "with SpecError at construction time"))
            continue
        # closure: validators + same-class methods they call, transitively
        seen: Set[str] = set()
        frontier = list(validators)
        text_parts: List[str] = []
        while frontier:
            m = frontier.pop()
            if m.name in seen:
                continue
            seen.add(m.name)
            text_parts.append(ast.get_source_segment(mod.source, m) or "")
            for n in ast.walk(m):
                if isinstance(n, ast.Call):
                    ch = _chain(n.func)
                    if ch and len(ch) == 2 and ch[0] in ("self", "cls") \
                            and ch[1] in methods:
                        frontier.append(methods[ch[1]])
        text = "\n".join(text_parts)
        for name in {n.id for m in validators for n in ast.walk(m)
                     if isinstance(n, ast.Name)} & set(consts):
            text += "\n" + consts[name]
        for f in fields:
            import re
            if not re.search(rf"\b{re.escape(f)}\b", text):
                out.append(mod.finding(
                    "R006", node,
                    f"{node.name}.{f} is not covered by "
                    f"__post_init__/validate",
                    f"add a check for '{f}' (e.g. _choice/_positive/"
                    f"_boolean) so bad values raise SpecError at "
                    f"construction"))
    return out
