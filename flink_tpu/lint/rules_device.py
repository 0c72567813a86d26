"""Device-discipline rule family (DEV): keep the TPU hot path hot.

- DEV001 host-sync-in-jit — host-synchronizing operations on traced
  values inside a jitted function either fail at trace time or (worse)
  silently force a device->host round trip per call.
- DEV002 jit-in-loop — `jax.jit(...)` invoked inside a loop body builds a
  fresh compiled callable per iteration: a recompilation (or at best
  cache-lookup) hazard on the hot path. Builders cache their jitted fn
  (lru_cache / instance dict) outside the loop.
- DEV003 jax-free-control-plane — the cluster control plane must not
  import jax at module level: an oracle-path worker must never claim a
  TPU chip just by starting up.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from flink_tpu.lint.index import ModuleIndex, ModuleInfo, enclosing_scope, parent_map
from flink_tpu.lint.rule import Rule, Violation, register

#: modules (package-relative) that form the cluster control plane
CONTROL_PLANE = (
    "runtime/cluster.py",
    "runtime/rpc.py",
    "runtime/blob.py",
    "runtime/heartbeat.py",
    "runtime/ha.py",
    "runtime/ha_kubernetes.py",
    "runtime/rest.py",
    "runtime/dataplane.py",
    "security/framing.py",
    "security/transport.py",
    # the history/doctor plane consumes plain-data snapshots and span
    # dicts handed to it — a jax import here would drag backend init
    # into every REST reader and JM schedule tick
    "metrics/history.py",
    "metrics/doctor.py",
)


def _numpy_aliases(mod: ModuleInfo) -> Set[str]:
    aliases = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    aliases.add(a.asname or "numpy")
    return aliases


def _is_jax_jit(fn: ast.AST) -> bool:
    """True for `jax.jit` or bare `jit` expressions."""
    if isinstance(fn, ast.Attribute) and fn.attr == "jit" and \
            isinstance(fn.value, ast.Name) and fn.value.id == "jax":
        return True
    return isinstance(fn, ast.Name) and fn.id == "jit"


def _jit_decorated(func: ast.AST) -> bool:
    if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    for dec in func.decorator_list:
        if _is_jax_jit(dec):
            return True
        # @partial(jax.jit, ...) / @functools.partial(jax.jit, ...)
        if isinstance(dec, ast.Call):
            if _is_jax_jit(dec.func):
                return True
            f = dec.func
            is_partial = (isinstance(f, ast.Name) and f.id == "partial") or (
                isinstance(f, ast.Attribute) and f.attr == "partial")
            if is_partial and dec.args and _is_jax_jit(dec.args[0]):
                return True
    return False


def _jitted_functions(mod: ModuleInfo,
                      parents: Dict[ast.AST, ast.AST]) -> List[ast.AST]:
    """FunctionDefs compiled by jax.jit: decorated ones, plus plain defs
    passed to a `jax.jit(name)` call in the same enclosing scope."""
    jitted: List[ast.AST] = []
    for node in ast.walk(mod.tree):
        if _jit_decorated(node):
            jitted.append(node)
        if isinstance(node, ast.Call) and _is_jax_jit(node.func) and \
                node.args and isinstance(node.args[0], ast.Name):
            target = _resolve_local_def(node, node.args[0].id, parents)
            if target is not None and target not in jitted:
                jitted.append(target)
        # jax.jit(lambda ...: ...)
        if isinstance(node, ast.Call) and _is_jax_jit(node.func) and \
                node.args and isinstance(node.args[0], ast.Lambda):
            jitted.append(node.args[0])
    return jitted


def _resolve_local_def(site: ast.AST, name: str,
                       parents: Dict[ast.AST, ast.AST]) -> Optional[ast.AST]:
    """Nearest enclosing scope's `def <name>` for a `jax.jit(name)` call."""
    cur: Optional[ast.AST] = site
    while cur is not None:
        cur = parents.get(cur)
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Module)):
            for stmt in ast.walk(cur):
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and stmt.name == name and stmt is not site:
                    return stmt
    return None


#: attribute calls that synchronize device -> host
HOST_SYNC_ATTRS = {"item", "block_until_ready", "tolist"}


def _contains_static_marker(expr: ast.AST) -> bool:
    """float()/int() on shapes and sizes is static metadata, not a host
    sync — skip literal args and args mentioning .shape/.ndim/.size/len().
    A nested literal (an index like x[-1]) does NOT make the arg static."""
    if isinstance(expr, ast.Constant):
        return True
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr in ("shape", "ndim",
                                                             "size", "dtype"):
            return True
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "len":
            return True
    return False


@register
class HostSyncInJitRule(Rule):
    id = "DEV001"
    name = "host-sync-in-jit"
    family = "device"
    rationale = (
        "Inside a function compiled with @jax.jit / jax.jit(fn), calling "
        ".item()/.tolist()/.block_until_ready(), np.asarray/np.array, "
        "jax.device_get, or float()/int()/bool() on a traced value either "
        "raises a ConcretizationTypeError at trace time or forces a "
        "device->host readback on every call — the exact sync the jitted "
        "hot path exists to avoid. Host conversions belong at the step "
        "boundary (the runner's readback section), never inside the "
        "compiled body."
    )
    hint = ("keep the jitted body pure jnp; do host conversion on the "
            "result at the step boundary (the stage clock's resolve stage)")

    def check(self, index: ModuleIndex) -> Iterator[Violation]:
        for mod in index.modules:
            parents = parent_map(mod.tree)
            np_names = _numpy_aliases(mod)
            # occurrence-indexed symbols: the 2nd .item() in one function
            # must not share the 1st one's fingerprint, or a single
            # baseline entry suppresses every current and future host sync
            # of that label in the scope
            seen: Dict[Tuple[str, str], int] = {}
            for func in _jitted_functions(mod, parents):
                fname = getattr(func, "name", "<lambda>")
                body = func.body if isinstance(func.body, list) else [func.body]
                for stmt in body:
                    yield from self._scan(stmt, mod, fname, np_names,
                                          parents, seen)

    def _scan(self, root: ast.AST, mod: ModuleInfo, fname: str,
              np_names: Set[str], parents: Dict[ast.AST, ast.AST],
              seen: Dict[Tuple[str, str], int]) -> Iterator[Violation]:
        for node in ast.walk(root):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            label = None
            if isinstance(fn, ast.Attribute):
                if fn.attr in HOST_SYNC_ATTRS:
                    label = f".{fn.attr}()"
                elif fn.attr in ("asarray", "array") and \
                        isinstance(fn.value, ast.Name) and \
                        fn.value.id in (np_names or {"np"}) and \
                        fn.value.id != "jnp":
                    label = f"{fn.value.id}.{fn.attr}()"
                elif fn.attr == "device_get" and \
                        isinstance(fn.value, ast.Name) and fn.value.id == "jax":
                    label = "jax.device_get()"
            elif isinstance(fn, ast.Name) and fn.id in ("float", "int",
                                                        "bool"):
                if node.args and not _contains_static_marker(node.args[0]):
                    label = f"{fn.id}()"
            if label is None:
                continue
            scope = enclosing_scope(parents, node) or fname
            base = f"{label}@{fname}"
            n = seen[(scope, base)] = seen.get((scope, base), 0) + 1
            yield self.violation(
                mod, node.lineno,
                f"host-sync {label} inside jitted function {fname}()",
                scope=scope, symbol=base if n == 1 else f"{base}#{n}")


@register
class JitInLoopRule(Rule):
    id = "DEV002"
    name = "jit-in-loop"
    family = "device"
    rationale = (
        "jax.jit(...) invoked inside a for/while body constructs a new "
        "compiled callable every iteration — at best a cache lookup per "
        "record batch, at worst a recompilation storm when the closure "
        "captures loop state. Every builder in this codebase caches its "
        "jitted fn outside the loop (functools.lru_cache or an instance "
        "dict); new code must do the same."
    )
    hint = ("hoist the jax.jit call out of the loop (cache per geometry "
            "with functools.lru_cache or a dict keyed on static shapes)")

    def check(self, index: ModuleIndex) -> Iterator[Violation]:
        for mod in index.modules:
            parents = None
            seen: Dict[str, int] = {}
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and _is_jax_jit(node.func)):
                    continue
                if parents is None:
                    parents = parent_map(mod.tree)
                loop = self._enclosing_loop(node, parents)
                if loop is None:
                    continue
                scope = enclosing_scope(parents, node)
                n = seen[scope] = seen.get(scope, 0) + 1
                yield self.violation(
                    mod, node.lineno,
                    (f"jax.jit(...) inside a "
                     f"{'for' if isinstance(loop, ast.For) else 'while'} "
                     f"loop body in {scope or '<module>'} — per-iteration "
                     f"(re)compilation hazard"),
                    scope=scope,
                    symbol=(f"jit-in-loop@{scope}" if n == 1 else
                            f"jit-in-loop@{scope}#{n}"))

    @staticmethod
    def _enclosing_loop(node: ast.AST,
                        parents: Dict[ast.AST, ast.AST]) -> Optional[ast.AST]:
        cur = parents.get(node)
        child = node
        while cur is not None:
            # stop at function boundaries: a def inside a loop runs later
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return None
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)) and \
                    child in getattr(cur, "body", []) + getattr(cur, "orelse", []):
                return cur
            child = cur
            cur = parents.get(cur)
        return None


@register
class JaxFreeControlPlaneRule(Rule):
    id = "DEV003"
    name = "jax-free-control-plane"
    family = "device"
    rationale = (
        "The cluster control plane (JM/TM endpoints, RPC, blob, "
        "heartbeats, HA, REST, dataplane, security) must not import jax "
        "at module level: backend init claims the TPU chip, so an "
        "oracle-path worker process would seize the accelerator just by "
        "starting up. Device-path code imports jax lazily inside the "
        "functions that actually run on device (_make_operator pattern)."
    )
    hint = ("move the jax import inside the function that needs it "
            "(device path only)")

    def check(self, index: ModuleIndex) -> Iterator[Violation]:
        for rel in CONTROL_PLANE:
            mod = index.get(rel)
            if mod is None:
                continue
            for imp, line in index.module_level_imports(mod):
                if imp == "jax" or imp.startswith("jax."):
                    yield self.violation(
                        mod, line,
                        f"control-plane module imports {imp} at module "
                        f"level (TPU backend init claims the chip)",
                        scope="", symbol=f"import:{imp}")
