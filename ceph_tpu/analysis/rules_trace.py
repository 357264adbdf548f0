"""trace-safety: host-sync and recompile hazards inside jitted code.

A jitted function runs ONCE per shape/dtype signature to build a trace;
anything that forces a concrete value (``.item()``, ``float()`` on a
traced array, ``np.asarray``) inserts a device->host sync into the hot
path or fails outright, ``print`` silently becomes trace-time-only, and
mutating ``self``/nonlocal state bakes one iteration's value into the
compiled program forever. These are exactly the bugs that type-check,
pass small tests on CPU, and destroy TPU throughput in production.

Jitted functions are found two ways: decorator forms (``@jax.jit``,
``@partial(jax.jit, ...)``/``pjit``) and call forms — ``jax.jit(fn)``
or ``jax.jit(functools.partial(fn, ...))`` anywhere in the module marks
``fn`` (the dominant idiom in this tree, e.g. ops/crc32c.py's
``_jit_crc0 = jax.jit(_crc0_words)``).
"""
from __future__ import annotations

import ast
from typing import Iterator

from .core import Finding, Rule, call_name, register

_JIT_NAMES = frozenset((
    "jax.jit", "jit", "pjit", "jax.pjit", "jax.experimental.pjit.pjit",
))
_PARTIAL_NAMES = frozenset(("functools.partial", "partial"))

#: attribute calls that force a device->host sync on a traced value
_SYNC_METHODS = frozenset((
    "item", "tolist", "block_until_ready", "copy_to_host_async",
))

#: calls that materialize a traced value on the host
_HOST_CALLS = frozenset((
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "np.copy", "numpy.copy",
))

#: codec methods that dispatch a device program and return device
#: arrays — materializing their result on the asyncio reactor thread
#: blocks the whole daemon for the transfer+execution round trip
#: (the batch's copy + execution time); the dispatch AND its
#: readback belong in an executor worker (cluster/ecbatch.py shape).
#: The bulk-CRUSH serving path (placement/bulk.py do_rule_bulk,
#: ops/crush.py straw2_bulk) is the same hazard on the dispatch plane:
#: the placement resolver runs it in an executor, never on the reactor
_DEVICE_DISPATCHES = frozenset((
    "encode_batch", "decode_batch", "encode_crc_batch",
    "do_rule_bulk", "straw2_bulk",
))


def _is_jit_expr(node: ast.AST) -> bool:
    """True for ``jax.jit`` / ``pjit`` possibly already applied
    (``jax.jit(...)``) or curried via partial(jax.jit, ...)."""
    if call_name(node) in _JIT_NAMES:
        return True
    if isinstance(node, ast.Call):
        if call_name(node.func) in _JIT_NAMES:
            return True
        if (call_name(node.func) in _PARTIAL_NAMES and node.args
                and call_name(node.args[0]) in _JIT_NAMES):
            return True
    return False


class _JitInfo:
    """How a function is jitted: which of its params are STATIC —
    partial-bound leading args (host constants closed over before the
    trace) and ``static_argnums``/``static_argnames`` — and therefore
    legal to concretize with ``int()``/``float()``."""

    def __init__(self) -> None:
        self.bound_pos = 0            # leading params bound via partial
        self.bound_kw: set[str] = set()
        self.static_names: set[str] = set()
        self.static_nums: set[int] = set()

    def merge(self, other: "_JitInfo") -> None:
        # conservative across multiple jit sites: a param is static
        # only if EVERY site makes it static
        self.bound_pos = min(self.bound_pos, other.bound_pos)
        self.bound_kw &= other.bound_kw
        self.static_names &= other.static_names
        self.static_nums &= other.static_nums


def _static_spec(jit_call: ast.Call) -> tuple[set[str], set[int]]:
    names: set[str] = set()
    nums: set[int] = set()
    for kw in jit_call.keywords:
        vals = (kw.value.elts
                if isinstance(kw.value, (ast.Tuple, ast.List))
                else [kw.value])
        consts = [v.value for v in vals if isinstance(v, ast.Constant)]
        if kw.arg == "static_argnames":
            names |= {v for v in consts if isinstance(v, str)}
        elif kw.arg == "static_argnums":
            nums |= {v for v in consts if isinstance(v, int)}
    return names, nums


def _jit_wrapped_names(tree: ast.Module) -> dict[str, _JitInfo]:
    """Functions passed to jax.jit/pjit as values anywhere in the
    module — ``jax.jit(f)``, ``jax.jit(functools.partial(f, x))``, and
    the dict-dispatch idiom ``jax.jit(partial(_IMPLS[k], m))`` where
    ``_IMPLS`` is a module-level dict of functions (ops/rs.py) — with
    the static-parameter spec of each jit site."""
    fn_dicts: dict[str, set[str]] = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Dict)):
            vals = {v.id for v in node.value.values
                    if isinstance(v, ast.Name)}
            if vals:
                fn_dicts[node.targets[0].id] = vals
    out: dict[str, _JitInfo] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and call_name(node.func) in _JIT_NAMES and node.args):
            continue
        target = node.args[0]
        info = _JitInfo()
        info.static_names, info.static_nums = _static_spec(node)
        if (isinstance(target, ast.Call)
                and call_name(target.func) in _PARTIAL_NAMES
                and target.args):
            info.bound_pos = len(target.args) - 1
            info.bound_kw = {k.arg for k in target.keywords if k.arg}
            target = target.args[0]
        names: set[str] = set()
        if isinstance(target, ast.Name):
            names.add(target.id)
        elif (isinstance(target, ast.Subscript)
                and isinstance(target.value, ast.Name)):
            names |= fn_dicts.get(target.value.id, set())
        for n in names:
            if n in out:
                out[n].merge(info)
            else:
                out[n] = info
    return out


def _traced_params(fn: ast.FunctionDef | ast.AsyncFunctionDef,
                   info: _JitInfo) -> set[str]:
    """Parameter names that carry TRACED values under ``info``."""
    a = fn.args
    pos = [*a.posonlyargs, *a.args]
    traced: set[str] = set()
    for i, p in enumerate(pos):
        if i < info.bound_pos or i in info.static_nums:
            continue
        traced.add(p.arg)
    traced |= {p.arg for p in a.kwonlyargs}
    traced -= info.static_names | info.bound_kw | {"self"}
    return traced


#: attribute chains that yield STATIC metadata of a traced array —
#: `int(x.shape[0])` is idiomatic and jit-safe, not a concretization
_STATIC_ATTRS = frozenset(("shape", "ndim", "dtype", "size"))


def _refs_traced_value(node: ast.AST, names: set[str]) -> bool:
    """Does ``node`` reference a traced param's VALUE (as opposed to
    its static metadata like ``.shape``)?"""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False  # prune: x.shape / x.dtype subtrees are static
    if isinstance(node, ast.Name):
        return node.id in names
    return any(_refs_traced_value(c, names)
               for c in ast.iter_child_nodes(node))


@register
class TraceSafetyRule(Rule):
    id = "trace-safety"

    def check(self, tree: ast.Module, path: str,
              source: str) -> Iterator[Finding]:
        wrapped = _jit_wrapped_names(tree)
        scope: list[str] = []
        findings: list[Finding] = []

        def visit(node: ast.AST) -> None:
            if isinstance(node, ast.ClassDef):
                scope.append(node.name)
                for c in ast.iter_child_nodes(node):
                    visit(c)
                scope.pop()
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope.append(node.name)
                info = self._jit_info(node, wrapped)
                if info is not None:
                    findings.extend(self._check_jitted(
                        node, info, path, ".".join(scope)))
                else:
                    if isinstance(node, ast.AsyncFunctionDef):
                        findings.extend(self._check_reactor_readback(
                            node, path, ".".join(scope)))
                    for c in ast.iter_child_nodes(node):
                        visit(c)
                scope.pop()
                return
            for c in ast.iter_child_nodes(node):
                visit(c)

        visit(tree)
        findings.extend(self._check_static_args(tree, path))
        return iter(findings)

    @staticmethod
    def _jit_info(fn, wrapped: dict[str, _JitInfo]) -> _JitInfo | None:
        for d in fn.decorator_list:
            if _is_jit_expr(d):
                info = _JitInfo()
                if isinstance(d, ast.Call):
                    info.static_names, info.static_nums = _static_spec(d)
                return info
        return wrapped.get(fn.name)

    def _check_jitted(self, fn, info: _JitInfo, path: str,
                      symbol: str) -> Iterator[Finding]:
        params = _traced_params(fn, info)

        def emit(node, what: str) -> Finding:
            return Finding(self.id, path, node.lineno, symbol, what)

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = call_name(node.func)
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SYNC_METHODS):
                    yield emit(node, f"host sync `.{node.func.attr}()` "
                                     "inside a jitted function")
                elif name in _HOST_CALLS:
                    yield emit(node, f"`{name}` materializes a traced "
                                     "value on the host inside jit")
                elif name == "print":
                    yield emit(node, "`print` inside jit runs at trace "
                                     "time only (use jax.debug.print)")
                elif (name in ("float", "int", "bool") and node.args
                      and _refs_traced_value(node.args[0], params)):
                    yield emit(node, f"`{name}()` on a traced value "
                                     "forces trace-time concretization")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    base = t
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    if (isinstance(base, ast.Attribute)
                            and isinstance(base.value, ast.Name)
                            and base.value.id == "self"):
                        yield emit(node, f"mutation of `self.{base.attr}`"
                                         " inside jit bakes one trace's "
                                         "value into the compiled fn")
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                kw = ("global" if isinstance(node, ast.Global)
                      else "nonlocal")
                yield emit(node, f"`{kw}` state mutation inside jit is "
                                 "invisible to retraces")

    def _check_reactor_readback(self, fn: ast.AsyncFunctionDef,
                                path: str,
                                symbol: str) -> Iterator[Finding]:
        """A blocking device readback on the reactor thread: inside an
        ``async def``, ``np.asarray(...)``/``np.array(...)`` wrapping a
        batched device dispatch materializes the result synchronously —
        the event loop stalls for the whole transfer+execution round
        trip. The dispatch and its readback must run in an executor
        worker (the ECBatcher _encode_sync/_decode_sync shape). The
        walk stops at nested function boundaries (each def is checked
        in its own visit)."""

        def local_walk(node: ast.AST) -> Iterator[ast.AST]:
            for c in ast.iter_child_nodes(node):
                if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                yield c
                yield from local_walk(c)

        for node in local_walk(fn):
            if not (isinstance(node, ast.Call)
                    and call_name(node.func) in _HOST_CALLS
                    and node.args):
                continue
            for sub in ast.walk(node.args[0]):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _DEVICE_DISPATCHES):
                    yield Finding(
                        self.id, path, node.lineno, symbol,
                        f"blocking device readback of "
                        f"`.{sub.func.attr}()` on the reactor thread — "
                        "dispatch + readback belong in an executor "
                        "worker")
                    break

    def _check_static_args(self, tree: ast.Module,
                           path: str) -> Iterator[Finding]:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and call_name(node.func) in _JIT_NAMES):
                continue
            for kw in node.keywords:
                if kw.arg not in ("static_argnums", "static_argnames"):
                    continue
                if isinstance(kw.value, (ast.List, ast.Set, ast.Dict)):
                    yield Finding(
                        self.id, path, kw.value.lineno, "<module>",
                        f"`{kw.arg}` should be an int/str or tuple "
                        "(unhashable containers break jit's cache key)")
