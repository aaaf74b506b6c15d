"""JAX and threading hazards that type checkers don't see.

Codes:

- ``jit-in-loop`` — ``jax.jit`` constructed inside a ``for``/``while``
  body: a fresh jit wrapper per iteration defeats XLA's compile cache
  keying and churns recompiles.  The blessed shapes are a module-level
  jit, an ``@functools.lru_cache`` builder, or a builder that *returns*
  the jitted callable (ops/device.py, parallel/exchange.py).
- ``jit-immediate`` — ``jax.jit(f)(args)`` called and invoked in one
  expression: the wrapper is rebuilt (and its traces re-keyed) on every
  call.
- ``host-sync`` — ``.item()`` inside the device data plane's hot-path
  modules: an implicit D2H sync that serializes the async pipeline.
- ``thread-nondaemon`` — ``threading.Thread`` constructed without
  ``daemon=True``: every helper thread in this tree must not block
  interpreter shutdown (the watchdog/failover planes assume it).
- ``bare-acquire`` — ``<lock>.acquire()`` as a bare statement outside
  ``with``: invisible to context-managed cleanup and to the lock-order
  witness discipline.  (Block-local acquire/release pairs are still
  modeled by the static lock graph, but new code should use ``with``.)
"""
from __future__ import annotations

import ast
from typing import List, Optional

from tez_tpu.analysis.core import Checker, Context, Finding

#: Modules whose code runs per-span / per-batch on the device data
#: plane — where one stray host sync stalls the whole overlap schedule.
_HOT_PATH_MODULES = (
    "ops/async_stage.py", "ops/sorter.py", "ops/device.py",
    "library/merge_manager.py",
    "parallel/exchange.py", "parallel/coordinator.py",
)


def _is_jit(node: ast.expr) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "jit" and \
            isinstance(node.value, ast.Name) and node.value.id == "jax":
        return True
    return isinstance(node, ast.Name) and node.id == "jit"


def _is_thread_ctor(node: ast.Call) -> bool:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "Thread" and \
            isinstance(f.value, ast.Name) and f.value.id == "threading":
        return True
    return isinstance(f, ast.Name) and f.id == "Thread"


def _receiver_looks_like_lock(node: ast.expr) -> bool:
    name: Optional[str] = None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    return name is not None and "lock" in name.lower()


def run(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for sf in ctx.files:
        if sf.tree is None or "analysis/" in sf.rel:
            continue
        hot = any(sf.rel.endswith(m) for m in _HOT_PATH_MODULES)

        # jit inside loop bodies
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.For, ast.While)):
                for sub in ast.walk(node):
                    if sub is node:
                        continue
                    if isinstance(sub, ast.Call) and _is_jit(sub.func):
                        findings.append(Finding(
                            "jax_hazards", "jit-in-loop", sf.rel,
                            sub.lineno, f"L{sub.lineno}",
                            "jax.jit constructed inside a loop body — "
                            "hoist to module level or an lru_cache "
                            "builder"))
            # jax.jit(f)(args) in one expression
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Call) and \
                    _is_jit(node.func.func):
                findings.append(Finding(
                    "jax_hazards", "jit-immediate", sf.rel, node.lineno,
                    f"L{node.lineno}",
                    "jax.jit(f)(...) built and invoked per call — cache "
                    "the jitted callable"))
            # host syncs in hot-path modules
            if hot and isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                findings.append(Finding(
                    "jax_hazards", "host-sync", sf.rel, node.lineno,
                    f"L{node.lineno}",
                    ".item() in a device hot path is an implicit D2H "
                    "sync — keep values on device or batch the readback"))
            # non-daemon threads
            if isinstance(node, ast.Call) and _is_thread_ctor(node):
                kw = {k.arg: k.value for k in node.keywords}
                daemon = kw.get("daemon")
                if not (isinstance(daemon, ast.Constant) and
                        daemon.value is True):
                    findings.append(Finding(
                        "jax_hazards", "thread-nondaemon", sf.rel,
                        node.lineno, f"L{node.lineno}",
                        "threading.Thread without daemon=True — helper "
                        "threads must not block interpreter shutdown"))
            # bare .acquire() statements
            if isinstance(node, ast.Expr) and \
                    isinstance(node.value, ast.Call) and \
                    isinstance(node.value.func, ast.Attribute) and \
                    node.value.func.attr == "acquire" and \
                    _receiver_looks_like_lock(node.value.func.value):
                findings.append(Finding(
                    "jax_hazards", "bare-acquire", sf.rel, node.lineno,
                    f"L{node.lineno}",
                    "bare .acquire() — use `with` (or try/finally) so "
                    "release is guaranteed and the witness sees scoping"))
    return findings


CHECKER = Checker(
    "jax_hazards",
    "jit recompile churn, hot-path host syncs, non-daemon threads, "
    "bare lock acquires",
    run)
