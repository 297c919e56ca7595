"""jax-hygiene — the three JAX idioms this repo has re-learned in review.

1. **x64 is scoped, never global.**  ``jax.config.update("jax_enable_
   x64", ...)`` flips dtype semantics for EVERY jitted program in the
   process — the crypto kernels are traced under 32-bit semantics and
   silently produce wrong limbs afterwards.  The spelling is the scoped
   context manager ``with jax.enable_x64(True):`` (see
   ``fork_choice/device_proto_array.py`` throughout);
   ``jax.experimental.enable_x64`` no longer exists in the installed jax.

2. **One shard_map spelling.**  ``jax.shard_map(..., check_vma=False)``
   — the top-level function of the installed jax.  The
   ``jax.experimental.shard_map`` module and its ``check_rep`` kwarg are
   deprecated; ``check_vma=False`` is explicit because the kernels close
   over replicated constant tables (``parallel/mesh.mesh_program``).

3. **No ``jnp.`` computation at import time.**  A module-level
   ``jnp.arange(...)`` materializes a device buffer (and may initialize
   the backend) the moment the module imports — import order starts
   deciding device state, and CPU-only test processes pay for buffers
   they never use.  Module constants stay numpy; convert at trace time.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from ..core import Checker, Context, Finding, dotted, register, str_const


def _root(chain: str) -> str:
    return chain.split(".", 1)[0]


@register
class JaxHygieneChecker(Checker):
    name = "jax-hygiene"
    doc = ("enable_x64 only as the scoped jax.enable_x64(True); "
           "shard_map only as jax.shard_map with check_vma=False; "
           "no jnp. computation at module import time")

    def check(self, ctx: Context, path: str, tree: ast.AST,
              lines) -> Iterable[Finding]:
        out: List[Finding] = []
        self._scan(tree, path, out, depth=0, func="module")
        for node in ast.walk(tree):
            if not isinstance(node, ast.ImportFrom) or not node.module:
                continue
            names = {a.name for a in node.names}
            if node.module == "jax.experimental.shard_map" or (
                    node.module.startswith("jax.")
                    and "shard_map" in names):
                out.append(Finding(
                    self.name, path, node.lineno,
                    f"shard_map imported from {node.module!r} — the "
                    f"installed spelling is the top-level jax.shard_map",
                    hint="call jax.shard_map(..., check_vma=False)",
                    detail="shard-map-import"))
            if node.module == "jax.experimental" and \
                    "enable_x64" in names:
                out.append(Finding(
                    self.name, path, node.lineno,
                    "enable_x64 imported from jax.experimental — gone "
                    "from the installed jax",
                    hint="use the scoped form: "
                         "'with jax.enable_x64(True):'",
                    detail="enable-x64-import"))
        return out

    def _scan(self, node: ast.AST, path: str, out: List[Finding],
              depth: int, func: str) -> None:
        """depth counts enclosing function bodies (0 = import time)."""
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                self._scan(d, path, out, depth, func)
            for default in node.args.defaults + \
                    [d for d in node.args.kw_defaults if d is not None]:
                self._scan(default, path, out, depth, func)
            for child in node.body:
                self._scan(child, path, out, depth + 1, node.name)
            return
        if isinstance(node, ast.Lambda):
            self._scan(node.body, path, out, depth + 1, func)
            return
        if isinstance(node, ast.Call):
            self._call(node, path, out, depth, func)
        for child in ast.iter_child_nodes(node):
            self._scan(child, path, out, depth, func)

    def _call(self, node: ast.Call, path: str, out: List[Finding],
              depth: int, func: str) -> None:
        chain = dotted(node.func) or ""

        if chain.endswith("config.update") and node.args:
            key = str_const(node.args[0]) or ""
            if "enable_x64" in key:
                out.append(Finding(
                    self.name, path, node.lineno,
                    "global jax_enable_x64 via config.update — flips "
                    "dtype semantics for every jitted program in the "
                    "process (the crypto kernels trace under 32-bit "
                    "semantics)",
                    hint="use the scoped form: "
                         "'with jax.enable_x64(True):'",
                    detail=f"enable-x64-config:{func}"))

        if chain == "jax.experimental.enable_x64":
            out.append(Finding(
                self.name, path, node.lineno,
                "jax.experimental.enable_x64 — gone from the installed jax",
                hint="use the scoped form: 'with jax.enable_x64(True):'",
                detail=f"enable-x64-spelling:{func}"))

        if chain == "jax.shard_map":
            kw = {k.arg: k.value for k in node.keywords}
            ok = isinstance(kw.get("check_vma"), ast.Constant) and \
                kw["check_vma"].value is False
            if not ok:
                out.append(Finding(
                    self.name, path, node.lineno,
                    "jax.shard_map call without check_vma=False — the "
                    "kernels close over replicated constant tables, "
                    "which the varying-axes check rejects",
                    hint="pass check_vma=False explicitly (mirror "
                         "parallel/mesh.mesh_program)",
                    detail=f"shard-map-check-vma:{func}"))
        elif chain == "shard_map" or chain.endswith(".shard_map"):
            # elif: a wrong-spelling call is ONE defect — reporting a
            # missing check_vma too would mint a second waiver key that
            # goes stale the moment the spelling is fixed.
            out.append(Finding(
                self.name, path, node.lineno,
                f"{chain}(...) — the installed spelling is the top-level "
                f"jax.shard_map",
                hint="call jax.shard_map(..., check_vma=False)",
                detail=f"shard-map-spelling:{func}"))

        if depth == 0 and (_root(chain) == "jnp"
                           or chain.startswith("jax.numpy.")):
            out.append(Finding(
                self.name, path, node.lineno,
                f"{chain}(...) at module import time — materializes "
                f"device buffers / initializes the backend on import",
                hint="keep module constants numpy and convert at "
                     "trace time, or build lazily inside the function",
                detail=f"module-jnp:{chain}"))
