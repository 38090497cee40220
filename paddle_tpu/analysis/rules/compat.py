"""compat-symbol: version-sensitive jax symbols route through core/compat.py.

jax has renamed ``shard_map`` (now top-level, ``check_vma`` /
``axis_names``; once ``jax.experimental.shard_map`` with ``check_rep`` /
``auto``) and ``pltpu.CompilerParams`` (once ``TPUCompilerParams``).
The installed jax has only the new spellings, and the package reaches
them in exactly one place, ``paddle_tpu/core/compat.py`` — so the old
spellings are forbidden because they are gone, and a direct use of the
new ones is forbidden because the next rename should land in one file
(the class of breakage that took the seed down, CHANGES.md PR 1).

Flagged outside ``core/compat.py``:

- ``from jax.experimental.shard_map import ...`` /
  ``import jax.experimental.shard_map`` / ``from jax import shard_map``
- attribute uses ``jax.shard_map`` / ``jax.experimental.shard_map``
- ``pltpu.CompilerParams`` / ``pltpu.TPUCompilerParams`` (attribute or
  ``getattr(pltpu, "...")``) on any pallas-tpu module alias
- ``check_rep=`` / ``auto=`` keywords on a ``shard_map`` call — the
  removed spelling; the compat wrapper takes ``check_vma=`` /
  ``axis_names=``
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..core import Finding, ParsedFile, call_name, expr_key

RULE = "compat-symbol"

_EXEMPT_SUFFIX = "core/compat.py"
_PARAMS = ("CompilerParams", "TPUCompilerParams")
_FIX = "route it through paddle_tpu/core/compat.py"


def _is_pallas_tpu(node: ast.AST) -> bool:
    key = expr_key(node)
    if key is None:
        return False
    return key == "pltpu" or "pallas" in key.split(".")


def check(pf: ParsedFile, ctx) -> Iterable[Finding]:
    if pf.rel_path.replace("\\", "/").endswith(_EXEMPT_SUFFIX):
        return
    for node in pf.nodes:
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "jax.experimental.shard_map":
                yield pf.finding(
                    RULE, node,
                    "import from jax.experimental.shard_map — gone from "
                    f"the installed jax; {_FIX} (compat.shard_map)")
            elif mod == "jax" and any(a.name == "shard_map"
                                      for a in node.names):
                yield pf.finding(
                    RULE, node,
                    "from jax import shard_map — version-sensitive "
                    f"symbol; {_FIX} (compat.shard_map)")
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax.experimental.shard_map":
                    yield pf.finding(
                        RULE, node,
                        "import jax.experimental.shard_map — gone from "
                        f"the installed jax; {_FIX} (compat.shard_map)")
        elif isinstance(node, ast.Attribute):
            key = expr_key(node)
            if key in ("jax.shard_map", "jax.experimental.shard_map"):
                yield pf.finding(
                    RULE, node,
                    f"direct use of {key} — version-sensitive symbol; "
                    f"{_FIX} (compat.shard_map)")
            elif node.attr in _PARAMS and _is_pallas_tpu(node.value):
                yield pf.finding(
                    RULE, node,
                    f"direct use of pltpu.{node.attr} — renamed across "
                    f"jax versions; {_FIX} "
                    "(compat.pallas_compiler_params())")
        elif isinstance(node, ast.Call):
            cn = call_name(node)
            if cn == "getattr" and len(node.args) >= 2 \
                    and _is_pallas_tpu(node.args[0]) \
                    and isinstance(node.args[1], ast.Constant) \
                    and node.args[1].value in _PARAMS:
                yield pf.finding(
                    RULE, node,
                    f"getattr(pltpu, {node.args[1].value!r}) — renamed "
                    f"across jax versions; {_FIX} "
                    "(compat.pallas_compiler_params())")
            elif cn is not None and cn.split(".")[-1] == "shard_map":
                for kw in node.keywords:
                    if kw.arg in ("check_rep", "auto"):
                        yield pf.finding(
                            RULE, node,
                            f"shard_map(..., {kw.arg}=) is a spelling "
                            "the installed jax no longer has; call "
                            "compat.shard_map with check_vma=/"
                            "axis_names= instead")
