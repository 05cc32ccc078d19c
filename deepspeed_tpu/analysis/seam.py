"""AST-level jax-version-seam lint.

ROADMAP standing constraint: ``utils/jax_compat.py`` is the ONLY place
allowed to spell a version-gated jax API — every other module imports
the portable helper.  This lint enforces that at the AST level (so a
symbol in a comment or docstring never trips it) over the production
tree: ``deepspeed_tpu/``, ``tools/`` and
``__graft_entry__.py``.  Tests are exempt — they may pin version
behavior on purpose.

A violation is a :class:`~deepspeed_tpu.analysis.report.Finding` of kind
``seam_violation`` (severity high), so ``tools/graft_lint.py --seam``
and the tier-1 hook share the baseline/severity machinery with the graph
auditor.  Intentional exceptions live in ``tools/seam_allowlist.json``
as ``"<repo-relative path>::<symbol>"`` entries.
"""

from __future__ import annotations

import ast
import json
import os
from typing import Iterable, List, Optional, Set, Tuple

from deepspeed_tpu.analysis.report import Finding

# The one file allowed to spell the gated APIs — plus this linter,
# which must name them to ban them.
SEAM_FILE = os.path.join("deepspeed_tpu", "utils", "jax_compat.py")
_EXEMPT_FILES = frozenset({
    SEAM_FILE.replace(os.sep, "/"),
    "deepspeed_tpu/analysis/seam.py",
})

# Module prefixes whose spelling has moved between jax releases, plus
# everything under jax._src (private — any release may move it).
GATED_MODULE_PREFIXES = ("jax.experimental.shard_map", "jax._src")

# Attribute chains gated by version: `jax.shard_map` (current-only),
# `jax.memory` (current-only), `jax.sharding.get_abstract_mesh`
# (current-only).
GATED_ATTR_CHAINS = frozenset({
    "jax.shard_map", "jax.memory", "jax.sharding.get_abstract_mesh",
})

# Bare names gated by version wherever they appear (pallas pre-/post-
# stabilization compiler-params class).
GATED_NAMES = frozenset({"TPUCompilerParams"})

# `from jax import <name>` / `from jax.sharding import <name>` forms of
# the gated attribute chains.
_GATED_FROM_IMPORTS = {
    "jax": {"shard_map", "memory"},
    "jax.sharding": {"get_abstract_mesh"},
    "jax.experimental": {"shard_map"},
}

_SCAN_DIRS = ("deepspeed_tpu", "tools")
_SCAN_FILES = ("__graft_entry__.py",)


def _dotted(node: ast.AST) -> Optional[str]:
    """`jax.sharding.get_abstract_mesh` Attribute chain → dotted string
    (None when the chain does not bottom out in a Name)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _violations_in_tree(tree: ast.AST) -> List[Tuple[int, str, str]]:
    """→ [(lineno, symbol, how)] for every gated-symbol use."""
    out: List[Tuple[int, str, str]] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if any(alias.name == p or alias.name.startswith(p + ".")
                       for p in GATED_MODULE_PREFIXES):
                    out.append((node.lineno, alias.name, "import"))
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level:   # relative import — never a jax module
                continue
            if any(mod == p or mod.startswith(p + ".")
                   for p in GATED_MODULE_PREFIXES):
                for alias in node.names:
                    out.append((node.lineno, f"{mod}.{alias.name}",
                                "import-from"))
                continue
            gated = _GATED_FROM_IMPORTS.get(mod, ())
            for alias in node.names:
                if alias.name in gated:
                    out.append((node.lineno, f"{mod}.{alias.name}",
                                "import-from"))
                if alias.name in GATED_NAMES:
                    out.append((node.lineno, alias.name, "import-from"))
        elif isinstance(node, ast.Attribute):
            chain = _dotted(node)
            if chain is None:
                continue
            if chain in GATED_ATTR_CHAINS or any(
                    chain == p or chain.startswith(p + ".")
                    for p in GATED_MODULE_PREFIXES):
                out.append((node.lineno, chain, "attribute"))
            elif node.attr in GATED_NAMES:
                out.append((node.lineno, node.attr, "attribute"))
        elif isinstance(node, ast.Constant):
            # getattr(pltpu, "TPUCompilerParams") and friends
            if isinstance(node.value, str) and node.value in GATED_NAMES:
                out.append((node.lineno, node.value, "string"))
    # one entry per (line, symbol)
    return sorted(set(out))


def lint_source(source: str, rel_path: str,
                allow: Iterable[str] = ()) -> List[Finding]:
    """Lint one file's source text; ``rel_path`` keys the allowlist."""
    rel = rel_path.replace(os.sep, "/")
    if rel in _EXEMPT_FILES:
        return []
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(kind="seam_violation", severity="warning",
                        message=f"unparseable python: {e}",
                        where=rel, detail={"key": "syntax"})]
    allow_set = set(allow)
    findings = []
    for lineno, symbol, how in _violations_in_tree(tree):
        if f"{rel}::{symbol}" in allow_set:
            continue
        findings.append(Finding(
            kind="seam_violation", severity="high",
            message=f"version-gated jax symbol `{symbol}` used directly "
                    f"({how}) — route it through utils/jax_compat.py, "
                    "the repo's only jax-version seam",
            where=f"{rel}:{lineno}",
            detail={"key": symbol, "how": how}))
    return findings


def default_allowlist_path(repo_root: str) -> str:
    return os.path.join(repo_root, "tools", "seam_allowlist.json")


def load_allowlist(path: str) -> Set[str]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            return {str(e) for e in json.load(f).get("allow", [])}
    except FileNotFoundError:
        return set()


def lint_repo(repo_root: str,
              allow: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint the production tree.  ``allow`` defaults to the checked-in
    ``tools/seam_allowlist.json``."""
    if allow is None:
        allow = load_allowlist(default_allowlist_path(repo_root))
    targets: List[str] = []
    for d in _SCAN_DIRS:
        base = os.path.join(repo_root, d)
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in filenames:
                if fn.endswith(".py"):
                    targets.append(os.path.join(dirpath, fn))
    for fn in _SCAN_FILES:
        p = os.path.join(repo_root, fn)
        if os.path.exists(p):
            targets.append(p)
    findings: List[Finding] = []
    for path in sorted(targets):
        rel = os.path.relpath(path, repo_root)
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        findings.extend(lint_source(src, rel, allow=allow))
    return findings
