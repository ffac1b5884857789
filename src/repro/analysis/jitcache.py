"""Jit-cache fragmentation lint.

The device paths key their jit caches by input *shape*; feeding raw
data-dependent shapes into a jitted entry point compiles once per
distinct shape and fragments the cache (the silent 100x slowdown class).
The repo's contract is pow2 bucketing before dispatch --
``read._bucket`` / ``offload.next_pow2`` / ``offload.run_slots`` -- so
every call site of a jitted entry point must show bucketing evidence in
its enclosing function.

Rule:

* **JC001** -- a call to a registered jitted entry point from a function
  that references no bucketing helper.  The check is per enclosing
  function (the padding usually happens a few lines above the call).

Test files are exempt (they exercise kernels with fixed literal shapes,
which cannot fragment a cache), as is the module that *defines* an
entry point (its internal padding is the implementation, not a call
site).
"""

from __future__ import annotations

import ast
import os

from repro.analysis.findings import Finding

# jitted entry points whose callers must bucket shapes first
ENTRY_POINTS = {
    "lookup_blocks", "bloom_multi_probe", "merge_runs", "sort_tuples",
    "compact_batch", "build_image",
}

# any reference to one of these names counts as bucketing evidence
BUCKET_HELPERS = {
    "next_pow2", "round_up", "_bucket", "bucket", "run_slots",
    "slot_layout", "bucket_blocks", "pad_to_bucket",
}


def _is_test_path(relpath: str) -> bool:
    parts = relpath.replace(os.sep, "/").split("/")
    return any(p in ("tests", "analysis_fixtures") for p in parts) or \
        os.path.basename(relpath).startswith("test_")


def _terminal_name(func: ast.expr) -> str | None:
    """Callee name for module-level targets; None for ``self.*`` chains
    (methods like ``engine.build_image`` bucket internally -- the lint
    targets the raw jitted module functions)."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        base = func
        while isinstance(base, ast.Attribute):
            base = base.value
        if isinstance(base, ast.Name) and base.id == "self":
            return None
        return func.attr
    return None


class JitCacheChecker:
    def __init__(self, relpath: str, tree: ast.Module, source: str):
        self.relpath = relpath
        self.tree = tree
        self.findings: list[Finding] = []
        # names defined at module level: calls to an entry point from the
        # module that defines it are the implementation, not a call site
        self.defined_here = {
            n.name for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}

    def run(self) -> list[Finding]:
        if _is_test_path(self.relpath):
            return []
        self._walk_functions(self.tree.body, "")
        return self.findings

    def _walk_functions(self, body, prefix: str):
        for n in body:
            if isinstance(n, ast.FunctionDef):
                self._check_function(n, f"{prefix}{n.name}")
                self._walk_functions(n.body, f"{prefix}{n.name}.")
            elif isinstance(n, ast.ClassDef):
                self._walk_functions(n.body, f"{prefix}{n.name}.")

    def _check_function(self, fn: ast.FunctionDef, qualname: str):
        calls: list[tuple[ast.Call, str]] = []
        has_bucketing = False
        for n in ast.walk(fn):
            if isinstance(n, ast.FunctionDef) and n is not fn:
                continue        # nested defs get their own pass
            if isinstance(n, ast.Name) and n.id in BUCKET_HELPERS:
                has_bucketing = True
            elif isinstance(n, ast.Attribute) and n.attr in BUCKET_HELPERS:
                has_bucketing = True
            elif isinstance(n, ast.Call):
                callee = _terminal_name(n.func)
                if callee in ENTRY_POINTS and \
                        callee not in self.defined_here:
                    calls.append((n, callee))
        if not has_bucketing:
            for call, callee in calls:
                self.findings.append(Finding(
                    rule="JC001", path=self.relpath, line=call.lineno,
                    qualname=qualname, detail=callee,
                    message=f"'{callee}' is a jitted entry point but "
                            f"'{qualname}' shows no shape bucketing "
                            "(next_pow2/_bucket/run_slots/...); "
                            "data-dependent shapes fragment the jit "
                            "cache -- bucket, or baseline with a shape "
                            "argument"))


def check(relpath: str, tree: ast.Module, source: str) -> list[Finding]:
    return JitCacheChecker(relpath, tree, source).run()
