"""R001 shape-keyed-jit and R002 dtype-discipline.

R001 targets the PR 9 decode leak: XLA keys its compile cache on
argument SHAPES, so a serving-path function that feeds request-derived
data into ``jnp`` ops (or mints a fresh ``jax.jit`` per call) compiles
one program per DISTINCT request width — an unbounded compile-cache
leak that stalls open-loop tails by hundreds of ms per new width. The
repo's discipline is pow2 padding-bucketing (``serve.Predictor``): any
hot-path function that touches jnp with request-shaped operands must
show ladder discipline (a ``bit_length``/pow2/bucket/pad computation)
in its body.

R002 targets dtype drift in both directions:

* float64 introduction outside the certified sites — the KKT
  certificate (``smo.kkt_violation``, ``core/cascade.py``) is the ONE
  place the repo deliberately recomputes in f64; anywhere else an f64
  constant/cast silently doubles memory traffic or (under jax's x64
  flag) forks the compiled dtype lattice. Non-certified f64 needs a
  ``noqa`` with a reason (host-side diagnostics are the usual one).
* Pallas kernel matmuls without ``preferred_element_type`` — a bf16
  tile fed to the MXU without an explicit f32 accumulation type
  accumulates at bf16 and silently loses the mixed-precision parity
  the KKT gates certify. Applies to ``*_kernel`` functions (the repo's
  Pallas kernel-body naming convention).
"""
from __future__ import annotations

import ast

from repro.analysis.framework import (Finding, Project, Rule, SourceFile,
                                      call_name, dotted_name, own_nodes,
                                      param_names, register, walk_functions)

# functions that legitimately touch jnp without ladder discipline:
# construction-time uploads and pre-compilation entry points
_R001_EXEMPT_FUNCS = ("__init__", "warmup")
# body markers that show pow2-ladder / padding discipline
_R001_MARKERS = ("pow2", "pad", "bucket")


def _in_scope_r001(path: str) -> bool:
    p = path.replace("\\", "/")
    return "/serve/" in p or p.endswith("/dist.py") or p.endswith("dist.py") \
        and "/" not in p or p.startswith("serve/")


def _has_ladder_marker(fn: ast.AST) -> bool:
    """Does the function body (including nested helpers) show pow2 /
    padding discipline? Markers: a ``.bit_length()`` call (the pow2
    rounding idiom) or any identifier mentioning pow2/pad/bucket."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute):
            ident = node.attr.lower()
            if node.attr == "bit_length":
                return True
            if any(m in ident for m in _R001_MARKERS):
                return True
        elif isinstance(node, ast.Name):
            ident = node.id.lower()
            if any(m in ident for m in _R001_MARKERS):
                return True
    return False


def _references_param(node: ast.AST, params: set[str]) -> list[str]:
    return sorted({n.id for n in ast.walk(node)
                   if isinstance(n, ast.Name) and n.id in params})


@register
class ShapeKeyedJit(Rule):
    name = "R001"
    summary = ("serving/dist hot path feeds request-shaped data to jnp "
               "(or mints jax.jit per call) without pow2 padding-bucket "
               "discipline — one compiled program per distinct width")

    def check(self, src: SourceFile, project: Project) -> list[Finding]:
        if not _in_scope_r001(src.path):
            return []
        out: list[Finding] = []
        for fn in walk_functions(src.tree):
            if fn.name in _R001_EXEMPT_FUNCS:
                continue
            # an lru_cache'd factory builds its jit once per static
            # config — the callable identity (and so the trace cache)
            # is memoized, which is exactly the discipline R001 wants
            if any("cache" in dotted_name(d).lower()
                   or ("cache" in dotted_name(getattr(d, "func", d)).lower()
                       if isinstance(d, ast.Call) else False)
                   for d in fn.decorator_list):
                continue
            padded = _has_ladder_marker(fn)
            params = param_names(fn)
            for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name == "jax.jit":
                    out.append(Finding(
                        rule=self.name, path=src.path, line=node.lineno,
                        col=node.col_offset,
                        message=(f"`jax.jit` constructed inside hot-path "
                                 f"function `{fn.name}` — every call mints "
                                 f"a fresh cache-keyed callable (retrace + "
                                 f"recompile per call); hoist it to "
                                 f"__init__ / module scope")))
                    continue
                if padded or not name.startswith(("jnp.", "jax.numpy.")):
                    continue
                hot_args = [a for arg in (*node.args,
                                          *(k.value for k in node.keywords))
                            for a in _references_param(arg, params)]
                if hot_args:
                    out.append(Finding(
                        rule=self.name, path=src.path, line=node.lineno,
                        col=node.col_offset,
                        message=(f"`{name}` on request-shaped argument(s) "
                                 f"{hot_args} in `{fn.name}` without pow2 "
                                 f"padding-bucket discipline — XLA compiles "
                                 f"one program per distinct shape (the PR 9 "
                                 f"decode-leak class); pad onto the pow2 "
                                 f"ladder first")))
        return out


# --------------------------------------------------------------- R002
# the certified f64 recompute sites: full-precision KKT certificates
# and the float64 oracle served decisions are held to
_R002_CERTIFIED_FILES = ("core/cascade.py", "serve/reference.py")
_R002_CERTIFIED_FUNCS = ("kkt_violation",)
_MATMUL_CALLS = ("jax.lax.dot_general", "lax.dot_general", "jnp.dot",
                 "jnp.matmul", "jnp.einsum", "pl.dot", "pltpu.dot")


def _is_f64_marker(node: ast.AST) -> bool:
    if isinstance(node, (ast.Attribute, ast.Name)):
        from repro.analysis.framework import dotted_name
        d = dotted_name(node)
        return d in ("np.float64", "numpy.float64", "jnp.float64",
                     "jax.numpy.float64")
    if isinstance(node, ast.Constant) and node.value == "float64":  # repro: noqa[R002] -- the rule's own pattern literal, not a dtype use
        return True
    return False


def _certified(src: SourceFile, fn_name: str) -> bool:
    p = src.path.replace("\\", "/")
    return (any(p.endswith(c) for c in _R002_CERTIFIED_FILES)
            or fn_name in _R002_CERTIFIED_FUNCS)


@register
class DtypeDiscipline(Rule):
    name = "R002"
    summary = ("f64 introduced outside the certified KKT-certificate "
               "sites, or a Pallas kernel matmul without "
               "preferred_element_type (bf16 accumulation drift)")

    def check(self, src: SourceFile, project: Project) -> list[Finding]:
        out: list[Finding] = []
        # map every node to its enclosing function name (module level ok)
        enclosing: dict[int, str] = {}
        for fn in walk_functions(src.tree):
            for node in ast.walk(fn):
                enclosing.setdefault(id(node), fn.name)
        if not any(src.path.replace("\\", "/").endswith(c)
                   for c in _R002_CERTIFIED_FILES):
            for node in ast.walk(src.tree):
                if not _is_f64_marker(node):
                    continue
                fn_name = enclosing.get(id(node), "<module>")
                if fn_name in _R002_CERTIFIED_FUNCS:
                    continue
                out.append(Finding(
                    rule=self.name, path=src.path, line=node.lineno,
                    col=node.col_offset,
                    message=(f"float64 introduced in `{fn_name}` outside "
                             f"the certified KKT-certificate sites "
                             f"({', '.join(_R002_CERTIFIED_FUNCS)} / "
                             f"{', '.join(_R002_CERTIFIED_FILES)}); keep "
                             f"device dtypes f32/bf16, or suppress with a "
                             f"reason if this is host-side diagnostics")))
        # Pallas kernel bodies: matmuls must pin f32 accumulation
        for fn in walk_functions(src.tree):
            if not fn.name.endswith("_kernel"):
                continue
            for node in own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name not in _MATMUL_CALLS:
                    continue
                kws = {k.arg for k in node.keywords}
                if "preferred_element_type" not in kws:
                    out.append(Finding(
                        rule=self.name, path=src.path, line=node.lineno,
                        col=node.col_offset,
                        message=(f"`{name}` in Pallas kernel `{fn.name}` "
                                 f"without preferred_element_type — bf16 "
                                 f"tiles would accumulate at bf16 instead "
                                 f"of f32, breaking the mixed-precision "
                                 f"parity gates")))
        return out
