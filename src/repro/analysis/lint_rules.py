"""AST-based repo-invariant lints over ``src/`` — the contracts that are
documented (docs/ARCHITECTURE.md "Static contracts") but were previously
enforced only by convention:

* **R1 traced-numpy** — no ``numpy`` call reachable (same-module call
  graph) from a traced body: a ``custom_vjp`` primal / registered
  fwd-bwd pair, or a Pallas kernel function.  Host numpy inside a traced
  body is at best a silent constant-fold, at worst a tracer leak.  Two
  sanctioned idioms are excluded: calls whose arguments reference
  ``float0`` (the zero-cotangent convention for integer residuals), and
  anything behind an ``lru_cache`` boundary (trace-safe host
  memoization — the cached value embeds as a constant).
* **R2 lru-cache-static** — ``lru_cache`` only on hashable-static
  signatures: no mutable-literal defaults, no parameter annotated with a
  known-unhashable type (list/dict/set/ndarray).
* **R3 custom-vjp-pairing** — every ``custom_vjp`` primal has a
  ``defvjp`` registration; fwd arity matches the primal; fwd returns a
  literal 2-tuple (out, residuals); bwd takes ``n_nondiff + 2`` args and
  returns one cotangent per differentiable primal arg (literal-tuple
  returns only; computed returns are skipped, not guessed).
* **R4 static-aux-frozen** — dataclasses that act as static aux /
  dispatch keys (names ending Meta/Spec/Config/Fingerprint/Choice/
  Variant/Cell) must be ``frozen=True`` with no unhashable field
  annotations, or they silently break jit caching and autotune keys.
* **R5 fingerprint-fields** — every dispatch-relevant ``SparseMeta``
  field appears in ``autotune.fingerprint``'s reads, and every
  ``Fingerprint`` field appears in ``key()``; a field missed by either
  is a cache-aliasing bug (two different structures, one autotune entry).
* **R6 package-facade** — every name in the package facade's literal
  ``__all__`` (``src/repro/__init__.py``) imports and resolves on the
  live package; a stale export would break every ``import repro``
  README snippet.
* **R7 obs-host-only** — no ``repro.obs`` call reachable (same-module
  call graph, same BFS as R1) from a traced body: observability is
  host-side, and an event emitted under jit either bakes its args in as
  compile-time constants or leaks tracers into the ring buffer.
  ``repro.obs.jaxmon`` is exempt (its wrappers are trace-time-safe by
  design — that is their whole job), as are the obs modules themselves.

``lint_source`` runs R1-R4 and R7 on one module; ``lint_tree`` runs
everything (R5 needs ops.py + autotune.py together; R6 runs when the
tree has a ``repro/__init__.py``) and is what the CLI gates CI on.

>>> fs = lint_source("import functools\\n"
...                  "@functools.lru_cache(maxsize=None)\\n"
...                  "def f(xs: list): return sum(xs)\\n", "x.py")
>>> [f.rule for f in fs]
['lru-cache-static']
"""
from __future__ import annotations

import ast
import os
import re

from repro.analysis.report import Finding

RULES = ("traced-numpy", "lru-cache-static", "custom-vjp-pairing",
         "static-aux-frozen", "fingerprint-fields", "package-facade",
         "obs-host-only")

# dataclasses with these name suffixes are static aux: jit static args,
# scan carries' hashable halves, cache keys
_STATIC_AUX_RE = re.compile(
    r".*(Meta|Spec|Config|Fingerprint|Choice|Variant|Cell)$")

_UNHASHABLE_NAMES = {"list", "List", "dict", "Dict", "set", "Set",
                     "ndarray", "bytearray", "MutableMapping"}

# SparseMeta fields that are legitimately absent from the fingerprint:
# ``shape`` is determined by (n_block_rows, n_block_cols, block) up to
# ragging the N-bucket already captures; ``nnzb_t`` is derived transpose
# bookkeeping, not a dispatch dimension; a ``built="device"`` meta never
# reaches the autotuner (``ops.resolve_backend`` picks ``DEVICE_PICK``).
FINGERPRINT_FIELD_ALLOWLIST = frozenset({"shape", "nnzb_t", "built"})


# ----------------------------------------------------------- AST utilities
def _dotted(node):
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _dec_name(dec):
    """Dotted name of a decorator, unwrapping a call: ``@x.y(...)`` -> x.y."""
    return _dotted(dec.func if isinstance(dec, ast.Call) else dec)


def _is_lru(func_def) -> bool:
    return any((_dec_name(d) or "").endswith("lru_cache")
               for d in func_def.decorator_list)


def _arity(func_def):
    """Positional arity, or None when *args makes it open-ended."""
    a = func_def.args
    if a.vararg is not None:
        return None
    return len(a.posonlyargs) + len(a.args)


def _all_args(func_def):
    a = func_def.args
    return list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)


def _ann_unhashable(ann) -> bool:
    for node in ast.walk(ann):
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        if name in _UNHASHABLE_NAMES:
            return True
    return False


def _mentions_float0(call) -> bool:
    return any(isinstance(n, (ast.Name, ast.Attribute))
               and "float0" in (_dotted(n) or getattr(n, "attr", "") or "")
               for n in ast.walk(call))


class _Module:
    """One parsed module plus the indexes every rule needs."""

    def __init__(self, tree: ast.Module, path: str):
        self.tree = tree
        self.path = path
        self.funcs = {n.name: n for n in tree.body
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef))}
        self.numpy_aliases = set()
        for n in ast.walk(tree):
            if isinstance(n, ast.Import):
                for alias in n.names:
                    if alias.name == "numpy":
                        self.numpy_aliases.add(alias.asname or "numpy")
        # name -> underlying function for ``g = functools.partial(f, ...)``
        self.partial_of = {}
        for n in tree.body:
            if (isinstance(n, ast.Assign) and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)
                    and isinstance(n.value, ast.Call)
                    and (_dotted(n.value.func) or "").endswith("partial")
                    and n.value.args
                    and isinstance(n.value.args[0], ast.Name)):
                self.partial_of[n.targets[0].id] = n.value.args[0].id

    # -- custom_vjp primals: {name: (nondiff_argnums, FunctionDef)}
    def custom_vjp_primals(self):
        out = {}
        for name, fd in self.funcs.items():
            for dec in fd.decorator_list:
                nondiff = None
                if (isinstance(dec, ast.Call)
                        and (_dotted(dec.func) or "").endswith("partial")
                        and dec.args
                        and (_dotted(dec.args[0]) or "").endswith(
                            "custom_vjp")):
                    nondiff = _literal_int_tuple(
                        _kw(dec, "nondiff_argnums")) or ()
                elif (_dec_name(dec) or "").endswith("custom_vjp"):
                    nondiff = (_literal_int_tuple(
                        _kw(dec, "nondiff_argnums")) or ()
                        if isinstance(dec, ast.Call) else ())
                if nondiff is not None:
                    out[name] = (nondiff, fd)
        return out

    # -- defvjp registrations: {primal: (fwd, bwd, lineno)}
    def defvjp_regs(self):
        out = {}
        for n in ast.walk(self.tree):
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "defvjp"
                    and isinstance(n.func.value, ast.Name)
                    and len(n.args) >= 2):
                names = [a.id if isinstance(a, ast.Name) else None
                         for a in n.args[:2]]
                out[n.func.value.id] = (names[0], names[1], n.lineno)
        return out

    # -- pallas kernel bodies (first arg of pl.pallas_call)
    def pallas_kernels(self):
        out = set()
        for n in ast.walk(self.tree):
            if (isinstance(n, ast.Call)
                    and (_dotted(n.func) or "").endswith("pallas_call")
                    and n.args):
                k = n.args[0]
                if isinstance(k, ast.Call) and k.args and \
                        isinstance(k.args[0], ast.Name):
                    k = k.args[0]          # pallas_call(partial(kern, ...))
                if isinstance(k, ast.Name):
                    name = self.partial_of.get(k.id, k.id)
                    if name in self.funcs:
                        out.add(name)
        return out


def _kw(call: ast.Call, name: str):
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _literal_int_tuple(node):
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    return None


# ------------------------------------------------------------------- rules
def _rule_traced_numpy(mod: _Module) -> list:
    findings = []
    primals = mod.custom_vjp_primals()
    regs = mod.defvjp_regs()
    roots = set(primals) | mod.pallas_kernels()
    for primal, (fwd, bwd, _) in regs.items():
        roots |= {n for n in (fwd, bwd) if n}
    # BFS over the same-module call graph, lru_cache as the stop boundary
    seen, queue = set(), [r for r in roots if r in mod.funcs]
    while queue:
        fname = queue.pop()
        if fname in seen:
            continue
        seen.add(fname)
        fd = mod.funcs[fname]
        for node in ast.walk(fd):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if callee and callee.split(".")[0] in mod.numpy_aliases:
                if not _mentions_float0(node):
                    findings.append(Finding(
                        "traced-numpy", mod.path, node.lineno,
                        f"numpy call `{callee}` inside `{fname}`, which is "
                        "reachable from a traced body (custom_vjp / Pallas "
                        "kernel); use jnp, or move it behind an lru_cache "
                        "host-memoization boundary"))
            elif isinstance(node.func, ast.Name) and \
                    node.func.id in mod.funcs:
                target = mod.funcs[node.func.id]
                if not _is_lru(target):
                    queue.append(node.func.id)
    return findings


def _obs_import_map(tree: ast.Module) -> dict:
    """Local name -> fully dotted ``repro.obs...`` origin, covering every
    binding form: ``import repro.obs.trace as t``, ``from repro import
    obs``, ``from repro.obs import trace as obs_trace``, and direct
    function imports (``from repro.obs.trace import span``).  A bare
    ``import repro.obs.trace`` binds ``repro`` and is caught by the
    raw-prefix check in the rule instead."""
    out = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            for alias in n.names:
                if alias.asname and (alias.name == "repro.obs"
                                     or alias.name.startswith("repro.obs.")):
                    out[alias.asname] = alias.name
        elif isinstance(n, ast.ImportFrom) and n.module and n.level == 0:
            for alias in n.names:
                full = f"{n.module}.{alias.name}"
                if full == "repro.obs" or full.startswith("repro.obs."):
                    out[alias.asname or alias.name] = full
    return out


def _rule_obs_host_only(mod: _Module) -> list:
    """R7: same reachability BFS as R1, flagging ``repro.obs`` calls.

    jaxmon is exempt (any resolved path with a ``jaxmon`` segment), and
    the obs package itself is skipped — its modules call each other."""
    if "repro/obs" in mod.path.replace(os.sep, "/"):
        return []
    obs_map = _obs_import_map(mod.tree)
    findings = []
    primals = mod.custom_vjp_primals()
    regs = mod.defvjp_regs()
    roots = set(primals) | mod.pallas_kernels()
    for primal, (fwd, bwd, _) in regs.items():
        roots |= {n for n in (fwd, bwd) if n}
    seen, queue = set(), [r for r in roots if r in mod.funcs]
    while queue:
        fname = queue.pop()
        if fname in seen:
            continue
        seen.add(fname)
        fd = mod.funcs[fname]
        for node in ast.walk(fd):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if not callee:
                continue
            head = callee.split(".")[0]
            if callee == "repro.obs" or callee.startswith("repro.obs."):
                resolved = callee
            elif head in obs_map:
                resolved = obs_map[head] + callee[len(head):]
            else:
                resolved = None
            if resolved is not None:
                if "jaxmon" not in resolved.split("."):
                    findings.append(Finding(
                        "obs-host-only", mod.path, node.lineno,
                        f"obs call `{callee}` inside `{fname}`, which is "
                        "reachable from a traced body (custom_vjp / Pallas "
                        "kernel); observability is host-side — emit the "
                        "event outside the traced region"))
            elif isinstance(node.func, ast.Name) and \
                    node.func.id in mod.funcs:
                if not _is_lru(mod.funcs[node.func.id]):
                    queue.append(node.func.id)
    return findings


def _rule_lru_static(mod: _Module) -> list:
    findings = []
    for fname, fd in mod.funcs.items():
        if not _is_lru(fd):
            continue
        defaults = list(fd.args.defaults) + \
            [d for d in fd.args.kw_defaults if d is not None]
        for d in defaults:
            if isinstance(d, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                              ast.DictComp, ast.SetComp)):
                findings.append(Finding(
                    "lru-cache-static", mod.path, d.lineno,
                    f"`{fname}` is lru_cache'd but has a mutable literal "
                    "default — unhashable, and shared across calls"))
        for arg in _all_args(fd):
            if arg.annotation is not None and \
                    _ann_unhashable(arg.annotation):
                findings.append(Finding(
                    "lru-cache-static", mod.path, arg.annotation.lineno,
                    f"`{fname}` is lru_cache'd but parameter "
                    f"`{arg.arg}` is annotated with an unhashable type; "
                    "cache keys must be hashable statics"))
    return findings


def _rule_custom_vjp(mod: _Module) -> list:
    findings = []
    primals = mod.custom_vjp_primals()
    regs = mod.defvjp_regs()
    for name, (nondiff, fd) in primals.items():
        if name not in regs:
            findings.append(Finding(
                "custom-vjp-pairing", mod.path, fd.lineno,
                f"custom_vjp primal `{name}` has no `{name}.defvjp(fwd, "
                "bwd)` registration in this module"))
            continue
        fwd_name, bwd_name, reg_line = regs[name]
        n_params = _arity(fd)
        fwd = mod.funcs.get(fwd_name)
        bwd = mod.funcs.get(bwd_name)
        if fwd is not None and n_params is not None and \
                _arity(fwd) not in (None, n_params):
            findings.append(Finding(
                "custom-vjp-pairing", mod.path, fwd.lineno,
                f"fwd `{fwd_name}` takes {_arity(fwd)} args but primal "
                f"`{name}` takes {n_params} — fwd sees the primal "
                "signature exactly"))
        if fwd is not None:
            for ret in ast.walk(fwd):
                if isinstance(ret, ast.Return) and \
                        isinstance(ret.value, ast.Tuple) and \
                        len(ret.value.elts) != 2:
                    findings.append(Finding(
                        "custom-vjp-pairing", mod.path, ret.lineno,
                        f"fwd `{fwd_name}` must return a 2-tuple "
                        "(out, residuals), got a "
                        f"{len(ret.value.elts)}-tuple"))
        if bwd is not None:
            want_bwd = len(nondiff) + 2
            if _arity(bwd) not in (None, want_bwd):
                findings.append(Finding(
                    "custom-vjp-pairing", mod.path, bwd.lineno,
                    f"bwd `{bwd_name}` takes {_arity(bwd)} args, want "
                    f"{want_bwd} (nondiff args + residuals + cotangent)"))
            if n_params is not None:
                want_cots = n_params - len(nondiff)
                for ret in ast.walk(bwd):
                    if isinstance(ret, ast.Return) and \
                            isinstance(ret.value, ast.Tuple) and \
                            len(ret.value.elts) != want_cots:
                        findings.append(Finding(
                            "custom-vjp-pairing", mod.path, ret.lineno,
                            f"bwd `{bwd_name}` returns "
                            f"{len(ret.value.elts)} cotangents, want "
                            f"{want_cots} (one per differentiable primal "
                            "arg)"))
    return findings


def _rule_static_aux(mod: _Module) -> list:
    findings = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        dc_dec = None
        for dec in node.decorator_list:
            if (_dec_name(dec) or "").endswith("dataclass"):
                dc_dec = dec
        if dc_dec is None or not _STATIC_AUX_RE.match(node.name):
            continue
        frozen = (isinstance(dc_dec, ast.Call)
                  and any(k.arg == "frozen"
                          and isinstance(k.value, ast.Constant)
                          and k.value.value is True
                          for k in dc_dec.keywords))
        if not frozen:
            findings.append(Finding(
                "static-aux-frozen", mod.path, node.lineno,
                f"dataclass `{node.name}` names a static-aux role "
                "(*Meta/*Spec/*Config/...) but is not frozen=True — it "
                "must be hashable to serve as a jit static / cache key"))
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and \
                    _ann_unhashable(stmt.annotation):
                findings.append(Finding(
                    "static-aux-frozen", mod.path, stmt.lineno,
                    f"`{node.name}` field annotated with an unhashable "
                    "type; static-aux dataclasses must hash"))
    return findings


def check_fingerprint_fields(ops_src: str, autotune_src: str,
                             ops_path: str = "ops.py",
                             autotune_path: str = "autotune.py") -> list:
    """R5 (cross-file): every dispatch-relevant SparseMeta field is read
    by ``fingerprint``/``_make_fingerprint``, and every Fingerprint field
    is rendered by ``key()``."""
    findings = []
    ops_tree = ast.parse(ops_src)
    at_tree = ast.parse(autotune_src)

    def _class(tree, name):
        for n in ast.walk(tree):
            if isinstance(n, ast.ClassDef) and n.name == name:
                return n
        return None

    def _fields(cls):
        return [(s.target.id, s.lineno) for s in cls.body
                if isinstance(s, ast.AnnAssign)
                and isinstance(s.target, ast.Name)]

    def _attr_reads(fn, base):
        return {n.attr for n in ast.walk(fn)
                if isinstance(n, ast.Attribute)
                and isinstance(n.value, ast.Name) and n.value.id == base}

    sparse_meta = _class(ops_tree, "SparseMeta")
    fp_cls = _class(at_tree, "Fingerprint")
    if sparse_meta is None or fp_cls is None:
        return [Finding("fingerprint-fields", autotune_path, 0,
                        "could not locate SparseMeta/Fingerprint classes "
                        "to audit")]
    fp_fns = [n for n in ast.walk(at_tree)
              if isinstance(n, ast.FunctionDef)
              and n.name in ("fingerprint", "_make_fingerprint")]
    reads = set().union(*(_attr_reads(f, "meta") for f in fp_fns)) \
        if fp_fns else set()
    line = fp_fns[0].lineno if fp_fns else 0
    for fname, _ in _fields(sparse_meta):
        if fname not in FINGERPRINT_FIELD_ALLOWLIST and fname not in reads:
            findings.append(Finding(
                "fingerprint-fields", autotune_path, line,
                f"SparseMeta.{fname} is dispatch-relevant but never read "
                "by autotune.fingerprint — two metas differing only in it "
                "would alias one cache entry"))
    key_fn = next((n for n in fp_cls.body
                   if isinstance(n, ast.FunctionDef) and n.name == "key"),
                  None)
    if key_fn is None:
        findings.append(Finding("fingerprint-fields", autotune_path,
                                fp_cls.lineno,
                                "Fingerprint has no key() method"))
    else:
        key_reads = _attr_reads(key_fn, "self")
        for fname, fline in _fields(fp_cls):
            if fname not in key_reads:
                findings.append(Finding(
                    "fingerprint-fields", autotune_path, fline,
                    f"Fingerprint.{fname} is not rendered into key() — "
                    "distinct fingerprints would collide in the cache"))
    return findings


def check_package_facade(init_path: str, package: str = "repro") -> list:
    """R6: every name in the facade's ``__all__`` imports and resolves.

    The export list must be a LITERAL (``ast.literal_eval``-able) so the
    check cannot be fooled by a computed ``__all__``; resolution runs
    against the importable ``package`` on ``sys.path`` — for the CI gate
    that is the same tree being linted (``pythonpath = ["src"]``)."""
    with open(init_path) as f:
        tree = ast.parse(f.read())
    names = None
    line = 0
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "__all__":
                    line = node.lineno
                    try:
                        names = list(ast.literal_eval(node.value))
                    except ValueError:
                        return [Finding(
                            "package-facade", init_path, node.lineno,
                            "__all__ is not a literal list — the facade "
                            "check cannot verify computed exports")]
    if names is None:
        return [Finding("package-facade", init_path, 0,
                        "package facade has no __all__")]
    import importlib
    try:
        mod = importlib.import_module(package)
    except Exception as e:  # noqa: BLE001 — any import failure is the bug
        return [Finding("package-facade", init_path, line,
                        f"`import {package}` failed: {e!r}")]
    findings = []
    for name in names:
        try:
            getattr(mod, name)
        except Exception as e:  # noqa: BLE001
            findings.append(Finding(
                "package-facade", init_path, line,
                f"__all__ name {name!r} does not resolve on "
                f"`import {package}`: {e!r}"))
    return findings


# ------------------------------------------------------------- entrypoints
def lint_source(text: str, path: str = "<source>") -> list:
    """R1-R4 and R7 on one module's source text."""
    mod = _Module(ast.parse(text), path)
    return (_rule_traced_numpy(mod) + _rule_lru_static(mod)
            + _rule_custom_vjp(mod) + _rule_static_aux(mod)
            + _rule_obs_host_only(mod))


def lint_file(path: str) -> list:
    with open(path) as f:
        return lint_source(f.read(), path)


def lint_tree(src_root: str) -> list:
    """All rules over every ``.py`` under ``src_root`` (R5 runs when the
    tree contains kernels/ops.py + kernels/autotune.py; R6 when it has a
    repro/__init__.py facade)."""
    findings = []
    ops_path = autotune_path = None
    for dirpath, _, names in sorted(os.walk(src_root)):
        for name in sorted(names):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            findings += lint_file(path)
            if path.endswith(os.path.join("kernels", "ops.py")):
                ops_path = path
            if path.endswith(os.path.join("kernels", "autotune.py")):
                autotune_path = path
    if ops_path and autotune_path:
        with open(ops_path) as f:
            ops_src = f.read()
        with open(autotune_path) as f:
            at_src = f.read()
        findings += check_fingerprint_fields(ops_src, at_src,
                                             ops_path, autotune_path)
    init_path = os.path.join(src_root, "repro", "__init__.py")
    if os.path.exists(init_path):           # fixture trees have no facade
        findings += check_package_facade(init_path)
    return findings
