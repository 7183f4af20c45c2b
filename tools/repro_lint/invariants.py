"""Engine 1: AST invariant lints over `src/` and `benchmarks/`.

Pure stdlib — importing this module (and running every check in it)
never imports jax, so `make lint` stays fast and the `--cache` CLI mode
stays jax-free. Each check enforces one standing invariant from
ROADMAP.md; the finding codes are documented in DESIGN.md §13.

The checks are deliberately *named-pattern* lints, not a general type
system: they encode the specific conventions this repo already holds
itself to (substrate-only distribution plumbing, kernel-only pallas,
validated + routed dispatchers, namespaced autotune keys) and the
specific hazard classes that have actually bitten (silent `block=`
coercion, bare cache keys, tracer leaks).
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterable, List, Set

from tools.repro_lint.findings import Finding

# --- path classification -------------------------------------------------

SUBSTRATE_RE = re.compile(r"(^|/)substrate/")
KERNEL_FILE_RE = re.compile(r"(^|/)kernels/[^/]+/kernel\.py$")
OPS_FILE_RE = re.compile(r"(^|/)kernels/[^/]+/ops\.py$")

# files allowed to mutate jax.config: only the environment set-up
# module, whose `enable_compile_cache` points the persistent
# compilation cache at its fixed directory (DESIGN.md §13); extend
# deliberately, never casually
CONFIG_ALLOWLIST: Set[str] = {"hostenv.py"}

# --- RL101: substrate-only distribution plumbing -------------------------

# canonical dotted names that constitute shard_map / mesh / collective
# plumbing; jax.sharding TYPE imports (Mesh, PartitionSpec,
# NamedSharding) are deliberately NOT here — passing specs around is
# fine, creating meshes / mapping over them / communicating is not
_COLLECTIVES = ("psum", "pmean", "pmax", "pmin", "all_gather",
                "all_to_all", "ppermute", "pshuffle", "psum_scatter",
                "axis_index")
FORBIDDEN_PLUMBING = {
    "jax.shard_map", "jax.make_mesh", "jax.set_mesh",
    "jax.experimental.shard_map", "jax.experimental.mesh_utils",
    "jax.sharding.use_mesh",
} | {f"jax.lax.{c}" for c in _COLLECTIVES}

# --- RL102: kernel-only pallas -------------------------------------------

PALLAS_PREFIX = "jax.experimental.pallas"

# --- RL103/RL104: dispatcher convention ----------------------------------

PREDICATE_RE = re.compile(r"(^|_)is_ragged|routes_to_oracle$")
VALIDATOR_NAME = "validate_block"
PALLAS_CALLEE_RE = re.compile(r"_pallas$")

# --- RL105: namespaced autotune keys -------------------------------------

CACHE_DICT_RE = re.compile(r"^(_memory_cache|disk)$")

# --- RL107: tracer hazards -----------------------------------------------

TRACED_MODULE_PREFIXES = ("jax.numpy.", "jax.nn.", "jax.lax.",
                          "jax.random.", "jax.scipy.")
CAST_NAMES = {"float", "int", "bool"}


def dotted_name(node: ast.AST) -> str | None:
    """`a.b.c` attribute chain -> "a.b.c"; None for anything fancier."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ModuleLint:
    """One parsed file plus the import-alias map the checks share."""

    def __init__(self, path: Path, rel: str, tree: ast.Module):
        self.path = path
        self.rel = rel
        self.tree = tree
        self.findings: List[Finding] = []
        # local alias -> canonical dotted module/name path
        self.aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    self.aliases[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def canonical(self, node: ast.AST) -> str | None:
        """Dotted name with its leading alias resolved through the
        module's imports ("pl.pallas_call" -> "jax.experimental.pallas
        .pallas_call", "jnp.max" -> "jax.numpy.max")."""
        name = dotted_name(node)
        if name is None:
            return None
        head, _, rest = name.partition(".")
        full = self.aliases.get(head, head)
        return f"{full}.{rest}" if rest else full

    def flag(self, node: ast.AST, code: str, message: str) -> None:
        self.findings.append(
            Finding(self.rel, getattr(node, "lineno", 0), code, message))


# --- import boundaries (RL101, RL102) ------------------------------------

def _imported_names(node: ast.Import | ast.ImportFrom) -> Iterable[str]:
    if isinstance(node, ast.Import):
        for a in node.names:
            yield a.name
    elif node.module and node.level == 0:
        for a in node.names:
            yield f"{node.module}.{a.name}"


def check_import_boundaries(mod: ModuleLint) -> None:
    in_substrate = bool(SUBSTRATE_RE.search(mod.rel))
    in_kernel_file = bool(KERNEL_FILE_RE.search(mod.rel))
    for node in ast.walk(mod.tree):
        names: List[str] = []
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = list(_imported_names(node))
        else:
            if isinstance(node, ast.Attribute):
                cname = mod.canonical(node)
                if cname:
                    names = [cname]
        for name in names:
            if not in_substrate and (
                    name in FORBIDDEN_PLUMBING
                    or any(name.startswith(f + ".")
                           for f in FORBIDDEN_PLUMBING)):
                mod.flag(node, "RL101",
                         f"'{name}' is substrate-only plumbing — route it "
                         f"through repro.substrate")
                break
            if not in_kernel_file and (
                    name == PALLAS_PREFIX
                    or name.startswith(PALLAS_PREFIX + ".")):
                mod.flag(node, "RL102",
                         f"'{name}' may only be imported by "
                         f"kernels/*/kernel.py")
                break


# --- dispatcher convention (RL103, RL104) --------------------------------

def _call_names(fn: ast.FunctionDef) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            if name:
                out.add(name.split(".")[-1])
    return out


def _reaches(name: str, calls: Dict[str, Set[str]],
             match) -> bool:
    """True when `name`'s transitive local call closure contains a
    callee whose (unqualified) name satisfies `match`."""
    seen: Set[str] = set()
    stack = [name]
    while stack:
        fn = stack.pop()
        if fn in seen:
            continue
        seen.add(fn)
        for callee in calls.get(fn, ()):
            if match(callee):
                return True
            if callee in calls:
                stack.append(callee)
    return False


def check_dispatcher_convention(mod: ModuleLint) -> None:
    """Every public entry in a kernels/*/ops.py that (transitively)
    reaches a `*_pallas` call must also reach `validate_block` (RL103)
    and a routing predicate of the `routes_to_oracle` / `is_ragged`
    family (RL104) — the convention PR 5 had to retrofit by hand."""
    if not OPS_FILE_RE.search(mod.rel):
        return
    fns = {n.name: n for n in mod.tree.body
           if isinstance(n, ast.FunctionDef)}
    calls = {name: _call_names(fn) for name, fn in fns.items()}
    for name, fn in fns.items():
        if name.startswith("_"):
            continue
        if not _reaches(name, calls,
                        lambda c: bool(PALLAS_CALLEE_RE.search(c))):
            continue
        if not _reaches(name, calls, lambda c: c == VALIDATOR_NAME):
            mod.flag(fn, "RL103",
                     f"dispatcher entry '{name}' reaches a pallas call "
                     f"without common.validate_block")
        if not _reaches(name, calls,
                        lambda c: bool(PREDICATE_RE.search(c))):
            mod.flag(fn, "RL104",
                     f"dispatcher entry '{name}' reaches a pallas call "
                     f"without a routes_to_oracle-family predicate")


# --- namespaced autotune keys (RL105) ------------------------------------

def _literal_key_lacks_namespace(key: ast.AST) -> bool:
    if isinstance(key, ast.Constant) and isinstance(key.value, str):
        return "/" not in key.value
    if isinstance(key, ast.JoinedStr):
        consts = "".join(v.value for v in key.values
                         if isinstance(v, ast.Constant)
                         and isinstance(v.value, str))
        return "/" not in consts
    return False


def check_autotune_keys(mod: ModuleLint) -> None:
    """Stores into the autotune caches (`_memory_cache[...]`,
    `disk[...]`) must use namespaced "<kernel>/..." keys: a literal or
    f-string key whose constant text carries no "/" is the bare-key
    regression class PR 4 migrated away from."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.Assign, ast.AugAssign)):
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for tgt in targets:
            if not isinstance(tgt, ast.Subscript):
                continue
            base = dotted_name(tgt.value)
            if base is None or not CACHE_DICT_RE.match(
                    base.split(".")[-1]):
                continue
            if _literal_key_lacks_namespace(tgt.slice):
                mod.flag(node, "RL105",
                         "autotune cache keys must be namespaced "
                         "'<kernel>/...' (use cache_key())")


# --- jax.config mutation (RL106) -----------------------------------------

def check_config_mutation(mod: ModuleLint) -> None:
    if Path(mod.rel).name in CONFIG_ALLOWLIST:
        return
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            name = mod.canonical(node.func)
            if name == "jax.config.update":
                mod.flag(node, "RL106",
                         "jax.config.update outside the allowlist — "
                         "config belongs to the process owner, not a "
                         "library module")
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                name = mod.canonical(tgt)
                if name and name.startswith("jax.config."):
                    mod.flag(node, "RL106",
                             f"assignment to '{name}' outside the "
                             f"allowlist")


# --- tracer hazards (RL107) ----------------------------------------------

def _is_jit_decorator(mod: ModuleLint, dec: ast.AST) -> bool:
    name = mod.canonical(dec)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(dec, ast.Call):
        fname = mod.canonical(dec.func)
        if fname in ("jax.jit", "jit"):
            return True
        if fname in ("functools.partial", "partial") and dec.args:
            return mod.canonical(dec.args[0]) in ("jax.jit", "jit")
    return False


def _jit_roots(mod: ModuleLint,
               fns: Dict[str, ast.FunctionDef]) -> Set[str]:
    roots = {name for name, fn in fns.items()
             if any(_is_jit_decorator(mod, d) for d in fn.decorator_list)}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) \
                and mod.canonical(node.func) in ("jax.jit", "jit"):
            for arg in node.args[:1]:
                if isinstance(arg, ast.Name) and arg.id in fns:
                    roots.add(arg.id)
    return roots


def _traced_locals(mod: ModuleLint, fn: ast.FunctionDef) -> Set[str]:
    """Names assigned from jnp/jax-producing calls inside `fn` — the
    values a Python cast or branch would force under trace."""
    traced: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            cname = mod.canonical(node.value.func)
            if cname and cname.startswith(TRACED_MODULE_PREFIXES):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        traced.add(tgt.id)
    return traced


def _mentions_traced(mod: ModuleLint, expr: ast.AST,
                     traced: Set[str]) -> bool:
    # `x is None` / `x is not None` identity checks are trace-safe
    # Python (they never force a tracer's value) — prune them before
    # looking for traced mentions
    if isinstance(expr, ast.Compare) \
            and all(isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops):
        return False
    if isinstance(expr, ast.BoolOp):
        return any(_mentions_traced(mod, v, traced) for v in expr.values)
    if isinstance(expr, ast.UnaryOp):
        return _mentions_traced(mod, expr.operand, traced)
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            cname = mod.canonical(node.func)
            if cname and cname.startswith(TRACED_MODULE_PREFIXES):
                return True
        if isinstance(node, ast.Name) and node.id in traced:
            return True
    return False


def _jit_reachable(mod: ModuleLint, fns: Dict[str, ast.FunctionDef],
                   calls: Dict[str, Set[str]]) -> Set[str]:
    """Module-local functions reachable from a jit entry point
    (decorator or direct `jax.jit(f)`) via the intra-module call graph
    — the shared reachability core of RL107 and RL108."""
    reachable: Set[str] = set()
    stack = list(_jit_roots(mod, fns))
    while stack:
        name = stack.pop()
        if name in reachable:
            continue
        reachable.add(name)
        stack.extend(c for c in calls.get(name, ()) if c in fns)
    return reachable


def check_tracer_hazards(mod: ModuleLint) -> None:
    """Inside functions reachable from a jit entry point (decorator or
    direct `jax.jit(f)`), flag the targeted hazard patterns: `.item()`,
    `float()/int()/bool()` on a jnp-derived value, and Python `if`/
    `while` branching on one — each forces a traced value to a Python
    scalar and fails (or silently constant-folds) under jit. Shape
    ints, flags, and oracle routing predicates never match."""
    fns = {n.name: n for n in mod.tree.body
           if isinstance(n, ast.FunctionDef)}
    calls = {name: _call_names(fn) for name, fn in fns.items()}
    for name in _jit_reachable(mod, fns, calls):
        fn = fns[name]
        traced = _traced_locals(mod, fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item":
                    mod.flag(node, "RL107",
                             f".item() in jit-reachable '{name}'")
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in CAST_NAMES \
                        and len(node.args) == 1 \
                        and not isinstance(node.args[0], ast.Constant) \
                        and _mentions_traced(mod, node.args[0], traced):
                    mod.flag(node, "RL107",
                             f"{node.func.id}() on a traced value in "
                             f"jit-reachable '{name}'")
            elif isinstance(node, (ast.If, ast.While)) \
                    and _mentions_traced(mod, node.test, traced):
                mod.flag(node, "RL107",
                         f"Python branch on a traced value in "
                         f"jit-reachable '{name}' — use lax.cond/"
                         f"lax.while_loop")


# --- telemetry in traced code (RL108) ------------------------------------

OBS_MODULE = "repro.obs"


def check_obs_in_jit(mod: ModuleLint) -> None:
    """`repro.obs` counter/span calls must never sit in jit-reachable
    code: under trace they would fire once per COMPILATION (silently
    under-counting every cached re-execution), and a span would time
    tracing, not the computation. Reuses RL107's jit-root reachability.
    Record eagerly from a non-jitted wrapper that skips traced values
    (the engine pattern), or route
    trace-time decisions through `kernels.common.record_route` — the
    one audited funnel, whose counters are documented as
    per-compilation."""
    fns = {n.name: n for n in mod.tree.body
           if isinstance(n, ast.FunctionDef)}
    calls = {name: _call_names(fn) for name, fn in fns.items()}
    for name in _jit_reachable(mod, fns, calls):
        for node in ast.walk(fns[name]):
            if not isinstance(node, ast.Call):
                continue
            cname = mod.canonical(node.func)
            if cname == OBS_MODULE \
                    or (cname and cname.startswith(OBS_MODULE + ".")):
                mod.flag(node, "RL108",
                         f"'{cname}' called in jit-reachable '{name}' — "
                         f"record eagerly (tracer-guarded "
                         f"wrapper) or via kernels.common.record_route")


# --- swallowed exceptions (RL109) ----------------------------------------

_BROAD_EXC = {"Exception", "BaseException", "builtins.Exception",
              "builtins.BaseException"}


def _is_broad_handler(mod: ModuleLint, handler: ast.ExceptHandler) -> bool:
    """Bare `except:`, or a clause (or tuple member) catching
    Exception/BaseException."""
    if handler.type is None:
        return True
    types = handler.type.elts if isinstance(handler.type, ast.Tuple) \
        else [handler.type]
    return any(mod.canonical(t) in _BROAD_EXC for t in types)


def _handler_records(mod: ModuleLint, handler: ast.ExceptHandler) -> bool:
    """Does the handler body re-raise, record to `repro.obs`, or capture
    the traceback? (The three accepted ways to not lose the error.)"""
    for node in ast.walk(ast.Module(body=handler.body, type_ignores=[])):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            cname = mod.canonical(node.func)
            if cname and (cname == OBS_MODULE
                          or cname.startswith(OBS_MODULE + ".")
                          or cname.startswith("traceback.")):
                return True
    return False


def check_exception_swallowing(mod: ModuleLint) -> None:
    """Broad handlers (`except:` / `except Exception` / BaseException)
    must not swallow the error silently: the body has to re-raise,
    record a `repro.obs` counter, or capture the traceback. A silent
    `pass`/`return` fallback turns every future failure — a torn
    checkpoint, a dead backend probe — into undebuggable nothing; the
    resilience layer (DESIGN.md §15) depends on degraded paths staying
    observable. Narrowing to the concrete exception types also
    satisfies the rule."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if _is_broad_handler(mod, node) and not _handler_records(mod, node):
            mod.flag(node, "RL109",
                     "broad exception handler swallows the error "
                     "silently — re-raise, narrow the exception types, "
                     "record a repro.obs counter, or capture the "
                     "traceback")


# --- driver --------------------------------------------------------------

ALL_CHECKS = (
    check_import_boundaries,
    check_dispatcher_convention,
    check_autotune_keys,
    check_config_mutation,
    check_tracer_hazards,
    check_obs_in_jit,
    check_exception_swallowing,
)


def iter_py_files(paths: Iterable[str]) -> Iterable[Path]:
    for p in paths:
        path = Path(p)
        if path.is_file() and path.suffix == ".py":
            yield path
        elif path.is_dir():
            yield from sorted(f for f in path.rglob("*.py")
                              if "__pycache__" not in f.parts)


def lint_file(path: Path, rel: str | None = None) -> List[Finding]:
    rel = rel if rel is not None else str(path)
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as e:
        return [Finding(rel, e.lineno or 0, "RL100",
                        f"syntax error: {e.msg}")]
    mod = ModuleLint(path, rel, tree)
    for check in ALL_CHECKS:
        check(mod)
    return mod.findings


def lint_paths(paths: Iterable[str]) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_py_files(paths):
        findings.extend(lint_file(path))
    return sorted(findings)
