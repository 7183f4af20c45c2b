"""Engine 2: static Pallas tiling/VMEM contract checks.

Two passes, neither of which needs a TPU or executes a kernel:

* **Geometry (RL201/RL202)** — pure AST over each
  `kernels/*/kernel.py`: every `pl.BlockSpec` fed to a `pl.pallas_call`
  must have an index_map whose arity matches that call's `grid`, and
  every symbolic tile parameter used in a BlockSpec shape must be
  covered by a `%`-divisibility assert somewhere in the wrapper module
  (the guard that turns a bad tile into a loud shape error instead of a
  silently wrong grid).

* **Dispatch contracts (RL210–RL213)** — imports the dispatchers' own
  routing predicates, resolvers, and byte models (`kernel_vmem_bytes`,
  `LOGISTIC_VMEM_BUDGET`, `rank_vmem_bytes`, `aligned_fit_block`) and
  evaluates them over an adversarial shape×block grid: every
  configuration the predicate lets through to the kernel must resolve
  to divisor tiles that obey the TPU's (8, 128) rule — a multiple of
  128 or the whole axis on lanes, 8-aligned or the whole axis on
  sublanes (RL211) — inside the kernel's VMEM budget
  (RL210), the predicate and the resolver must agree with the
  dispatcher's own fused route-and-resolve path (RL212), and every
  tiling the autotuner would sweep must be one the dispatcher will
  actually serve (RL213 — a winner the dispatcher re-routes to the
  oracle is a shape that silently loses its kernel path forever).
  The grid pins the PR-5 regression shapes (n = 1016 = 8·127 sliver
  traps, p = 8168 budget-collapse, p = 16k+ accumulator blow-ups) so
  budget drift and alignment traps fail at lint time, before any test
  executes a kernel.

This module imports jax transitively (through the repro dispatchers) —
the `--cache` CLI mode never loads it.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Set, Tuple

from tools.repro_lint.findings import Finding
from tools.repro_lint.invariants import (
    KERNEL_FILE_RE, dotted_name, iter_py_files,
)

# --- geometry pass (RL201 / RL202) ---------------------------------------


def _lambda_accepts(lam: ast.AST, arity: int) -> bool:
    if not isinstance(lam, ast.Lambda):
        return True                    # not statically checkable
    args = lam.args
    npos = len(args.args)
    if args.vararg is not None:
        return arity >= npos
    return arity == npos + len(args.kwonlyargs) * 0 \
        if not args.defaults else arity >= npos - len(args.defaults)


def _blockspec_nodes(call: ast.Call,
                     local_specs: Dict[str, List[ast.Call]]
                     ) -> List[ast.Call]:
    """Resolve the BlockSpec nodes fed to one pallas_call: direct
    `pl.BlockSpec(...)` calls, plus local-variable references resolved
    FLOW-SENSITIVELY to the latest assignment above the call (a wrapper
    with two pallas_call branches may rebind the same spec name per
    branch — e.g. the logistic full-lane vs feature-tiled layouts)."""
    out: List[ast.Call] = []

    def resolve(node: ast.AST) -> None:
        if isinstance(node, (ast.List, ast.Tuple)):
            for elt in node.elts:
                resolve(elt)
        elif isinstance(node, ast.Call):
            name = dotted_name(node.func)
            if name and name.split(".")[-1] == "BlockSpec":
                out.append(node)
        elif isinstance(node, ast.Name) and node.id in local_specs:
            prior = [spec for spec in local_specs[node.id]
                     if spec.lineno < call.lineno]
            if prior:
                out.append(max(prior, key=lambda spec: spec.lineno))

    for kw in call.keywords:
        if kw.arg in ("in_specs", "out_specs"):
            resolve(kw.value)
    return out


def _grid_arity(call: ast.Call) -> int | None:
    for kw in call.keywords:
        if kw.arg == "grid":
            if isinstance(kw.value, ast.Tuple):
                return len(kw.value.elts)
            return 1
    return None


def _module_divisibility_names(tree: ast.Module) -> Set[str]:
    """Names appearing inside `%`-expressions of asserts anywhere in
    the module — `assert n % bn == 0 and p % bp == 0` covers
    {n, bn, p, bp} even when the assert lives in a shared helper."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assert):
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Mod):
                    for leaf in ast.walk(sub):
                        if isinstance(leaf, ast.Name):
                            names.add(leaf.id)
    return names


def check_kernel_geometry(path: Path, rel: str) -> List[Finding]:
    findings: List[Finding] = []
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as e:
        return [Finding(rel, e.lineno or 0, "RL100",
                        f"syntax error: {e.msg}")]
    asserted = _module_divisibility_names(tree)
    for fn in [n for n in tree.body if isinstance(n, ast.FunctionDef)]:
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        local_specs: Dict[str, List[ast.Call]] = {}
        pallas_calls: List[ast.Call] = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                vname = dotted_name(node.value.func)
                if vname and vname.split(".")[-1] == "BlockSpec":
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            local_specs.setdefault(tgt.id, []) \
                                .append(node.value)
            if isinstance(node, ast.Call):
                cname = dotted_name(node.func)
                if cname and cname.split(".")[-1] == "pallas_call":
                    pallas_calls.append(node)
        for call in pallas_calls:
            arity = _grid_arity(call)
            if arity is None:
                continue
            for spec in _blockspec_nodes(call, local_specs):
                # positional form: BlockSpec(shape, index_map)
                shape = spec.args[0] if spec.args else None
                imap = spec.args[1] if len(spec.args) > 1 else None
                if imap is not None and not _lambda_accepts(imap, arity):
                    findings.append(Finding(
                        rel, spec.lineno, "RL201",
                        f"BlockSpec index_map arity disagrees with "
                        f"grid arity {arity} in '{fn.name}'"))
                if isinstance(shape, ast.Tuple):
                    for elt in shape.elts:
                        if isinstance(elt, ast.Name) \
                                and elt.id in params \
                                and elt.id not in asserted:
                            findings.append(Finding(
                                rel, spec.lineno, "RL202",
                                f"tile parameter '{elt.id}' used in a "
                                f"BlockSpec of '{fn.name}' has no "
                                f"divisibility assert in this module"))
    return findings


def check_geometry(paths) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_py_files(paths):
        rel = str(path)
        if KERNEL_FILE_RE.search(rel.replace("\\", "/")):
            findings.extend(check_kernel_geometry(path, rel))
    return findings


# --- dispatch-contract pass (RL210–RL213) --------------------------------

# adversarial shape grid: powers of two, the PR-5 sliver/alignment
# regressions (1016 = 8·127, 504 = 8·63, 8168 = 8·1021), ragged odds,
# small axes, and the budget-busting top end
GRID_N = (8, 30, 64, 120, 128, 200, 504, 1016, 1024, 4096)
GRID_P = (8, 64, 128, 200, 504, 1016, 2048, 2720, 4096, 8168, 8192,
          16384, 32768)
LOGISTIC_BLOCKS = (None, 8, 32, 128, 256, 1024,
                   (8, 8), (64, 8), (128, 128), (128, 1024), (128, 2048),
                   (256, 4096), (1024, 8))
RANK_BLOCKS = (8, 32, 64, 128, 256, (8, 8), (32, 128), (128, 32),
               (256, 256))
SOLVER_P = (8, 40, 80, 128, 504, 1016, 1024, 4096)
SOLVER_R = (1, 8, 64, 128)
SOLVER_BLOCKS = (8, 32, 128, 256, (48, 8, 48), (128, 1, 128),
                 (64, 8, 64))


def _aligned_divisor(size: int, tile: int) -> bool:
    return size % tile == 0 and (tile % 8 == 0 or tile == size)


def _lane_divisor(size: int, tile: int) -> bool:
    return size % tile == 0 and (tile % 128 == 0 or tile == size)


def check_logistic_contract() -> List[Finding]:
    from repro.kernels.logistic_grad.ops import (
        LOGISTIC_VMEM_BUDGET, _route_and_resolve, kernel_vmem_bytes,
        resolve_logistic_blocks, routes_to_oracle,
    )
    rel = "src/repro/kernels/logistic_grad/ops.py"
    findings: List[Finding] = []
    for n in GRID_N:
        for p in GRID_P:
            for block in LOGISTIC_BLOCKS:
                reason, bn, bp = _route_and_resolve(n, p, block)
                if (reason is not None) != routes_to_oracle(n, p, block) \
                        or (bn, bp) != resolve_logistic_blocks(n, p, block):
                    findings.append(Finding(
                        rel, 0, "RL212",
                        f"routes_to_oracle/resolve_logistic_blocks "
                        f"disagree with _route_and_resolve at "
                        f"(n={n}, p={p}, block={block})"))
                if reason is not None:
                    continue
                if not (_aligned_divisor(n, bn)
                        and _lane_divisor(p, bp)):
                    findings.append(Finding(
                        rel, 0, "RL211",
                        f"dispatchable (n={n}, p={p}, block={block}) "
                        f"resolves misaligned/non-divisor tiles "
                        f"(bn={bn}, bp={bp})"))
                if kernel_vmem_bytes(p, bn, bp) > LOGISTIC_VMEM_BUDGET:
                    findings.append(Finding(
                        rel, 0, "RL210",
                        f"dispatchable (n={n}, p={p}, block={block}) "
                        f"-> (bn={bn}, bp={bp}) busts "
                        f"LOGISTIC_VMEM_BUDGET: "
                        f"{kernel_vmem_bytes(p, bn, bp)} bytes"))
    return findings


def check_logistic_autotune_candidates() -> List[Finding]:
    from repro.kernels.autotune import logistic_candidates
    from repro.kernels.logistic_grad.ops import routes_to_oracle
    rel = "src/repro/kernels/autotune.py"
    findings: List[Finding] = []
    for n in GRID_N:
        for p in GRID_P:
            if routes_to_oracle(n, p):
                continue       # sweep never runs for oracle shapes
            for cand in logistic_candidates(n, p):
                if routes_to_oracle(n, p, cand):
                    findings.append(Finding(
                        rel, 0, "RL213",
                        f"logistic_candidates(n={n}, p={p}) offers "
                        f"{cand}, which the dispatcher routes to the "
                        f"oracle — a timed winner would silently lose "
                        f"the kernel path"))
    return findings


def check_rank_contract() -> List[Finding]:
    from repro.kernels.autotune import rank_candidates
    from repro.kernels.rank_update.ops import (
        RANK_VMEM_BUDGET, rank_routes_to_oracle, rank_vmem_bytes,
        resolve_rank_blocks,
    )
    rel = "src/repro/kernels/rank_update/ops.py"
    findings: List[Finding] = []
    for n in GRID_N:
        for p in GRID_P[:10]:
            for block in RANK_BLOCKS:
                if rank_routes_to_oracle(n, p, block):
                    continue
                bp, bn = resolve_rank_blocks(n, p, block)
                if not (_lane_divisor(p, bp)
                        and _aligned_divisor(n, bn)):
                    findings.append(Finding(
                        rel, 0, "RL211",
                        f"dispatchable (n={n}, p={p}, block={block}) "
                        f"resolves misaligned/non-divisor tiles "
                        f"(bp={bp}, bn={bn})"))
                if rank_vmem_bytes(bp, bn) > RANK_VMEM_BUDGET:
                    findings.append(Finding(
                        rel, 0, "RL210",
                        f"dispatchable (n={n}, p={p}, block={block}) "
                        f"-> (bp={bp}, bn={bn}) busts RANK_VMEM_BUDGET: "
                        f"{rank_vmem_bytes(bp, bn)} bytes"))
            if not rank_routes_to_oracle(n, p):
                for cand in rank_candidates(n, p):
                    if rank_routes_to_oracle(n, p, cand):
                        findings.append(Finding(
                            "src/repro/kernels/autotune.py", 0, "RL213",
                            f"rank_candidates(n={n}, p={p}) offers "
                            f"{cand}, which the dispatcher routes to "
                            f"the oracle"))
    return findings


def check_solver_contract() -> List[Finding]:
    from repro.kernels.autotune import block_candidates
    from repro.kernels.ista_step.kernel import (
        DEFAULT_SCOPED_VMEM, step_vmem_bytes, step_vmem_limit,
    )
    from repro.kernels.ista_step.ops import (
        STEP_VMEM_BUDGET, resolve_blocks, step_routes_to_oracle,
    )
    rel = "src/repro/kernels/ista_step/ops.py"
    findings: List[Finding] = []
    for p in SOLVER_P:
        for r in dict.fromkeys(SOLVER_R + (p,)):      # and r = p
            for block in SOLVER_BLOCKS + tuple(block_candidates(p, r)):
                if step_routes_to_oracle(p, r, block):
                    continue
                bp, br, bk = resolve_blocks(p, r, block)
                ok = (_aligned_divisor(p, bp) and _lane_divisor(r, br)
                      and _lane_divisor(p, bk))
                if not ok:
                    findings.append(Finding(
                        rel, 0, "RL211",
                        f"dispatchable (p={p}, r={r}, block={block}) "
                        f"resolves non-divisor tiles "
                        f"(bp={bp}, br={br}, bk={bk})"))
                need = step_vmem_bytes(bp, br, bk)
                limit = step_vmem_limit(bp, br, bk) or DEFAULT_SCOPED_VMEM
                if need > STEP_VMEM_BUDGET or need > limit:
                    findings.append(Finding(
                        rel, 0, "RL210",
                        f"dispatchable (p={p}, r={r}, block={block}) -> "
                        f"(bp={bp}, br={br}, bk={bk}) takes {need} bytes "
                        f"of VMEM: over STEP_VMEM_BUDGET "
                        f"({STEP_VMEM_BUDGET}) or the {limit} bytes the "
                        f"call asks for"))
    return findings


def check_master_contracts() -> List[Finding]:
    """group_threshold / flash_attention: resolver output must stay a
    divisor of its axis for every shape the predicate lets through."""
    from repro.kernels.flash_attention.ops import (
        flash_routes_to_oracle, resolve_flash_blocks,
    )
    from repro.kernels.group_threshold.ops import (
        group_routes_to_oracle, resolve_group_block,
    )
    findings: List[Finding] = []
    for p in GRID_P[:11] + (200000,):
        for block in (None, 8, 64, 256, 1024):
            if group_routes_to_oracle(p, block):
                continue
            bp = resolve_group_block(p, block)
            if not _aligned_divisor(p, bp):
                findings.append(Finding(
                    "src/repro/kernels/group_threshold/ops.py", 0,
                    "RL211",
                    f"dispatchable (p={p}, block={block}) resolves "
                    f"misaligned/non-divisor tile bp={bp}"))
    for S in (32, 64, 100, 128, 192, 256, 1016):
        for T in (64, 128, 256):
            for block in ((256, 256), (64, 64), (32, 128)):
                if flash_routes_to_oracle(S, T, block):
                    continue
                bq, bk = resolve_flash_blocks(S, T, block)
                if not (_aligned_divisor(S, bq)
                        and _aligned_divisor(T, bk)):
                    findings.append(Finding(
                        "src/repro/kernels/flash_attention/ops.py", 0,
                        "RL211",
                        f"dispatchable (S={S}, T={T}, block={block}) "
                        f"resolves misaligned/non-divisor tiles "
                        f"(bq={bq}, bk={bk})"))
    return findings


def check_dispatch_contracts() -> List[Finding]:
    findings: List[Finding] = []
    findings.extend(check_logistic_contract())
    findings.extend(check_logistic_autotune_candidates())
    findings.extend(check_rank_contract())
    findings.extend(check_solver_contract())
    findings.extend(check_master_contracts())
    return findings


def check_contracts(paths) -> List[Finding]:
    """Full Engine-2 run: AST geometry over the given paths plus the
    imported dispatch-contract grid."""
    findings = check_geometry(paths)
    findings.extend(check_dispatch_contracts())
    return sorted(findings)
