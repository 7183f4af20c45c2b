"""`--cache` mode: validate a committed autotune cache file.

Pure stdlib (json + re) — never imports jax, so this runs in CI jobs
and pre-commit hooks that have no accelerator stack at all. It catches
the legacy bare-key regression class from PR 4/5: every key in
`.cache/autotune.json` must be namespaced `"<kernel>/<backend>_<dims>_
<dtype>"`, optionally followed by "@<digest>" of the swept candidate
list, with the dimension spec and value arity that kernel's sweep
actually writes (`kernels/autotune.py::cache_key`).
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import List

from tools.repro_lint.findings import Finding

KEY_RE = re.compile(
    r"^(?P<kernel>[a-z0-9_]+)/(?P<backend>[a-z0-9]+)_"
    r"(?P<dims>[a-z]+\d+(?:_[a-z]+\d+)*)_(?P<dtype>[a-z0-9]+)"
    r"(?:@[0-9a-f]{8})?$")

# kernel namespace -> (ordered dim letters, value arity)
KERNEL_SHAPES = {
    "fista_step": (("m", "p", "r"), 3),
    "logistic_grad": (("m", "n", "p"), 2),
    "rank_update": (("m", "n", "p"), 2),
}


def _dims_of(spec: str) -> tuple:
    return tuple(re.match(r"[a-z]+", part).group(0)
                 for part in spec.split("_"))


def _value_ok(value, arity: int) -> bool:
    if isinstance(value, list):
        return (len(value) == arity
                and all(isinstance(b, int) and not isinstance(b, bool)
                        and b >= 1 for b in value))
    # pre-namespace fista entries were bare ints (square blocks); they
    # are migrated to triples on load, but a committed int is still a
    # servable legacy form for fista_step only
    return (arity == 3 and isinstance(value, int)
            and not isinstance(value, bool) and value >= 1)


def check_cache_file(path: str | Path) -> List[Finding]:
    path = Path(path)
    rel = str(path)
    if not path.exists():
        return []                      # nothing committed, nothing to check
    try:
        entries = json.loads(path.read_text())
    except ValueError as e:
        return [Finding(rel, 0, "RL302", f"unparseable JSON: {e}")]
    if not isinstance(entries, dict):
        return [Finding(rel, 0, "RL302",
                        "cache root must be a JSON object")]
    findings: List[Finding] = []
    for key, value in entries.items():
        if "/" not in key:
            findings.append(Finding(
                rel, 0, "RL301",
                f"bare (un-namespaced) key {key!r} — the pre-PR-4 "
                f"regression class; keys must be '<kernel>/...'"))
            continue
        m = KEY_RE.match(key)
        if not m or m.group("kernel") not in KERNEL_SHAPES:
            findings.append(Finding(
                rel, 0, "RL302",
                f"key {key!r} has an unknown namespace or malformed "
                f"'<kernel>/<backend>_<dims>_<dtype>' spec"))
            continue
        dims, arity = KERNEL_SHAPES[m.group("kernel")]
        if _dims_of(m.group("dims")) != dims:
            findings.append(Finding(
                rel, 0, "RL302",
                f"key {key!r} carries dims "
                f"{_dims_of(m.group('dims'))}, expected {dims} for "
                f"'{m.group('kernel')}'"))
        if not _value_ok(value, arity):
            findings.append(Finding(
                rel, 0, "RL303",
                f"value for {key!r} must be a list of {arity} positive "
                f"ints, got {value!r}"))
    return findings
