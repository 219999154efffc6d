"""AST repo-invariant rules (layer 2): `ast`-based lint over `src/repro/`.

Four custom rules encode the repo's structural invariants — the things the
test suites can't see because they are about *how the source is written*,
not what it computes:

  * raw-dense-bypass — models/ and serve/ must route matmuls and convs
    through the engine (`api.dense` / `api.conv2d` / compiled programs),
    never raw `jnp.einsum`/`jnp.dot`/`@`/`lax.conv*`: a bypass skips
    planning, precision pinning, tuning and fault injection. kernels/ and
    core/ implement the engine and are allowlisted wholesale; the
    attention/SSM model families hold activation-activation contractions
    the engine does not cover yet (a ROADMAP open item) and carry
    documented module allowlist entries.
  * mutable-global — config-like state must live on the thread-local
    stacks (`config._TLS` pattern), not in module globals: a module-level
    binding that is rebound via `global` or mutated from inside functions
    is flagged unless it carries an `# analyze: allow[mutable-global]`
    pragma naming it a sanctioned registry/override slot.
  * fault-hook-unguarded — `serve.faults.active()` returns
    Optional[FaultInjector]; every hook site must bind it to a local and
    None-check before use. Chaining `.fire()` straight off `active()` (or
    using the local before a None test) crashes every un-faulted run.
  * kernel-nondeterminism — Pallas kernel bodies (functions handed to
    `pl.pallas_call`, directly or via `functools.partial`, or named
    `*_kernel`) must be bitwise-reproducible: no wall clocks, no stdlib /
    numpy RNG, no `id()`/`hash()` (`jax.random` with an explicit key is
    deterministic and allowed).

Suppression: a finding is dropped when its source line carries
`# analyze: allow[<rule-id>]`. Module-wide allowlists live in
`RAW_DENSE_MODULE_ALLOW` with the reason recorded next to each entry.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.analyze.diagnostics import (Diagnostic, Report, Rule, finding,
                                       register_rule)

register_rule(Rule(
    id="raw-dense-bypass", severity="error", layer="ast",
    contract="models/ and serve/ must route matmuls/convs through the "
             "engine API, not raw jnp.einsum/dot/@/lax.conv* — a bypass "
             "skips planning, precision, tuning and fault injection"))
register_rule(Rule(
    id="mutable-global", severity="error", layer="ast",
    contract="no module-level mutable config state outside the "
             "thread-local stacks; sanctioned registry slots carry an "
             "explicit allow pragma"))
register_rule(Rule(
    id="fault-hook-unguarded", severity="error", layer="ast",
    contract="serve.faults.active() returns an Optional and every hook "
             "site must None-check it before use"))
register_rule(Rule(
    id="kernel-nondeterminism", severity="error", layer="ast",
    contract="Pallas kernel bodies must be bitwise-reproducible: no wall "
             "clocks, no stdlib/numpy RNG, no id()/hash()"))

_PRAGMA = re.compile(r"#\s*analyze:\s*allow\[([a-z0-9-]+(?:,\s*[a-z0-9-]+)*)\]")

# module allowlists are posix paths relative to the repro package root
RAW_DENSE_MODULE_ALLOW: Dict[str, str] = {
    "models/flash.py":
        "flash-attention reference path: activation-activation QK/PV "
        "contractions outside the engine's weight-GEMM contract "
        "(ROADMAP: fold attention into the engine)",
    "models/attention.py":
        "attention scores/context einsums are activation-activation "
        "contractions the engine does not plan yet (ROADMAP open item)",
    "models/ssm.py":
        "selective-scan state updates are activation-activation einsums "
        "outside the engine's weight-GEMM contract (ROADMAP open item)",
    "models/moe.py":
        "router dispatch/combine einsums contract activations against "
        "activations (ROADMAP open item)",
}
# raw dense math is the *job* of these subtrees
RAW_DENSE_TREE_ALLOW: Tuple[str, ...] = ("kernels", "core", "engine",
                                         "launch", "analyze", "configs")

_DENSE_NP_ROOTS = {"jnp", "np", "numpy"}
_DENSE_NP_ATTRS = {"einsum", "dot", "matmul", "tensordot", "vdot", "inner"}
_DENSE_LAX_ATTRS = ("conv", "dot_general", "dot")
_MUTATORS = {"append", "extend", "insert", "pop", "popitem", "remove",
             "discard", "clear", "update", "setdefault", "add"}
_NONDET_ROOTS = {"random", "secrets", "uuid"}
_NONDET_TIME = {"time", "time_ns", "monotonic", "monotonic_ns",
                "perf_counter", "perf_counter_ns", "clock_gettime"}
_NONDET_BARE = {"id", "hash"}


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _allowed(line: str, rule_id: str) -> bool:
    m = _PRAGMA.search(line)
    if not m:
        return False
    return rule_id in {r.strip() for r in m.group(1).split(",")}


class _FileLinter:
    def __init__(self, path: Path, rel: str, tree: ast.Module,
                 lines: Sequence[str]) -> None:
        self.rel = rel                  # posix path relative to repro/
        self.site_base = f"src/repro/{rel}"
        self.tree = tree
        self.lines = lines
        self.out: List[Diagnostic] = []

    def emit(self, rule_id: str, node: ast.AST, message: str,
             fix: str = "") -> None:
        lineno = getattr(node, "lineno", 1)
        line = self.lines[lineno - 1] if lineno <= len(self.lines) else ""
        if _allowed(line, rule_id):
            return
        self.out.append(finding(rule_id, f"{self.site_base}:{lineno}",
                                message, fix=fix))

    # -- raw-dense-bypass ---------------------------------------------------

    def check_raw_dense(self) -> None:
        top = self.rel.split("/", 1)[0]
        if top not in ("models", "serve"):
            return
        if self.rel in RAW_DENSE_MODULE_ALLOW:
            return
        fix = ("route through repro.engine (api.dense/api.conv2d or a "
               "compiled program), or add a documented allowlist entry in "
               "analyze.rules_ast.RAW_DENSE_MODULE_ALLOW")
        for node in ast.walk(self.tree):
            if isinstance(node, ast.BinOp) and isinstance(node.op,
                                                          ast.MatMult):
                self.emit("raw-dense-bypass", node,
                          "raw '@' matmul bypasses the engine", fix)
            elif isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name is None:
                    continue
                parts = name.split(".")
                root, attr = parts[0], parts[-1]
                if root in _DENSE_NP_ROOTS and attr in _DENSE_NP_ATTRS:
                    self.emit("raw-dense-bypass", node,
                              f"raw {name}(...) bypasses the engine", fix)
                elif "lax" in parts[:-1] or root == "lax":
                    if attr.startswith(_DENSE_LAX_ATTRS[0]) \
                            or attr in _DENSE_LAX_ATTRS[1:]:
                        self.emit("raw-dense-bypass", node,
                                  f"raw {name}(...) bypasses the engine",
                                  fix)

    # -- mutable-global -----------------------------------------------------

    def check_mutable_global(self) -> None:
        module_binds: Dict[str, Tuple[ast.AST, bool]] = {}
        for stmt in self.tree.body:
            target: Optional[ast.expr] = None
            value: Optional[ast.expr] = None
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                target, value = stmt.targets[0], stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                target, value = stmt.target, stmt.value
            if not isinstance(target, ast.Name):
                continue
            mutable_lit = isinstance(value, (ast.List, ast.Dict, ast.Set)) \
                or (isinstance(value, ast.Call)
                    and isinstance(value.func, ast.Name)
                    and value.func.id in ("list", "dict", "set"))
            module_binds[target.id] = (stmt, mutable_lit)
        if not module_binds:
            return

        rebound: Set[str] = set()
        mutated: Set[str] = set()
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    rebound.update(node.names)
                elif isinstance(node, (ast.Assign, ast.AugAssign,
                                       ast.Delete)):
                    targets = (node.targets
                               if isinstance(node, (ast.Assign, ast.Delete))
                               else [node.target])
                    for t in targets:
                        if isinstance(t, ast.Subscript) \
                                and isinstance(t.value, ast.Name):
                            mutated.add(t.value.id)
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.attr in _MUTATORS:
                    mutated.add(node.func.value.id)

        for name, (stmt, mutable_lit) in module_binds.items():
            if name in rebound:
                how = "rebound via `global`"
            elif mutable_lit and name in mutated:
                how = "a mutable container mutated from function scope"
            else:
                continue
            self.emit(
                "mutable-global", stmt,
                f"module-level binding {name!r} is {how} — mutable "
                "process-global state outside the thread-local stacks",
                fix="move the state onto a thread-local stack (see "
                    "engine.config._TLS), or mark a sanctioned registry "
                    "slot with `# analyze: allow[mutable-global]`")

    # -- fault-hook-unguarded -----------------------------------------------

    @staticmethod
    def _is_active_call(node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        name = _dotted(node.func)
        return name is not None and name.split(".")[-1] == "active" \
            and ("faults" in name or name == "active")

    def check_fault_hooks(self) -> None:
        fix = ("bind `inj = faults.active()` and test `inj is not None` "
               "before touching it — the hook is an Optional")
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Attribute) \
                    and self._is_active_call(node.value):
                self.emit("fault-hook-unguarded", node,
                          f"faults.active().{node.attr} chains off the "
                          "Optional hook without a None check", fix)

        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            locals_from_active: Set[str] = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and self._is_active_call(node.value):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            locals_from_active.add(t.id)
            if not locals_from_active:
                continue
            guard_pos: Dict[str, Tuple[int, int]] = {}
            for node in ast.walk(fn):
                if isinstance(node, ast.Compare) \
                        and isinstance(node.left, ast.Name) \
                        and node.left.id in locals_from_active \
                        and any(isinstance(c, (ast.Constant,))
                                and c.value is None
                                for c in node.comparators):
                    pos = (node.lineno, node.col_offset)
                    cur = guard_pos.get(node.left.id)
                    if cur is None or pos < cur:
                        guard_pos[node.left.id] = pos
                elif isinstance(node, ast.If) \
                        and isinstance(node.test, ast.Name) \
                        and node.test.id in locals_from_active:
                    pos = (node.lineno, node.col_offset)
                    cur = guard_pos.get(node.test.id)
                    if cur is None or pos < cur:
                        guard_pos[node.test.id] = pos
            for node in ast.walk(fn):
                if isinstance(node, ast.Attribute) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id in locals_from_active:
                    pos = (node.lineno, node.col_offset)
                    guard = guard_pos.get(node.value.id)
                    if guard is None or pos < guard:
                        self.emit(
                            "fault-hook-unguarded", node,
                            f"{node.value.id}.{node.attr} used before any "
                            f"None check of {node.value.id!r} (assigned "
                            "from faults.active())", fix)

    # -- kernel-nondeterminism ----------------------------------------------

    def _kernel_names(self) -> Set[str]:
        names: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name.endswith("_kernel"):
                names.add(node.name)
            elif isinstance(node, ast.Call):
                fname = _dotted(node.func)
                if fname is None or fname.split(".")[-1] != "pallas_call" \
                        or not node.args:
                    continue
                body = node.args[0]
                if isinstance(body, ast.Call):        # functools.partial(f,…)
                    pf = _dotted(body.func)
                    if pf is not None and pf.split(".")[-1] == "partial" \
                            and body.args \
                            and isinstance(body.args[0], ast.Name):
                        names.add(body.args[0].id)
                elif isinstance(body, ast.Name):
                    names.add(body.id)
        return names

    def check_kernel_determinism(self) -> None:
        kernels = self._kernel_names()
        if not kernels:
            return
        for fn in ast.walk(self.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    or fn.name not in kernels:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = _dotted(node.func)
                if name is None:
                    continue
                parts = name.split(".")
                root, attr = parts[0], parts[-1]
                nondet = (
                    (root in _NONDET_ROOTS and root != "jax")
                    or (root == "time" and attr in _NONDET_TIME)
                    or (root in ("np", "numpy") and len(parts) >= 2
                        and parts[1] == "random")
                    or name == "os.urandom"
                    or (len(parts) == 1 and root in _NONDET_BARE))
                if nondet:
                    self.emit(
                        "kernel-nondeterminism", node,
                        f"{name}(...) inside Pallas kernel body "
                        f"{fn.name!r} breaks bitwise reproducibility",
                        fix="kernels must be pure functions of their refs; "
                            "derive randomness from an explicit key "
                            "outside the kernel if needed")

    def run(self) -> List[Diagnostic]:
        self.check_raw_dense()
        self.check_mutable_global()
        self.check_fault_hooks()
        self.check_kernel_determinism()
        self.out.sort(key=lambda d: d.site)
        return self.out


def lint_file(path: Path, pkg_root: Path) -> List[Diagnostic]:
    """All layer-2 findings for one source file under the repro package."""
    rel = path.resolve().relative_to(pkg_root.resolve()).as_posix()
    text = path.read_text()
    try:
        tree = ast.parse(text, filename=str(path))
    except SyntaxError as e:
        return [finding("program-capture-failed", f"src/repro/{rel}",
                        f"file does not parse: {e}")]
    return _FileLinter(path, rel, tree, text.splitlines()).run()


def default_root() -> Path:
    return Path(__file__).resolve().parents[1]


def lint_tree(root: Optional[Path] = None) -> Report:
    """Lint every .py under `root` (default: the installed repro package)."""
    root = default_root() if root is None else root
    report = Report()
    for path in sorted(root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        report.extend(lint_file(path, root))
    return report
