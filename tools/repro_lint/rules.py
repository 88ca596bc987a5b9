"""RL001–RL006: the repo's determinism / dtype / accounting invariants.

Each rule's ``rationale`` is the short form of the catalog entry in
``docs/static_analysis.md``; each has a pass/fail fixture pair under
``tools/repro_lint/fixtures/`` exercised by ``tests/test_lint.py``.
"""

from __future__ import annotations

import ast
import os

from tools.repro_lint.engine import SourceRule, TreeRule, Violation

# Modules whose code lands inside jaxprs (jit/shard_map bodies live
# here).  launch/ (host-side serving loops, wall-clock timers), ckpt/
# (host I/O) and configs/ are deliberately out of scope for the
# dtype/host-purity rules.
JITTED_DIRS = ("core", "kernels", "weak_tree", "models", "optim", "data")


def in_jitted_module(relpath: str) -> bool:
    p = relpath.replace(os.sep, "/")
    return any(f"repro/{d}/" in p for d in JITTED_DIRS)


def _dotted(node: ast.AST) -> str | None:
    """'jnp', 'jax.lax', 'np.random' … for Name/Attribute chains."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _QualnameVisitor(ast.NodeVisitor):
    """Tracks the enclosing function/class qualname while walking."""

    def __init__(self):
        self.stack: list[str] = []

    @property
    def qualname(self) -> str:
        return ".".join(self.stack)

    def visit_FunctionDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()


# ---------------------------------------------------------------------------
# RL001 — no bare extrema / top_k tie-breaking
# ---------------------------------------------------------------------------

# (path suffix, qualname substring, callee attr, reason)
ALLOWLIST: list[tuple[str, str, str, str]] = [
    ("weak_tree/trees.py", "erm_players", "top_k",
     "operates on ranks votes*F + (F-1-f): all values distinct by "
     "construction, so top_k tie order cannot matter"),
]

_EXTREMA = {"argmin", "argmax", "top_k"}


class NoBareExtrema(SourceRule):
    rule_id = "RL001"
    title = "no bare argmin/argmax/top_k outside pinned sites"
    rationale = (
        "XLA makes no cross-backend promise about which index argmin/"
        "argmax/top_k return on ties; the repo's bit-parity law requires "
        "the lowest index.  Use repro.core.pinned (min/where/iota) or an "
        "ALLOWLIST entry arguing the operands are tie-free."
    )

    def check(self, tree, src, relpath):
        out: list[Violation] = []
        rule = self

        class V(_QualnameVisitor):
            def visit_Call(self, node):
                name = None
                recv = None
                if isinstance(node.func, ast.Attribute):
                    name = node.func.attr
                    recv = _dotted(node.func.value)
                elif isinstance(node.func, ast.Name):
                    name = node.func.id
                if name in _EXTREMA and recv not in ("np", "numpy", "math"):
                    if not self._allowed(name):
                        out.append(rule.violation(
                            relpath, node,
                            f"bare `{name}` (tie order is backend-defined); "
                            f"use repro.core.pinned or add an ALLOWLIST "
                            f"entry [in {self.qualname or '<module>'}]"))
                if (name == "argsort"
                        and any(kw.arg == "stable"
                                and isinstance(kw.value, ast.Constant)
                                and kw.value.value is False
                                for kw in node.keywords)):
                    out.append(rule.violation(
                        relpath, node, "argsort(stable=False) is "
                        "nondeterministic on ties"))
                self.generic_visit(node)

            def _allowed(self, name):
                q = self.qualname
                return any(relpath.endswith(sfx) and part in q and name == cn
                           for sfx, part, cn, _ in ALLOWLIST)

        V().visit(tree)
        return out


# ---------------------------------------------------------------------------
# RL002 — collectives paired with wire accounting (sharded engine)
# ---------------------------------------------------------------------------

_COLLECTIVES = {"all_gather", "psum", "pmean", "pmax", "pmin",
                "ppermute", "all_to_all", "psum_scatter"}
_WIRE_NAME = __import__("re").compile(
    r"^(n_(examples|scalars|bytes|hist|votes)"
    r"|a?wire_[a-z0-9_]+|hist_wire_[a-z0-9_]+)$")


def _wire_bindings(node: ast.AST) -> set[str]:
    """Names bound in wire-counter positions anywhere under ``node``:
    assignment targets, call keywords, dict-literal string keys."""
    found: set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = n.targets if isinstance(n, ast.Assign) else [n.target]
            for t in targets:
                if isinstance(t, ast.Name) and _WIRE_NAME.match(t.id):
                    found.add(t.id)
        elif isinstance(n, ast.keyword) and n.arg and _WIRE_NAME.match(n.arg):
            found.add(n.arg)
        elif isinstance(n, ast.Dict):
            for k in n.keys:
                if (isinstance(k, ast.Constant) and isinstance(k.value, str)
                        and _WIRE_NAME.match(k.value)):
                    found.add(k.value)
    return found


def _references_name(node: ast.AST, name: str) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id == name:
            return True
        if isinstance(n, ast.Attribute) and n.attr == name:
            return True
        if (isinstance(n, ast.Constant) and n.value == name):
            return True
    return False


def _accumulates(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Add):
            return True
        if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                and n.func.attr in ("set", "add")):
            return True
    return False


class LedgerPairing(SourceRule):
    rule_id = "RL002"
    title = "every collective in the sharded engine pairs with wire counters"
    rationale = (
        "core/sharded_batched.py is the engine whose traffic "
        "validate_ledger audits; a collective without a measured "
        "wire-counter update in the same function ships unaccounted "
        "bits.  Additionally every wire field the module's own schema "
        "declares (_RoundCarry wire_* fields, STATE_DTYPES wire keys) "
        "must have a maintaining accumulation somewhere in the module — "
        "deleting a counter update is a lint failure, not silent drift."
    )

    def applies_to(self, relpath):
        return relpath.replace(os.sep, "/").endswith(
            "core/sharded_batched.py")

    def check(self, tree, src, relpath):
        out: list[Violation] = []

        # -- pass 1: per-function collective/counter pairing ---------------
        for node in tree.body:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            colls = [
                n for n in ast.walk(node)
                if isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr in _COLLECTIVES
            ]
            if colls and not _wire_bindings(node):
                out.append(self.violation(
                    relpath, colls[0],
                    f"`{node.name}` calls "
                    f"{sorted({c.func.attr for c in colls})} but binds no "
                    f"wire counter (n_*/wire_*/awire_*/hist_wire_*)"))

        # -- pass 2: schema census vs maintaining accumulations ------------
        schema: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                for stmt in node.body:
                    if (isinstance(stmt, ast.AnnAssign)
                            and isinstance(stmt.target, ast.Name)
                            and _WIRE_NAME.match(stmt.target.id)):
                        schema.add(stmt.target.id)
            elif isinstance(node, ast.Assign):
                if any(isinstance(t, ast.Name) and t.id == "STATE_DTYPES"
                       for t in node.targets):
                    v = node.value
                    if isinstance(v, ast.Call):
                        for kw in v.keywords:
                            if kw.arg and _WIRE_NAME.match(kw.arg):
                                schema.add(kw.arg)
                    elif isinstance(v, ast.Dict):
                        for k in v.keys:
                            if (isinstance(k, ast.Constant)
                                    and isinstance(k.value, str)
                                    and _WIRE_NAME.match(k.value)):
                                schema.add(k.value)
        if not schema:
            out.append(Violation(
                self.rule_id, relpath, 1,
                "wire-schema introspection found no wire_* fields in "
                "_RoundCarry / STATE_DTYPES — the rule cannot audit this "
                "module (did the schema move?)"))
            return out

        maintained: set[str] = set()
        for n in ast.walk(tree):
            pairs: list[tuple[str, ast.AST]] = []
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = (n.targets if isinstance(n, ast.Assign)
                           else [n.target])
                for t in targets:
                    if isinstance(t, ast.Name):
                        pairs.append((t.id, n.value))
            elif isinstance(n, ast.keyword) and n.arg:
                pairs.append((n.arg, n.value))
            elif isinstance(n, ast.Dict):
                for k, v in zip(n.keys, n.values):
                    if (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)):
                        pairs.append((k.value, v))
            for name, value in pairs:
                if (name in schema and _references_name(value, name)
                        and _accumulates(value)):
                    maintained.add(name)

        for name in sorted(schema - maintained):
            out.append(Violation(
                self.rule_id, relpath, 1,
                f"wire field `{name}` is declared in the module schema "
                f"but has no maintaining accumulation (an assignment/"
                f"keyword/dict entry that reads `{name}` and adds to it) "
                f"— its counter update was deleted or never written"))
        return out


# ---------------------------------------------------------------------------
# RL003 — dtype discipline in jitted modules
# ---------------------------------------------------------------------------

_NEEDS_DTYPE = {
    "zeros": 2, "ones": 2, "empty": 2,   # ok with >=2 positional args
    "full": 3,
    "arange": None, "linspace": None, "eye": None,  # kwarg only
}
_BAD_DTYPE_NAMES = {"float64", "complex64", "complex128", "double"}


class DtypeDiscipline(SourceRule):
    rule_id = "RL003"
    title = "no f64 literals, bare astype, or dtype-less jnp constructors"
    rationale = (
        "STATE_DTYPES is the checkpoint/parity contract; a dtype-less "
        "jnp constructor silently flips to float64 under x64, and "
        ".astype(float) means different widths on different hosts.  "
        "Every jnp array in a jitted module is constructed with an "
        "explicit dtype."
    )

    def applies_to(self, relpath):
        return in_jitted_module(relpath)

    def check(self, tree, src, relpath):
        out: list[Violation] = []
        for node in ast.walk(tree):
            # host-side numpy is allowed f64 (canonicalized at the jnp
            # boundary); only jnp-space f64 reaches traces
            if (isinstance(node, ast.Attribute)
                    and node.attr in _BAD_DTYPE_NAMES
                    and _dotted(node.value) in ("jnp", "jax.numpy")):
                out.append(self.violation(
                    relpath, node, f"float64/complex dtype "
                    f"`jnp.{node.attr}` in a jitted module"))
            elif (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and node.value in _BAD_DTYPE_NAMES):
                out.append(self.violation(
                    relpath, node,
                    f"float64/complex dtype string '{node.value}'"))
            elif isinstance(node, ast.Call):
                out.extend(self._check_call(node, relpath))
        return out

    def _check_call(self, node: ast.Call, relpath):
        out = []
        if isinstance(node.func, ast.Attribute):
            name, recv = node.func.attr, _dotted(node.func.value)
            if (name == "astype" and node.args
                    and isinstance(node.args[0], ast.Name)
                    and node.args[0].id in ("float", "int", "complex")):
                out.append(self.violation(
                    relpath, node,
                    f"bare .astype({node.args[0].id}) — width is "
                    f"host-dependent; name the jnp dtype"))
            if recv in ("jnp", "jax.numpy") and name in _NEEDS_DTYPE:
                has_kw = any(kw.arg == "dtype" for kw in node.keywords)
                min_pos = _NEEDS_DTYPE[name]
                has_pos = (min_pos is not None
                           and len(node.args) >= min_pos)
                if not (has_kw or has_pos):
                    out.append(self.violation(
                        relpath, node,
                        f"jnp.{name}(...) without explicit dtype "
                        f"(flips to f64 under x64)"))
        return out


# ---------------------------------------------------------------------------
# RL004 — kernel directories are complete kernel/ops/ref triples
# ---------------------------------------------------------------------------

class KernelTriple(TreeRule):
    rule_id = "RL004"
    title = "every kernels/<name>/ is a kernel/ops/ref triple with interpret routing"
    rationale = (
        "The kernel contract (docs/static_analysis.md): ref.py is the pure-jnp "
        "ground truth, kernel.py the pallas body, ops.py the public "
        "entry routing an `interpret=` flag so CPU CI exercises the "
        "kernel path.  A missing leg means an untestable kernel."
    )

    REQUIRED = ("kernel.py", "ops.py", "ref.py")

    def check_tree(self, root):
        out: list[Violation] = []
        for dirpath, dirnames, filenames in os.walk(root):
            if os.path.basename(dirpath) != "kernels":
                continue
            for sub in sorted(dirnames):
                if sub == "__pycache__":
                    continue
                kdir = os.path.join(dirpath, sub)
                rel = os.path.relpath(kdir).replace(os.sep, "/")
                missing = [f for f in self.REQUIRED
                           if not os.path.exists(os.path.join(kdir, f))]
                if missing:
                    out.append(Violation(
                        self.rule_id, rel, 0,
                        f"kernel dir missing {missing} — must be a "
                        f"complete kernel/ops/ref triple"))
                    continue
                ops = os.path.join(kdir, "ops.py")
                if not self._routes_interpret(ops):
                    out.append(Violation(
                        self.rule_id, rel + "/ops.py", 0,
                        "no public function takes an `interpret=` "
                        "flag — CPU CI cannot exercise the kernel path"))
        return out

    @staticmethod
    def _routes_interpret(ops_path: str) -> bool:
        with open(ops_path, encoding="utf-8") as f:
            try:
                tree = ast.parse(f.read(), filename=ops_path)
            except SyntaxError:
                return False
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                names = [a.arg for a in
                         args.args + args.kwonlyargs + args.posonlyargs]
                if "interpret" in names:
                    return True
        return False


# ---------------------------------------------------------------------------
# RL005 — host purity in jitted modules
# ---------------------------------------------------------------------------

_LEGACY_NP_RANDOM = {
    "seed", "rand", "randn", "randint", "random", "choice", "shuffle",
    "permutation", "uniform", "normal", "binomial", "poisson", "get_state",
    "set_state", "random_sample", "standard_normal",
}


class HostPurity(SourceRule):
    rule_id = "RL005"
    title = "no sys.path mutation; no time/random in jitted modules"
    rationale = (
        "sys.path mutation makes import resolution order-dependent "
        "(banned repo-wide); `time`/`random` and legacy global-state "
        "`np.random.*` calls in modules that define jitted code bake "
        "host state into traced constants.  Seeded np.random.default_rng "
        "/ Generator / SeedSequence remain allowed."
    )

    def check(self, tree, src, relpath):
        out: list[Violation] = []
        jitted = in_jitted_module(relpath)
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and node.attr == "path" \
                    and _dotted(node.value) == "sys":
                out.append(self.violation(
                    relpath, node, "sys.path mutation/access — import "
                    "resolution must not depend on call order"))
            elif jitted and isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.split(".")[0] in ("time", "random"):
                        out.append(self.violation(
                            relpath, node,
                            f"import {alias.name} in a jitted module — "
                            f"host clock/RNG state must not reach traces"))
            elif jitted and isinstance(node, ast.ImportFrom):
                if node.module and node.module.split(".")[0] in (
                        "time", "random"):
                    out.append(self.violation(
                        relpath, node,
                        f"from {node.module} import … in a jitted module"))
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute):
                recv = _dotted(node.func.value)
                if (recv in ("np.random", "numpy.random")
                        and node.func.attr in _LEGACY_NP_RANDOM):
                    out.append(self.violation(
                        relpath, node,
                        f"legacy global-state np.random.{node.func.attr} "
                        f"— use np.random.default_rng(seed)"))
        return out


# ---------------------------------------------------------------------------
# RL006 — observability is host-side only
# ---------------------------------------------------------------------------

# Last dotted component of callables that put a function argument inside
# a trace: passing `f` by name to any of these makes `f`'s body traced.
_TRANSFORMS = {
    "jit", "vmap", "pmap", "shard_map",
    "while_loop", "scan", "fori_loop", "cond", "switch",
    "checkpoint", "remat",
}

# Decorators that jit the function they sit on (directly or via
# functools.partial(jax.jit, ...)).
_JIT_DECORATORS = {"jit", "pmap", "checkpoint", "remat"}


def _last(dotted: str | None) -> str:
    return (dotted or "").rsplit(".", 1)[-1]


def _is_jit_decorator(d: ast.AST) -> bool:
    if _last(_dotted(d)) in _JIT_DECORATORS:
        return True
    if isinstance(d, ast.Call):
        fl = _last(_dotted(d.func))
        if fl in _JIT_DECORATORS:
            return True
        if fl == "partial" and d.args:
            return _last(_dotted(d.args[0])) in _JIT_DECORATORS
    return False


def _obs_imports(tree: ast.AST) -> tuple[set[str], set[str]]:
    """(module aliases bound to repro.obs[.x], names imported FROM it)."""
    aliases: set[str] = set()
    direct: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "repro.obs" or a.name.startswith("repro.obs."):
                    if a.asname:
                        aliases.add(a.asname)
                    # plain `import repro.obs.trace` binds `repro`; call
                    # sites then spell the full repro.obs.* chain, which
                    # _obs_call matches by prefix.
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "repro":
                for a in node.names:
                    if a.name == "obs":
                        aliases.add(a.asname or "obs")
            elif mod == "repro.obs":
                for a in node.names:
                    aliases.add(a.asname or a.name)
            elif mod.startswith("repro.obs."):
                for a in node.names:
                    direct.add(a.asname or a.name)
    return aliases, direct


def _obs_call(node: ast.Call, aliases: set[str],
              direct: set[str]) -> str | None:
    d = _dotted(node.func)
    if d:
        if d.startswith("repro.obs."):
            return d
        if "." in d and d.split(".", 1)[0] in aliases:
            return d
    if isinstance(node.func, ast.Name) and node.func.id in direct:
        return node.func.id
    return None


class HostSideObservability(SourceRule):
    rule_id = "RL006"
    title = "no span/metric emission inside jitted code"
    rationale = (
        "obs spans/metrics are host-side Python side effects; inside a "
        "traced function they fire once at trace time (then never "
        "again from the compiled program) and their timestamps bound "
        "tracing, not execution — silently wrong numbers.  The rule "
        "takes the traced closure (jit-decorated functions, functions "
        "passed by name to jit/vmap/shard_map/while_loop/scan/…, plus "
        "everything they reference module-locally) and bans repro.obs "
        "calls inside it.  `jax.named_scope` is the device-visible "
        "label that IS allowed in traced code; spans wrap the dispatch "
        "from the host side (see run_rounds)."
    )

    def applies_to(self, relpath):
        return in_jitted_module(relpath)

    def check(self, tree, src, relpath):
        aliases, direct = _obs_imports(tree)
        if not aliases and not direct:
            return []

        funcs: dict[str, list[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.setdefault(node.name, []).append(node)

        roots: set[str] = set()
        for name, defs in funcs.items():
            if any(_is_jit_decorator(d) for fn in defs
                   for d in fn.decorator_list):
                roots.add(name)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and _last(_dotted(node.func)) in _TRANSFORMS):
                for a in node.args:
                    if isinstance(a, ast.Name) and a.id in funcs:
                        roots.add(a.id)

        # conservative transitive closure: any module-local function
        # NAME referenced inside a traced function joins the closure
        # (covers functools.partial(_round_body, …) handed to while_loop)
        closure: set[str] = set()
        todo = sorted(roots)
        while todo:
            name = todo.pop()
            if name in closure:
                continue
            closure.add(name)
            for fn in funcs[name]:
                for n in ast.walk(fn):
                    if (isinstance(n, ast.Name) and n.id in funcs
                            and n.id not in closure):
                        todo.append(n.id)

        out: list[Violation] = []
        seen: set[int] = set()
        for name in sorted(closure):
            for fn in funcs[name]:
                for n in ast.walk(fn):
                    if not isinstance(n, ast.Call):
                        continue
                    label = _obs_call(n, aliases, direct)
                    if label and n.lineno not in seen:
                        seen.add(n.lineno)
                        out.append(self.violation(
                            relpath, n,
                            f"obs call `{label}` inside the traced "
                            f"closure (via `{name}`) — spans/metrics "
                            f"are host-side only; use jax.named_scope "
                            f"for device-visible labels"))
        return out


ALL_RULES = [NoBareExtrema(), LedgerPairing(), DtypeDiscipline(),
             KernelTriple(), HostPurity(), HostSideObservability()]

RULE_IDS = sorted(r.rule_id for r in ALL_RULES)
