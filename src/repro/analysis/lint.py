"""AST-based project linter — every rule encodes a bug this repo has
actually shipped (DESIGN.md §15 keeps the catalog with the history).

The repro's correctness claims ("bitwise-identical across dataflows,
order-invariant plans, deterministic benches") are properties of the
*source*, not just the tests: a stray ``time.time()`` in a serving path
(the PR 9 ``serve_stream`` bug), an unseeded ``random.uniform`` in a
retry loop (``launch/fault.py`` pre-PR 10), or an ``np.asarray`` inside
a jit-reachable function (the PR 6 host-sync class) each re-introduce a
defect class that a test only catches after the fact. This module checks
them on every push, before any test runs.

Rules are registry entries (the same latest-wins pattern as
``models/backend.py``): add one with :func:`register_rule` and it is
picked up by :func:`lint_source` / :func:`lint_paths` and the
``tools/check_static.py`` front door with no further wiring.

Per-site opt-out is an inline comment naming the rule::

    t0 = time.monotonic()   # lint: allow-wall-clock — compile-time harness

(a comment-only line immediately above the site works too). The
allowlist is deliberate friction: the comment documents *why* the site
is exempt, at the site.

>>> findings = lint_source("import time\\n"
...                        "def service(req):\\n"
...                        "    return time.time()\\n")
>>> [(f.rule, f.line) for f in findings]
[('wall-clock', 3)]
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
import re
from typing import Callable, Iterable, Iterator

__all__ = [
    "Finding",
    "LintRule",
    "Module",
    "RULES",
    "lint_paths",
    "lint_source",
    "register_rule",
]


# ---------------------------------------------------------------------------
# findings + rule registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint hit. ``key`` identifies the finding class for the baseline
    file WITHOUT the line number, so grandfathered findings survive
    unrelated edits moving them around the file."""

    rule: str
    path: str
    line: int
    col: int
    message: str
    snippet: str = ""

    @property
    def key(self) -> str:
        return f"{self.path}::{self.rule}::{self.snippet}"

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] " \
               f"{self.message}"


@dataclasses.dataclass(frozen=True)
class LintRule:
    """A registered rule: ``fn(module) -> iterable[Finding]`` plus the
    historical bug it encodes (shown in reports and DESIGN.md §15)."""

    name: str
    history: str
    fn: Callable[["Module"], Iterable[Finding]]


#: rule name -> :class:`LintRule`; latest registration wins (same
#: shadowing contract as the backend registry).
RULES: dict[str, LintRule] = {}


def register_rule(name: str, *, history: str = "") -> Callable:
    """Decorator: register ``fn(module) -> iterable[Finding]`` under
    ``name``. Sites opt out with ``# lint: allow-<name>``."""
    def deco(fn: Callable) -> Callable:
        RULES[name] = LintRule(name, history, fn)
        return fn
    return deco


# ---------------------------------------------------------------------------
# the parsed module
# ---------------------------------------------------------------------------

_ALLOW_RE = re.compile(r"#\s*lint:\s*((?:allow-[\w-]+[,\s]*)+)")
_ALLOW_TOKEN = re.compile(r"allow-([\w-]+)")
_COMMENT_ONLY = re.compile(r"^\s*#")


class Module:
    """One source file, parsed once and shared by every rule: the AST, an
    import alias table (local name -> dotted module path, so ``np.random``
    and ``numpy.random`` resolve identically), and the inline-allowlist
    line map."""

    def __init__(self, source: str, path: str = "<string>"):
        self.source = source
        self.path = path
        self.tree = ast.parse(source, filename=path)
        self.lines = source.splitlines()
        self.aliases = self._alias_table(self.tree)
        self._allows = self._allow_map(self.lines)

    # -- imports ------------------------------------------------------------

    @staticmethod
    def _alias_table(tree: ast.AST) -> dict[str, str]:
        table: dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    table[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                    and node.module:
                for a in node.names:
                    table[a.asname or a.name] = f"{node.module}.{a.name}"
        return table

    def resolve(self, node: ast.AST) -> str | None:
        """Dotted path of a Name/Attribute chain through the alias table
        (``np.random.uniform`` -> ``numpy.random.uniform``), or None when
        the base name was never imported (locals never match module
        rules)."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        base = self.aliases.get(node.id)
        if base is None:
            return None
        parts.append(base)
        return ".".join(reversed(parts))

    # -- allowlist ----------------------------------------------------------

    @staticmethod
    def _allow_map(lines: list[str]) -> dict[int, set[str]]:
        allows: dict[int, set[str]] = {}
        for i, text in enumerate(lines, start=1):
            m = _ALLOW_RE.search(text)
            if not m:
                continue
            rules = set(_ALLOW_TOKEN.findall(m.group(1)))
            allows.setdefault(i, set()).update(rules)
            if _COMMENT_ONLY.match(text):     # standalone comment: next line
                allows.setdefault(i + 1, set()).update(rules)
        return allows

    def allowed(self, line: int, rule: str) -> bool:
        return rule in self._allows.get(line, ())

    # -- finding constructor ------------------------------------------------

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 0)
        snippet = (self.lines[line - 1].strip()
                   if 0 < line <= len(self.lines) else "")
        return Finding(rule=rule, path=self.path, line=line,
                       col=getattr(node, "col_offset", 0) + 1,
                       message=message, snippet=snippet)

    def calls(self) -> Iterator[ast.Call]:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                yield node


# ---------------------------------------------------------------------------
# rule: wall-clock reads outside clock-injectable code (PR 9 bug class)
# ---------------------------------------------------------------------------

_WALL_CLOCK = {
    "time.time", "time.time_ns", "time.perf_counter",
    "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}


@register_rule(
    "wall-clock",
    history="PR 9: serve_stream measured service time on the wall clock, "
            "making p50/p99 (and the CI bench gate) nondeterministic; the "
            "fix was an injectable clock (VirtualClock). Wall-clock reads "
            "belong behind a clock= seam, or behind an explicit "
            "'# lint: allow-wall-clock' stating why not.")
def rule_wall_clock(mod: Module) -> Iterator[Finding]:
    for call in mod.calls():
        dotted = mod.resolve(call.func)
        if dotted in _WALL_CLOCK and not mod.allowed(call.lineno,
                                                     "wall-clock"):
            yield mod.finding(
                "wall-clock", call,
                f"{dotted}() is a wall-clock read; take an injectable "
                f"clock (see launch.serve.VirtualClock) or annotate the "
                f"site with '# lint: allow-wall-clock'")


# ---------------------------------------------------------------------------
# rule: unseeded / module-global randomness (launch/fault.py bug class)
# ---------------------------------------------------------------------------

#: numpy.random names that ARE the modern seeded API (everything else on
#: the module is the hidden-global-state legacy surface)
_NP_RANDOM_OK = {"default_rng", "Generator", "SeedSequence", "BitGenerator",
                 "PCG64", "PCG64DXSM", "Philox", "MT19937", "SFC64"}
#: stdlib random names that construct an (injectable, seedable) instance
#: instead of mutating the module-global state
_PY_RANDOM_OK = {"Random", "SystemRandom", "getstate", "setstate"}


@register_rule(
    "unseeded-random",
    history="launch/fault.py:89 pre-PR 10: retry's backoff jitter drew "
            "from the module-global random.uniform — unseedable, so any "
            "code path through retry was nondeterministic. Deterministic "
            "tiers require an injected numpy Generator "
            "(np.random.default_rng(seed)) or an explicit jax PRNG key.")
def rule_unseeded_random(mod: Module) -> Iterator[Finding]:
    for call in mod.calls():
        dotted = mod.resolve(call.func)
        if dotted is None or mod.allowed(call.lineno, "unseeded-random"):
            continue
        parts = dotted.split(".")
        if parts[0] == "random" and len(parts) == 2 \
                and parts[1] not in _PY_RANDOM_OK:
            yield mod.finding(
                "unseeded-random", call,
                f"{dotted}() uses the module-global stdlib RNG; inject a "
                f"seeded generator (np.random.default_rng(seed) / "
                f"random.Random(seed)) instead")
        elif parts[:2] == ["numpy", "random"] and len(parts) == 3 \
                and parts[2] not in _NP_RANDOM_OK:
            yield mod.finding(
                "unseeded-random", call,
                f"{dotted}() is the legacy global-state numpy RNG; use "
                f"np.random.default_rng(seed) or an injected Generator")


# ---------------------------------------------------------------------------
# rule: host sync inside functions reachable from jitted entry points
# (PR 6 bug class)
# ---------------------------------------------------------------------------

_HOST_SYNC_FNS = {"numpy.asarray", "numpy.array", "numpy.asanyarray",
                  "numpy.ascontiguousarray", "jax.device_get"}
_HOST_SYNC_METHODS = {"item", "tolist"}
_JIT_WRAPPERS = {"jax.jit", "jax.vmap", "jax.pmap", "jax.make_jaxpr"}


def _defs_by_name(tree: ast.AST) -> dict[str, list[ast.AST]]:
    defs: dict[str, list[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs


def _callable_name(node: ast.AST, mod: Module) -> str | None:
    """The simple name a callable expression refers to: ``f`` for ``f`` /
    ``self.f`` / ``cls.f`` / ``obj.f``, unwrapping ``functools.partial``."""
    if isinstance(node, ast.Call):                 # partial(f, ...)
        dotted = mod.resolve(node.func)
        if dotted in ("functools.partial", "partial") and node.args:
            return _callable_name(node.args[0], mod)
        return None
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _jit_roots(mod: Module, defs: dict[str, list[ast.AST]]) -> set[str]:
    """Function names handed to jax.jit/vmap/pmap/make_jaxpr anywhere in
    the module (call sites, assignments, decorators) — the trace entry
    points host-sync reachability starts from."""
    roots: set[str] = set()
    for call in mod.calls():
        if mod.resolve(call.func) in _JIT_WRAPPERS and call.args:
            arg = call.args[0]
            name = _callable_name(arg, mod)
            if name is not None:
                roots.add(name)
            elif isinstance(arg, ast.Lambda):
                # jax.vmap(lambda ...: local_fn(...)) roots local_fn
                for c in ast.walk(arg.body):
                    if isinstance(c, ast.Call):
                        n = _callable_name(c.func, mod)
                        if n is not None and n in defs:
                            roots.add(n)
    for name, nodes in defs.items():
        for node in nodes:
            for deco in node.decorator_list:
                d = mod.resolve(deco.func if isinstance(deco, ast.Call)
                                else deco)
                if d in _JIT_WRAPPERS:
                    roots.add(name)
                elif isinstance(deco, ast.Call) \
                        and mod.resolve(deco.func) in ("functools.partial",
                                                       "partial") \
                        and deco.args \
                        and mod.resolve(deco.args[0]) in _JIT_WRAPPERS:
                    roots.add(name)
    return roots & set(defs)


def _reachable(defs: dict[str, list[ast.AST]], roots: set[str]) -> set[str]:
    """Name-level BFS over the intra-module call graph: ``f()`` and
    ``self.f()`` / ``cls.f()`` edges (method resolution is approximated by
    simple name — conservative: over-reaches, never under-reaches)."""
    seen = set()
    frontier = list(roots)
    while frontier:
        name = frontier.pop()
        if name in seen:
            continue
        seen.add(name)
        for node in defs.get(name, ()):
            for c in ast.walk(node):
                if not isinstance(c, ast.Call):
                    continue
                callee = None
                if isinstance(c.func, ast.Name):
                    callee = c.func.id
                elif isinstance(c.func, ast.Attribute) and \
                        isinstance(c.func.value, ast.Name) and \
                        c.func.value.id in ("self", "cls"):
                    callee = c.func.attr
                if callee in defs and callee not in seen:
                    frontier.append(callee)
    return seen


@register_rule(
    "host-sync",
    history="PR 6 bug class: batched_forward pulled geometry through "
            "np.asarray per cloud, forcing a device->host sync inside what "
            "should have been one jittable pipeline (and breaking jit "
            "outright). Functions reachable from a jax.jit/vmap root must "
            "not host-sync; tracer-guarded telemetry sites annotate "
            "themselves with '# lint: allow-host-sync'.")
def rule_host_sync(mod: Module) -> Iterator[Finding]:
    defs = _defs_by_name(mod.tree)
    roots = _jit_roots(mod, defs)
    if not roots:
        return
    reach = _reachable(defs, roots)
    seen_nodes: set[int] = set()
    for name in sorted(reach):
        for fn_node in defs[name]:
            for c in ast.walk(fn_node):
                if not isinstance(c, ast.Call) or id(c) in seen_nodes:
                    continue
                seen_nodes.add(id(c))
                if mod.allowed(c.lineno, "host-sync"):
                    continue
                dotted = mod.resolve(c.func)
                if dotted in _HOST_SYNC_FNS:
                    yield mod.finding(
                        "host-sync", c,
                        f"{dotted}() in '{name}' (reachable from a jitted "
                        f"entry point) forces a device->host sync in a "
                        f"traced path")
                elif isinstance(c.func, ast.Attribute) \
                        and c.func.attr in _HOST_SYNC_METHODS \
                        and not c.args and not c.keywords:
                    yield mod.finding(
                        "host-sync", c,
                        f".{c.func.attr}() in '{name}' (reachable from a "
                        f"jitted entry point) forces a device->host sync "
                        f"in a traced path")


# ---------------------------------------------------------------------------
# rule: a pallas_call whose interpret flag is not the derived one
# ---------------------------------------------------------------------------

@register_rule(
    "interpret-pinned",
    history="Every kernel wrapper once defaulted to interpret=True and "
            "threaded the flag, so on a TPU the kernels would have run in "
            "the Pallas interpreter. "
            "The flag now has one source, "
            "repro.kernels.platform.interpret_mode(), derived from the "
            "platform; a pallas_call that pins it, threads it from a "
            "parameter or omits it can run the interpreter on the chip.")
def rule_interpret_pinned(mod: Module) -> Iterator[Finding]:
    for call in mod.calls():
        dotted = mod.resolve(call.func)
        if dotted is None or not dotted.endswith("pallas_call") \
                or mod.allowed(call.lineno, "interpret-pinned"):
            continue
        flag = next((kw.value for kw in call.keywords
                     if kw.arg == "interpret"), None)
        fn = flag.func if isinstance(flag, ast.Call) else None
        derived = (fn is not None and not flag.args and not flag.keywords
                   and getattr(fn, "id", getattr(fn, "attr", None))
                   == "interpret_mode")
        if not derived:
            yield mod.finding(
                "interpret-pinned", call if flag is None else flag,
                "pallas_call site does not pass interpret=interpret_mode() "
                "(repro.kernels.platform): the flag must come from the "
                "platform, so a TPU never runs the interpreter")


# ---------------------------------------------------------------------------
# rule: bare except
# ---------------------------------------------------------------------------

@register_rule(
    "bare-except",
    history="A bare 'except:' swallows KeyboardInterrupt/SystemExit — in "
            "the serving loop that turns a Ctrl-C into a hung engine, and "
            "in retry wrappers it hides the very fault class being "
            "retried. Catch the narrowest exception that is actually "
            "expected.")
def rule_bare_except(mod: Module) -> Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None \
                and not mod.allowed(node.lineno, "bare-except"):
            yield mod.finding(
                "bare-except", node,
                "bare 'except:' catches KeyboardInterrupt/SystemExit; "
                "name the exception classes this site expects")


# ---------------------------------------------------------------------------
# rule: mutable dataclass registered as a jax pytree
# ---------------------------------------------------------------------------

def _dataclass_frozen(deco: ast.AST, mod: Module) -> bool | None:
    """None when ``deco`` is not a dataclass decorator; else frozen-ness."""
    if isinstance(deco, ast.Call):
        if mod.resolve(deco.func) in ("dataclasses.dataclass", "dataclass"):
            for kw in deco.keywords:
                if kw.arg == "frozen" and isinstance(kw.value, ast.Constant):
                    return bool(kw.value.value)
            return False
        return None
    if mod.resolve(deco) in ("dataclasses.dataclass", "dataclass"):
        return False
    return None


_PYTREE_REG = ("jax.tree_util.register_pytree_node_class",
               "register_pytree_node_class")


@register_rule(
    "mutable-pytree",
    history="CrossbarProgram/DevicePlan are frozen for a reason: a pytree "
            "dataclass that mutates after being closed over by a jit trace "
            "desynchronizes the trace cache from the object — the compiled "
            "function keeps computing with the OLD leaves. Pytree "
            "dataclasses must be frozen=True.")
def rule_mutable_pytree(mod: Module) -> Iterator[Finding]:
    classes: dict[str, ast.ClassDef] = {
        n.name: n for n in ast.walk(mod.tree) if isinstance(n, ast.ClassDef)}
    registered: set[str] = set()
    for cls in classes.values():
        for deco in cls.decorator_list:
            if mod.resolve(deco) in _PYTREE_REG:
                registered.add(cls.name)
    for call in mod.calls():                      # register_...(ClassName)
        if mod.resolve(call.func) in _PYTREE_REG and call.args \
                and isinstance(call.args[0], ast.Name):
            registered.add(call.args[0].id)
    for name in sorted(registered):
        cls = classes.get(name)
        if cls is None:
            continue
        frozen = [f for f in (_dataclass_frozen(d, mod)
                              for d in cls.decorator_list) if f is not None]
        if frozen and not frozen[0] \
                and not mod.allowed(cls.lineno, "mutable-pytree"):
            yield mod.finding(
                "mutable-pytree", cls,
                f"dataclass '{name}' is registered as a jax pytree but is "
                f"not frozen=True; mutation after tracing desynchronizes "
                f"jit caches from the object")


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def lint_source(source: str, path: str = "<string>",
                rules: Iterable[str] | None = None) -> list[Finding]:
    """Run the selected ``rules`` (default: all registered) over one
    source string. Returns findings sorted by (line, col, rule)."""
    mod = Module(source, path)
    selected = list(RULES) if rules is None else list(rules)
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise ValueError(f"unknown lint rule(s) {unknown}; registered: "
                         f"{sorted(RULES)}")
    out: list[Finding] = []
    for name in selected:
        out.extend(RULES[name].fn(mod))
    return sorted(out, key=lambda f: (f.line, f.col, f.rule))


def lint_paths(paths: Iterable[str | pathlib.Path],
               rules: Iterable[str] | None = None,
               root: str | pathlib.Path | None = None) -> list[Finding]:
    """Lint ``.py`` files (directories recurse). Finding paths are
    reported relative to ``root`` (default: cwd) so baseline keys are
    machine-independent. Syntax errors surface as findings under the
    pseudo-rule ``parse-error`` instead of aborting the run."""
    root = pathlib.Path(root) if root is not None else pathlib.Path.cwd()
    files: list[pathlib.Path] = []
    for p in paths:
        p = pathlib.Path(p)
        files.extend(sorted(p.rglob("*.py")) if p.is_dir() else [p])
    out: list[Finding] = []
    for f in files:
        try:
            rel = str(f.resolve().relative_to(root.resolve()))
        except ValueError:
            rel = str(f)
        try:
            out.extend(dataclasses.replace(x, path=rel)
                       for x in lint_source(f.read_text(), rel, rules))
        except SyntaxError as e:
            out.append(Finding(rule="parse-error", path=rel,
                               line=e.lineno or 0, col=e.offset or 0,
                               message=f"could not parse: {e.msg}"))
    return out
