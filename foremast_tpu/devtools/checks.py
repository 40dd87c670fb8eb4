"""The five invariant checkers. Each rule is a bug class PRs 1-4 hit by
hand; docs/development.md pairs every rule with its motivating incident.

rule              invariant
----------------  -------------------------------------------------------
lock-discipline   no blocking call lexically inside a ``with <lock>``
                  body; the static held-before graph (lexical nesting +
                  one level of same-class/same-module calls) stays
                  acyclic. Runtime complement: devtools/locktrace.py.
trace-registry    tracing span names, flight-recorder event types, and
                  verdict-provenance path tags come from registered
                  constants (utils/tracing.py SPAN_NAMES,
                  engine/flightrec.py EVENT_*, engine/provenance.py
                  PATH_*) — no inline f-string or unregistered literal
                  names, so the observability vocabulary stays a stable
                  greppable inventory.
knob-registry     every env read outside engine/config.py resolves
                  through utils/knobs.py; every registered knob has a
                  default and a docs/configuration.md row; reads name
                  registered knobs. Suppressions must carry a reason.
metrics-lint      exporter emissions carry the foremastbrain: prefix and
                  non-empty HELP; scrape-path iteration over private
                  mutable collections happens under a lock or on a
                  list()/dict() snapshot.
thread-hygiene    threading.Thread constructions pass daemon= explicitly
                  and are join-or-register (no anonymous
                  Thread(...).start()); no bare print() outside
                  CLI/bench/examples/devtools.
jit-hygiene       no jax.jit construction inside loop bodies; jit static
                  args are literal (hashable by construction); no Python
                  `if`/`while` on traced values in ops/ and models/.
unchecked-write   os.write() results are checked (a discarded count hides
                  short writes); os.replace/os.unlink/os.rename in the
                  durable-store modules happen behind a registered crash
                  seam (seam_point()/@durable_seam) so the crashcheck
                  sweep can cut power on either side of the rename.
ack-after-durable flow-sensitive: a public store method that mutates
                  RAM-visible state (self.<x>[k] = ...) must not return
                  (ack the caller) before a WAL/persist call — the PR 13
                  lost-ack bug class crashcheck convicts dynamically.
verdict-determin. scoring-path modules draw no wall-clock or unseeded
-ism              randomness: time.time()/datetime.now() only as the
                  `x if clock is None else clock` injectable fallback,
                  RNG only via literal-seeded PRNGKey/default_rng —
                  replayed verdicts must be bit-identical.
exception-swallow broad `except` in durability modules must re-raise,
                  return a failure, bump an error counter, or log at
                  warning+; `except BaseException` must re-raise —
                  SimulatedCrash (resilience/faults.py) rides
                  BaseException precisely so it cannot be swallowed.
"""
from __future__ import annotations

import ast

from .linter import Checker, Finding, ModuleInfo

__all__ = ["default_checkers", "LockDiscipline", "KnobRegistry",
           "MetricsLint", "ThreadHygiene", "JitHygiene",
           "TraceNameRegistry", "UncheckedWrite", "AckAfterDurable",
           "VerdictDeterminism", "ExceptionSwallow"]


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> str | None:
    """'self._lock' / 'os.environ.get' for Name/Attribute chains."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_lock_name(name: str) -> bool:
    last = name.rsplit(".", 1)[-1].lstrip("_")
    return last in ("lock", "mutex", "flock") or last.endswith("lock")


def _lock_expr_id(expr: ast.AST, modbase: str, cls: str | None) -> str | None:
    """Identity of a lock acquired by a `with` item, or None if the
    expression does not look like a lock. `with self._flock():` counts."""
    if isinstance(expr, ast.Call):
        expr = expr.func
    name = dotted(expr)
    if name is None or not _is_lock_name(name):
        return None
    if name.startswith("self."):
        rest = name[len("self."):]
        if cls:
            return f"{modbase}.{cls}.{rest}"
        return f"{modbase}.{rest}"
    return f"{modbase}.{name}"


def _iter_body(node: ast.AST):
    """Walk a statement body WITHOUT descending into nested function /
    class definitions (deferred code does not run under the lock)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop()
        yield child
        if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(child))


def _modbase(relpath: str) -> str:
    return relpath.removeprefix("foremast_tpu/").removesuffix(".py") \
        .replace("/", ".")


# ---------------------------------------------------------------------------
# (1) lock-discipline
# ---------------------------------------------------------------------------

# calls that block (or launch device work) and therefore must not run
# while holding a hot lock. Matched on the LAST dotted component, plus the
# subprocess module prefix.
_BLOCKING_LAST = {
    "urlopen", "fetch_series", "fetch_window", "sleep", "result",
    "block_until_ready", "device_put", "getaddrinfo",
}
_SUBPROCESS_ATTRS = {"run", "Popen", "call", "check_call", "check_output"}


class LockDiscipline(Checker):
    name = "lock-discipline"

    def __init__(self):
        # edge -> (path, line) of first sighting
        self._edges: dict[tuple[str, str], tuple[str, int]] = {}
        # method/function -> locks acquired at its (non-nested) top level
        self._fn_locks: dict[str, set[str]] = {}
        # deferred call edges: (held_lock, callee_key, path, line)
        self._calls: list[tuple[str, str, str, int]] = []

    def _blocking(self, call: ast.Call) -> str | None:
        name = dotted(call.func)
        if name is None:
            return None
        last = name.rsplit(".", 1)[-1]
        if name.startswith("subprocess.") and last in _SUBPROCESS_ATTRS:
            return name
        if last in _BLOCKING_LAST:
            # `.result()` only as a zero/low-arg method call (futures),
            # not e.g. a field named result
            return name
        return None

    def check(self, module: ModuleInfo) -> list[Finding]:
        findings: list[Finding] = []
        modbase = _modbase(module.relpath)

        def visit_fn(fn: ast.AST, cls: str | None):
            fn_key = f"{modbase}.{cls + '.' if cls else ''}{fn.name}"

            def visit(node: ast.AST, held: tuple[str, ...]):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda, ast.ClassDef)):
                    return  # deferred code does not run under the lock
                if isinstance(node, ast.With):
                    locks = []
                    for item in node.items:
                        lid = _lock_expr_id(item.context_expr, modbase, cls)
                        if lid is not None:
                            locks.append(lid)
                    for lid in locks:
                        if not held:
                            self._fn_locks.setdefault(fn_key, set()).add(lid)
                        for h in held:
                            if h != lid:
                                self._edges.setdefault(
                                    (h, lid), (module.relpath, node.lineno))
                    inner = held + tuple(locks)
                    for child in ast.iter_child_nodes(node):
                        visit(child, inner)
                    return
                if held and isinstance(node, ast.Call):
                    blk = self._blocking(node)
                    if blk is not None:
                        findings.append(Finding(
                            self.name, module.relpath, node.lineno,
                            f"blocking call {blk}() while holding "
                            f"{held[-1]} — move the I/O outside the lock "
                            f"or snapshot under it"))
                    callee = dotted(node.func)
                    if callee is not None:
                        if callee.startswith("self.") and cls:
                            self._calls.append(
                                (held[-1], f"{modbase}.{cls}.{callee[5:]}",
                                 module.relpath, node.lineno))
                        elif "." not in callee:
                            self._calls.append(
                                (held[-1], f"{modbase}.{callee}",
                                 module.relpath, node.lineno))
                for child in ast.iter_child_nodes(node):
                    visit(child, held)

            for stmt in fn.body:
                visit(stmt, ())

        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        visit_fn(item, node.name)
        for node in module.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit_fn(node, None)
        return findings

    def finish(self) -> list[Finding]:
        # resolve one level of call edges into lock->lock edges
        for held, callee, path, line in self._calls:
            for lid in self._fn_locks.get(callee, ()):
                if lid != held:
                    self._edges.setdefault((held, lid), (path, line))
        # cycle detection over the static graph
        adj: dict[str, set[str]] = {}
        for (a, b) in self._edges:
            adj.setdefault(a, set()).add(b)
        findings: list[Finding] = []
        seen_cycles: set[tuple[str, ...]] = set()
        for start in sorted(adj):
            path_stack = [(start, (start,))]
            visited = set()
            while path_stack:
                node, path = path_stack.pop()
                for nxt in adj.get(node, ()):
                    if nxt == start:
                        cyc = path + (start,)
                        norm = tuple(sorted(set(cyc)))
                        if norm in seen_cycles:
                            continue
                        seen_cycles.add(norm)
                        src, line = self._edges[(node, nxt)]
                        findings.append(Finding(
                            self.name, src, line,
                            "static lock-order cycle: "
                            + " -> ".join(cyc)))
                    elif nxt not in visited:
                        visited.add(nxt)
                        path_stack.append((nxt, path + (nxt,)))
        return findings


# ---------------------------------------------------------------------------
# (2) knob-registry
# ---------------------------------------------------------------------------

_ENV_ALLOWLIST = {
    "foremast_tpu/engine/config.py",
    "foremast_tpu/utils/knobs.py",
}


class KnobRegistry(Checker):
    name = "knob-registry"
    require_reason = True

    def __init__(self, docs_text: str | None = None):
        self.docs_text = docs_text
        self._registered: dict[str, tuple[str, int, bool]] = {}
        self._reads: list[tuple[str, str, int]] = []

    def check(self, module: ModuleInfo) -> list[Finding]:
        findings: list[Finding] = []
        in_registry = module.relpath in _ENV_ALLOWLIST
        for node in ast.walk(module.tree):
            # NOTE: bare `environ` is deliberately not matched — WSGI
            # handlers take a request dict named environ.
            if isinstance(node, ast.Subscript):
                if dotted(node.value) == "os.environ":
                    if not in_registry:
                        findings.append(Finding(
                            self.name, module.relpath, node.lineno,
                            "direct os.environ read — register the knob in "
                            "utils/knobs.py and use knobs.read()"))
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname in ("os.getenv", "getenv", "os.environ.get"):
                if not in_registry:
                    findings.append(Finding(
                        self.name, module.relpath, node.lineno,
                        f"direct {fname}() read — register the knob in "
                        "utils/knobs.py and use knobs.read()"))
            elif fname == "knobs.read" or (
                    in_registry and fname == "read"):
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    self._reads.append((node.args[0].value, module.relpath,
                                        node.lineno))
            elif fname == "knobs.register" or (
                    module.relpath == "foremast_tpu/utils/knobs.py"
                    and fname == "register"):
                if not node.args or not isinstance(node.args[0],
                                                   ast.Constant):
                    continue
                knob = str(node.args[0].value)
                has_default = len(node.args) >= 2 or any(
                    kw.arg == "default" for kw in node.keywords)
                self._registered[knob] = (module.relpath, node.lineno,
                                          has_default)
        return findings

    def finish(self) -> list[Finding]:
        findings: list[Finding] = []
        for knob, (path, line, has_default) in sorted(
                self._registered.items()):
            if not has_default:
                findings.append(Finding(
                    self.name, path, line,
                    f"knob {knob} registered without a default"))
            if self.docs_text is not None \
                    and f"`{knob}`" not in self.docs_text:
                findings.append(Finding(
                    self.name, path, line,
                    f"knob {knob} has no docs/configuration.md row"))
        for knob, path, line in self._reads:
            if knob not in self._registered:
                findings.append(Finding(
                    self.name, path, line,
                    f"knobs.read({knob!r}) but {knob} is never registered"))
        return findings


# ---------------------------------------------------------------------------
# (3) metrics-lint
# ---------------------------------------------------------------------------

_SCRAPE_MODULES = {
    "foremast_tpu/service/api.py",
    "foremast_tpu/dataplane/exporter.py",
    "foremast_tpu/engine/health.py",
}
_SNAPSHOT_WRAPPERS = {"list", "dict", "tuple", "sorted", "sum", "len",
                      "frozenset", "set"}


class MetricsLint(Checker):
    name = "metrics-lint"

    def _name_ok(self, arg: ast.AST) -> tuple[bool, str]:
        """(prefix ok, rendered name) for literal / f-string names;
        dynamic names pass (resolved by the caller's own literal)."""
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value.startswith("foremastbrain:"), arg.value
        if isinstance(arg, ast.JoinedStr) and arg.values:
            first = arg.values[0]
            if isinstance(first, ast.Constant) and isinstance(first.value,
                                                              str):
                return first.value.startswith("foremastbrain:"), first.value
            return False, "<f-string>"
        return True, ""

    def check(self, module: ModuleInfo) -> list[Finding]:
        findings: list[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            last = fname.rsplit(".", 1)[-1] if fname else ""
            if last not in ("record_gauge", "record_counter",
                            "record_histogram"):
                continue
            # skip the method definitions' own module internals? no —
            # every call site must conform.
            if not node.args:
                continue
            ok, rendered = self._name_ok(node.args[0])
            if not ok:
                findings.append(Finding(
                    self.name, module.relpath, node.lineno,
                    f"metric {rendered!r} missing the foremastbrain: "
                    "naming convention"))
            help_idx = 3
            help_arg = None
            if len(node.args) > help_idx:
                help_arg = node.args[help_idx]
            for kw in node.keywords:
                if kw.arg == "help":
                    help_arg = kw.value
            if help_arg is None or (
                    isinstance(help_arg, ast.Constant)
                    and not help_arg.value):
                findings.append(Finding(
                    self.name, module.relpath, node.lineno,
                    f"metric {rendered or '<dynamic>'} emitted without "
                    "HELP text (pass help=...)"))
        if module.relpath in _SCRAPE_MODULES:
            findings.extend(self._check_scrape_snapshots(module))
        return findings

    def _check_scrape_snapshots(self, module: ModuleInfo) -> list[Finding]:
        """Iteration over a private mutable collection in a scrape module
        must happen under a lock or on a snapshot — the PR 4
        quarantined_count bug class."""
        findings: list[Finding] = []

        def private_attr_iter(expr: ast.AST) -> str | None:
            """dotted name when expr iterates a private attr collection
            (self._x / self._x.items()/values()/keys()), else None."""
            if isinstance(expr, ast.Call):
                fname = dotted(expr.func)
                if fname and fname.rsplit(".", 1)[-1] in (
                        "items", "values", "keys"):
                    expr = expr.func.value
                else:
                    return None
            name = dotted(expr)
            if name and any(p.startswith("_")
                            for p in name.split(".")[1:]):
                return name
            return None

        def walk(node: ast.AST, locked: bool):
            for child in ast.iter_child_nodes(node):
                child_locked = locked
                if isinstance(child, ast.With):
                    for item in child.items:
                        src = dotted(item.context_expr) or dotted(
                            getattr(item.context_expr, "func", ast.Pass()))
                        if src and _is_lock_name(src):
                            child_locked = True
                targets = []
                if isinstance(child, ast.For):
                    targets.append(child.iter)
                elif isinstance(child, (ast.ListComp, ast.SetComp,
                                        ast.DictComp, ast.GeneratorExp)):
                    targets.extend(gen.iter for gen in child.generators)
                for t in targets:
                    if not child_locked:
                        name = private_attr_iter(t)
                        if name is not None:
                            findings.append(Finding(
                                self.name, module.relpath, t.lineno,
                                f"scrape-path iteration over mutable "
                                f"{name} outside a lock — snapshot it "
                                f"(list()/dict() under the owner's lock)"))
                walk(child, child_locked)

        walk(module.tree, False)
        return findings


# ---------------------------------------------------------------------------
# (4) thread-hygiene
# ---------------------------------------------------------------------------

_PRINT_EXEMPT_PREFIXES = (
    "foremast_tpu/cli.py",
    "foremast_tpu/__main__.py",
    "foremast_tpu/bench_",
    "foremast_tpu/examples/",
    "foremast_tpu/devtools/",
    "foremast_tpu/trigger/",
)


class ThreadHygiene(Checker):
    name = "thread-hygiene"

    def check(self, module: ModuleInfo) -> list[Finding]:
        findings: list[Finding] = []
        print_exempt = module.relpath.startswith(_PRINT_EXEMPT_PREFIXES)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname in ("threading.Thread", "Thread"):
                if not any(kw.arg == "daemon" for kw in node.keywords):
                    findings.append(Finding(
                        self.name, module.relpath, node.lineno,
                        "threading.Thread without an explicit daemon= — "
                        "decide shutdown semantics at the construction "
                        "site"))
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "start" \
                    and isinstance(node.func.value, ast.Call):
                inner = dotted(node.func.value.func)
                if inner in ("threading.Thread", "Thread"):
                    findings.append(Finding(
                        self.name, module.relpath, node.lineno,
                        "anonymous Thread(...).start() — keep a reference "
                        "so the thread can be joined or registered"))
            elif fname == "print" and not print_exempt:
                findings.append(Finding(
                    self.name, module.relpath, node.lineno,
                    "bare print() in library code — use the module "
                    "logger (logging.getLogger('foremast_tpu...'))"))
        return findings


# ---------------------------------------------------------------------------
# (5) jit-hygiene
# ---------------------------------------------------------------------------

_TRACED_MODULE_PREFIXES = ("foremast_tpu/ops/", "foremast_tpu/models/")
_TRACED_CALL_PREFIXES = ("jnp.", "lax.", "jax.numpy.", "jax.lax.")
_CONCRETIZERS = {"float", "int", "bool", "item"}


def _is_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_literal(el) for el in node.elts)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op,
                                                    (ast.USub, ast.UAdd)):
        return _is_literal(node.operand)
    return False


class JitHygiene(Checker):
    name = "jit-hygiene"

    def _is_jit_call(self, node: ast.Call) -> bool:
        fname = dotted(node.func)
        if fname in ("jax.jit", "jit"):
            return True
        if fname in ("partial", "functools.partial") and node.args:
            return dotted(node.args[0]) in ("jax.jit", "jit")
        return False

    def check(self, module: ModuleInfo) -> list[Finding]:
        findings: list[Finding] = []

        # (a) jit construction inside loop bodies; (b) static args literal
        def walk(node: ast.AST, loop_depth: int):
            for child in ast.iter_child_nodes(node):
                depth = loop_depth
                if isinstance(child, (ast.For, ast.While, ast.ListComp,
                                      ast.SetComp, ast.DictComp,
                                      ast.GeneratorExp)):
                    depth += 1
                if isinstance(child, ast.Call) and self._is_jit_call(child):
                    if depth > 0:
                        findings.append(Finding(
                            self.name, module.relpath, child.lineno,
                            "jax.jit constructed inside a loop body — "
                            "every iteration makes a fresh wrapper whose "
                            "compile cache starts empty; hoist it"))
                    for kw in child.keywords:
                        if kw.arg in ("static_argnums", "static_argnames",
                                      "donate_argnums") \
                                and not _is_literal(kw.value):
                            findings.append(Finding(
                                self.name, module.relpath, child.lineno,
                                f"jit {kw.arg} is not a literal — static "
                                "args must be hashable by construction"))
                walk(child, depth)

        walk(module.tree, 0)

        # (c) Python control flow on traced values in kernel modules
        if module.relpath.startswith(_TRACED_MODULE_PREFIXES):
            for fn in ast.walk(module.tree):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    findings.extend(self._check_traced_if(module, fn))
        return findings

    def _check_traced_if(self, module: ModuleInfo,
                         fn: ast.AST) -> list[Finding]:
        traced: set[str] = set()
        findings: list[Finding] = []

        def expr_traced(expr: ast.AST) -> bool:
            if isinstance(expr, ast.Name):
                return expr.id in traced
            if isinstance(expr, ast.Call):
                fname = dotted(expr.func) or ""
                if fname.rsplit(".", 1)[-1] in _CONCRETIZERS:
                    return False  # explicit concretization
                if fname.startswith(_TRACED_CALL_PREFIXES):
                    return True
                return False
            if isinstance(expr, ast.Compare):
                return expr_traced(expr.left) or any(
                    expr_traced(c) for c in expr.comparators)
            if isinstance(expr, ast.BoolOp):
                return any(expr_traced(v) for v in expr.values)
            if isinstance(expr, ast.UnaryOp):
                return expr_traced(expr.operand)
            if isinstance(expr, ast.BinOp):
                return expr_traced(expr.left) or expr_traced(expr.right)
            return False

        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                val = node.value
                if isinstance(val, ast.Call):
                    fname = dotted(val.func) or ""
                    if fname.startswith(_TRACED_CALL_PREFIXES) \
                            and fname.rsplit(".", 1)[-1] not in (
                                "asarray", "array", "shape", "arange"):
                        traced.add(node.targets[0].id)
                    elif fname.rsplit(".", 1)[-1] in _CONCRETIZERS:
                        traced.discard(node.targets[0].id)
                    else:
                        traced.discard(node.targets[0].id)
                else:
                    traced.discard(node.targets[0].id)
            elif isinstance(node, (ast.If, ast.While)):
                if expr_traced(node.test):
                    findings.append(Finding(
                        self.name, module.relpath, node.lineno,
                        "Python control flow on a traced value — use "
                        "jnp.where / lax.cond (or concretize explicitly "
                        "with float()/bool() outside jit)"))
        return findings


# ---------------------------------------------------------------------------
# (6) trace-registry
# ---------------------------------------------------------------------------

# registry source files: ALL_CAPS string-constant assignments in these
# modules define the legal vocabularies
_SPAN_REGISTRY_FILE = "foremast_tpu/utils/tracing.py"
_EVENT_REGISTRY_FILE = "foremast_tpu/engine/flightrec.py"
_PATH_REGISTRY_FILE = "foremast_tpu/engine/provenance.py"
# detection-waterfall stage names (engine/slo.py STAGE_ORDER): the
# DetectionWaterfall.add_stage() vocabulary, enforced like span names
_STAGE_REGISTRY_FILE = "foremast_tpu/engine/slo.py"

# instrumentation-free zones: bench/demo/devtools scripts may improvise
_TRACE_EXEMPT_PREFIXES = (
    "foremast_tpu/bench_",
    "foremast_tpu/examples/",
    "foremast_tpu/devtools/",
)

_SPAN_CALLS = {"span", "tracing.span", "tracer.span", "tracing.tracer.span",
               "self.span", "tr.span", "annotate", "tracing.annotate"}


def _collect_caps_strings(tree: ast.AST) -> set[str]:
    """String literals inside module-level ALL_CAPS assignments (covers
    plain constants, dict VALUES, and frozenset registries). Dict KEYS are
    deliberately skipped: in maps like SCORE_SPANS they are lookup aliases
    ('pair'), not registered names — collecting them would let a typo'd
    span("pair") pass as registered."""
    out: set[str] = set()

    def visit(n: ast.AST):
        if isinstance(n, ast.Dict):
            for v in n.values:
                visit(v)
            return
        if isinstance(n, ast.Constant):
            if isinstance(n.value, str):
                out.add(n.value)
            return
        for c in ast.iter_child_nodes(n):
            visit(c)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id.isupper()
                   for t in node.targets):
            continue
        visit(node.value)
    return out


def _is_constant_ref(node: ast.AST) -> bool:
    """Name/Attribute/Subscript whose terminal identifier is ALL_CAPS —
    i.e. a reference to a registered constant or constant map."""
    if isinstance(node, ast.Subscript):
        node = node.value
    name = dotted(node)
    if name is None:
        return False
    last = name.rsplit(".", 1)[-1]
    return last.isupper() and len(last) > 1


class TraceNameRegistry(Checker):
    name = "trace-registry"
    require_reason = True

    def __init__(self):
        self._spans: set[str] = set()
        self._events: set[str] = set()
        self._paths: set[str] = set()
        self._stages: set[str] = set()
        # deferred literal usages: (kind, literal, path, line)
        self._literals: list[tuple[str, str, str, int]] = []

    def _check_name_arg(self, kind: str, arg: ast.AST,
                        module: ModuleInfo, line: int,
                        findings: list[Finding]):
        if isinstance(arg, ast.JoinedStr):
            findings.append(Finding(
                self.name, module.relpath, line,
                f"inline f-string {kind} name — build it from a "
                f"registered constant map instead (see utils/tracing.py "
                f"SCORE_SPANS for the pattern)"))
        elif isinstance(arg, ast.Constant):
            if isinstance(arg.value, str):
                self._literals.append((kind, arg.value, module.relpath,
                                       line))
        elif not _is_constant_ref(arg):
            findings.append(Finding(
                self.name, module.relpath, line,
                f"dynamic {kind} name — route it through a registered "
                f"constant (ALL_CAPS) so the name inventory stays static"))

    def check(self, module: ModuleInfo) -> list[Finding]:
        if module.relpath == _SPAN_REGISTRY_FILE:
            self._spans |= _collect_caps_strings(module.tree)
            return []
        if module.relpath == _EVENT_REGISTRY_FILE:
            self._events |= _collect_caps_strings(module.tree)
            return []
        if module.relpath == _PATH_REGISTRY_FILE:
            self._paths |= _collect_caps_strings(module.tree)
            return []
        if module.relpath == _STAGE_REGISTRY_FILE:
            self._stages |= _collect_caps_strings(module.tree)
            return []
        if module.relpath.startswith(_TRACE_EXEMPT_PREFIXES):
            return []
        findings: list[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if fname is None:
                continue
            last = fname.rsplit(".", 1)[-1]
            if fname in _SPAN_CALLS and node.args:
                self._check_name_arg("span", node.args[0], module,
                                     node.lineno, findings)
            elif last == "add_timing" and node.args:
                self._check_name_arg("span", node.args[0], module,
                                     node.lineno, findings)
            elif last == "record_event" and node.args and any(
                    part in ("flight", "recorder", "flightrec")
                    for part in fname.split(".")):
                # scoped to flight-recorder receivers: the operator layer
                # has its own record_event (the Kubernetes Events API)
                self._check_name_arg("event", node.args[0], module,
                                     node.lineno, findings)
            elif fname.endswith("provenance.record") and len(node.args) >= 2:
                self._check_name_arg("provenance-path", node.args[1],
                                     module, node.lineno, findings)
            elif last == "add_stage" and len(node.args) >= 2:
                # DetectionWaterfall.add_stage(job_id, STAGE, seconds):
                # waterfall stage names are registered constants like
                # span names — dashboards/runbooks enumerate the set
                self._check_name_arg("stage", node.args[1], module,
                                     node.lineno, findings)
        return findings

    def finish(self) -> list[Finding]:
        registries = {"span": self._spans, "event": self._events,
                      "provenance-path": self._paths,
                      "stage": self._stages}
        hints = {
            "span": "utils/tracing.py SPAN_NAMES",
            "event": "engine/flightrec.py EVENT_TYPES",
            "provenance-path": "engine/provenance.py PATHS",
            "stage": "engine/slo.py STAGE_ORDER",
        }
        findings: list[Finding] = []
        for kind, literal, path, line in self._literals:
            reg = registries[kind]
            if not reg:
                continue  # single-file run: registry module not in scope
            if literal not in reg:
                findings.append(Finding(
                    self.name, path, line,
                    f"{kind} name {literal!r} is not registered — add it "
                    f"to {hints[kind]}"))
        return findings


# ---------------------------------------------------------------------------
# (7) unchecked-write
# ---------------------------------------------------------------------------

# the modules that own CRC-framed durable files; mirrors the seam roster in
# resilience/faults.py (checks.py must stay stdlib-only, so it cannot import
# faults to read the live registry)
_SEAM_MODULES = {
    "foremast_tpu/dataplane/segfile.py",
    "foremast_tpu/dataplane/winstore.py",
    "foremast_tpu/engine/jobtier.py",
    "foremast_tpu/engine/archive.py",
}
_RENAME_CALLS = {"os.replace", "os.unlink", "os.rename"}


def _is_seam_call(node: ast.Call) -> bool:
    """seam_point(self, ...) / injector.seam(...) / seam(...) — a
    registered crash-point crossing (resilience/faults.py)."""
    name = dotted(node.func)
    if name is None:
        return False
    last = name.rsplit(".", 1)[-1]
    return last in ("seam_point", "seam")


class UncheckedWrite(Checker):
    """Discarded ``os.write`` return values, and rename/unlink durability
    steps that the crashcheck sweep cannot see. A short write that nobody
    notices tears the LAST frame silently; an unregistered rename is a
    crash point the exhaustive sweep never enumerates — both defeat the
    record-or-effect proof."""

    name = "unchecked-write"
    require_reason = True

    def check(self, module: ModuleInfo) -> list[Finding]:
        findings: list[Finding] = []
        # (a) everywhere: os.write() as a bare expression statement —
        # the byte count is the ONLY signal a write was short
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Expr) and isinstance(node.value,
                                                         ast.Call) \
                    and dotted(node.value.func) == "os.write":
                findings.append(Finding(
                    self.name, module.relpath, node.lineno,
                    "os.write() result discarded — a short write would "
                    "land a torn frame undetected; check the count and "
                    "roll back (see segfile.append_frame)"))
        if module.relpath not in _SEAM_MODULES:
            return findings
        # (b) seam modules: every rename/unlink happens in a function
        # that registered a crash seam BEFORE it (or is itself a
        # @durable_seam), so crashcheck can cut power on either side
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            sealed = any(
                (dotted(d) or dotted(getattr(d, "func", ast.Pass())) or "")
                .rsplit(".", 1)[-1] == "durable_seam"
                for d in fn.decorator_list)
            seam_lines = [n.lineno for n in _iter_body(fn)
                          if isinstance(n, ast.Call) and _is_seam_call(n)]
            for n in _iter_body(fn):
                if isinstance(n, ast.Call) \
                        and dotted(n.func) in _RENAME_CALLS:
                    if sealed or any(s <= n.lineno for s in seam_lines):
                        continue
                    findings.append(Finding(
                        self.name, module.relpath, n.lineno,
                        f"{dotted(n.func)}() in a durable-store module "
                        "with no seam_point()/@durable_seam before it — "
                        "crashcheck cannot enumerate a crash at this "
                        "boundary; register the seam"))
        return findings


# ---------------------------------------------------------------------------
# (8) ack-after-durable
# ---------------------------------------------------------------------------

# the durable-write primitives: a call to any of these (directly, or via
# ONE level of same-class helper) covers the mutation. The rule scopes
# STRUCTURALLY — any class one of whose methods calls a primitive is a
# durable store, wherever it lives — so a store moved to a new module
# stays covered and test fixtures exercise the rule from any path.
_WAL_CALLS = {"_wal_docs", "_wal_state", "wal_append", "wal_append_many",
              "append_frame", "append_frames", "_persist",
              "spill_docs", "spill_state", "spill_prov", "tombstone_docs"}
# recovery/replay methods rebuild RAM FROM the durable tier — mutation
# without a WAL append is their whole job. Read-path methods (get*/fetch*)
# that mutate are lazy cache fills from the tier: same direction of flow,
# the WAL is the SOURCE of the write, not its destination.
_REPLAY_NAME_HINTS = ("recover", "replay", "restore", "load", "boot",
                      "from_tier")
_READ_PATH_PREFIXES = ("get", "fetch", "peek", "read")


class AckAfterDurable(Checker):
    """A public store method that mutates RAM-visible state and then
    returns has acked the caller; if no WAL/persist call precedes that
    return (lexically — one `if` branch covering is accepted), a crash
    after the ack loses an acknowledged write. This is the static twin of
    crashcheck's record-or-effect assertion and the PR 13 lost-ack bug."""

    name = "ack-after-durable"
    require_reason = True

    def _self_subscript_store(self, node: ast.AST) -> bool:
        """self._jobs[k] = ... / del self._windows[k] — a mutation of
        RAM-visible keyed state (plain attribute stores are counters)."""
        targets: list[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        elif isinstance(node, ast.Delete):
            targets = node.targets
        for t in targets:
            if isinstance(t, ast.Subscript):
                base = dotted(t.value)
                if base is not None and base.startswith("self."):
                    return True
        return False

    def check(self, module: ModuleInfo) -> list[Finding]:
        findings: list[Finding] = []
        for cls in module.tree.body:
            if not isinstance(cls, ast.ClassDef):
                continue
            methods = [m for m in cls.body
                       if isinstance(m, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            # pass 1: which methods call a WAL primitive directly?
            # A class with none is not a durable store — skip it.
            wal_methods = set()
            for m in methods:
                for n in _iter_body(m):
                    if isinstance(n, ast.Call):
                        name = dotted(n.func) or ""
                        if name.rsplit(".", 1)[-1] in _WAL_CALLS:
                            wal_methods.add(m.name)
                            break
            if not wal_methods:
                continue
            # pass 2: public mutating methods must hit WAL before return
            for m in methods:
                if m.name.startswith("_"):
                    continue
                if any(h in m.name.lower() for h in _REPLAY_NAME_HINTS):
                    continue
                if m.name.lower().startswith(_READ_PATH_PREFIXES):
                    continue
                mut_lines: list[int] = []
                wal_lines: list[int] = []
                ret_nodes: list[ast.Return] = []
                for n in _iter_body(m):
                    if self._self_subscript_store(n):
                        mut_lines.append(n.lineno)
                    elif isinstance(n, ast.Call):
                        name = dotted(n.func) or ""
                        last = name.rsplit(".", 1)[-1]
                        if last in _WAL_CALLS or (
                                name.startswith("self.")
                                and last in wal_methods):
                            wal_lines.append(n.lineno)
                    elif isinstance(n, ast.Return):
                        ret_nodes.append(n)
                if not mut_lines:
                    continue
                first_mut = min(mut_lines)
                if not wal_lines:
                    findings.append(Finding(
                        self.name, module.relpath, first_mut,
                        f"{cls.name}.{m.name}() mutates RAM-visible "
                        "state with no WAL/persist call on any path — a "
                        "crash loses the acked write (PR 13 bug class)"))
                    continue
                first_wal = min(wal_lines)
                for r in ret_nodes:
                    if first_mut < r.lineno < first_wal:
                        findings.append(Finding(
                            self.name, module.relpath, r.lineno,
                            f"{cls.name}.{m.name}() returns after "
                            "mutating state but before the first "
                            "WAL/persist call — ack-after-durable: the "
                            "caller sees success a crash would undo"))
        return findings


# ---------------------------------------------------------------------------
# (9) verdict-determinism
# ---------------------------------------------------------------------------

_SCORING_PREFIXES = ("foremast_tpu/engine/analyzer.py",
                     "foremast_tpu/models/", "foremast_tpu/ops/")
_WALLCLOCK_CALLS = {"time.time", "datetime.now", "datetime.utcnow",
                    "datetime.datetime.now", "datetime.datetime.utcnow",
                    "date.today", "datetime.date.today"}
# seeded constructors: fine iff the seed/key argument is a literal
_SEEDED_RNG = {"default_rng", "RandomState", "PRNGKey", "key", "seed"}


class VerdictDeterminism(Checker):
    """Scoring-path modules must replay bit-identically: the same window
    through the same model yields the same verdict digest (crashcheck's
    converge assertion and the PR 16 incident both hang off this). Wall
    clocks are allowed ONLY as the injectable fallback
    ``now = time.time() if now is None else now`` — tests pin the clock;
    RNG only through a literal-seeded PRNGKey/default_rng."""

    name = "verdict-determinism"
    require_reason = True

    def _fallback_allowed(self, tree: ast.AST) -> set[int]:
        """ids of wall-clock Call nodes inside the injectable-clock
        fallback idiom: `x if <name> is None else <name>` or
        `if <name> is None: x = time.time()`."""

        def is_none_test(test: ast.AST) -> bool:
            return (isinstance(test, ast.Compare)
                    and len(test.ops) == 1
                    and isinstance(test.ops[0], ast.Is)
                    and isinstance(test.comparators[0], ast.Constant)
                    and test.comparators[0].value is None)

        allowed: set[int] = set()
        for node in ast.walk(tree):
            body: list[ast.AST] = []
            if isinstance(node, ast.IfExp) and is_none_test(node.test):
                body = [node.body, node.orelse]
            elif isinstance(node, ast.If) and is_none_test(node.test):
                body = list(node.body)
            for sub in body:
                for n in ast.walk(sub):
                    if isinstance(n, ast.Call) \
                            and dotted(n.func) in _WALLCLOCK_CALLS:
                        allowed.add(id(n))
        return allowed

    def check(self, module: ModuleInfo) -> list[Finding]:
        if not module.relpath.startswith(_SCORING_PREFIXES):
            return []
        findings: list[Finding] = []
        allowed = self._fallback_allowed(module.tree)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            if name is None:
                continue
            if name in _WALLCLOCK_CALLS and id(node) not in allowed:
                findings.append(Finding(
                    self.name, module.relpath, node.lineno,
                    f"{name}() on the scoring path — verdicts must "
                    "replay bit-identically; take an injectable clock "
                    "(`now=None` parameter with an `is None` fallback)"))
                continue
            parts = name.split(".")
            if "random" not in parts[:-1]:
                continue  # only random-module/namespace draws
            last = parts[-1]
            if last in _SEEDED_RNG:
                seed = node.args[0] if node.args else None
                for kw in node.keywords:
                    if kw.arg in ("seed", "key"):
                        seed = kw.value
                if seed is None or not _is_literal(seed):
                    findings.append(Finding(
                        self.name, module.relpath, node.lineno,
                        f"{name}() without a literal seed on the scoring "
                        "path — derive keys from a literal root so "
                        "replays are bit-identical"))
            else:
                findings.append(Finding(
                    self.name, module.relpath, node.lineno,
                    f"unseeded {name}() on the scoring path — draw from "
                    "a literal-seeded PRNGKey/default_rng instead"))
        return findings


# ---------------------------------------------------------------------------
# (10) exception-swallow
# ---------------------------------------------------------------------------

_DURABILITY_MODULES = {
    "foremast_tpu/dataplane/segfile.py",
    "foremast_tpu/dataplane/winstore.py",
    "foremast_tpu/dataplane/delta.py",
    "foremast_tpu/engine/jobtier.py",
    "foremast_tpu/engine/jobs.py",
    "foremast_tpu/engine/archive.py",
}
_ERRORISH = ("error", "degrad", "drop", "skip", "fallback", "fail",
             "lost", "miss")
_LOG_LEVELS = {"warning", "warn", "error", "exception", "critical"}


class ExceptionSwallow(Checker):
    """A broad ``except`` in a durability module that neither re-raises,
    returns a failure, counts the error, nor logs at warning+ turns a
    torn write into silent data loss. ``except BaseException`` is held
    to the strict form — it must re-raise — because SimulatedCrash
    (resilience/faults.py) rides BaseException precisely so degrade
    handlers cannot swallow a crash the sweep injected."""

    name = "exception-swallow"
    require_reason = True

    def _handler_escapes(self, handler: ast.ExceptHandler) -> tuple[bool,
                                                                    bool]:
        """(re-raises, otherwise-accounts-for-the-error)."""
        reraises = False
        accounted = False
        for n in _iter_body(handler):
            if isinstance(n, ast.Raise):
                reraises = True
            elif isinstance(n, ast.Return):
                accounted = True  # failure surfaced to the caller
            elif isinstance(n, ast.AugAssign):
                t = dotted(n.target)
                if t and t.startswith("self.") and any(
                        h in t.rsplit(".", 1)[-1].lower()
                        for h in _ERRORISH):
                    accounted = True  # error counter bumped
            elif isinstance(n, ast.Call):
                name = dotted(n.func) or ""
                last = name.rsplit(".", 1)[-1]
                if last in _LOG_LEVELS and "log" in name.lower():
                    accounted = True
                elif last.startswith("degrade"):
                    accounted = True
        return reraises, accounted

    def check(self, module: ModuleInfo) -> list[Finding]:
        if module.relpath not in _DURABILITY_MODULES:
            return []
        findings: list[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Try):
                continue
            for handler in node.handlers:
                htype = handler.type
                tname = dotted(htype) if htype is not None else None
                broad = htype is None or tname in ("Exception",
                                                   "BaseException")
                if not broad:
                    continue
                reraises, accounted = self._handler_escapes(handler)
                if tname == "BaseException" or htype is None:
                    if not reraises:
                        findings.append(Finding(
                            self.name, module.relpath, handler.lineno,
                            "bare/BaseException handler that does not "
                            "re-raise — it would swallow SimulatedCrash "
                            "and KeyboardInterrupt; narrow it or add "
                            "`raise`"))
                elif not (reraises or accounted):
                    findings.append(Finding(
                        self.name, module.relpath, handler.lineno,
                        "broad except swallows failures in a durability "
                        "module — re-raise, return a failure, bump an "
                        "error counter (self.errors += 1), or log at "
                        "warning+ with exc_info"))
        return findings


def default_checkers(docs_text: str | None = None) -> list[Checker]:
    return [
        LockDiscipline(),
        KnobRegistry(docs_text=docs_text),
        MetricsLint(),
        ThreadHygiene(),
        JitHygiene(),
        TraceNameRegistry(),
        UncheckedWrite(),
        AckAfterDurable(),
        VerdictDeterminism(),
        ExceptionSwallow(),
    ]
