"""guberlint rule set GL000-GL019.

Each rule pins one serving-path invariant; docs/linting.md is the
operator-facing catalog. Rules are deliberately heuristic — static
analysis cannot prove "this float() touches a device value" — so every
rule pairs with the suppression pragma (`# guberlint: allow-<name>`)
for witnessed-intentional sites and the committed baseline for
grandfathered ones. The contract is monotone: new code cannot add
findings without an explicit, reviewable pragma.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.lint import Context, Finding, Module, REPO_ROOT, Rule

# ---------------------------------------------------------------------------
# shared AST helpers

# Rule-scope fixtures mirror real package paths under this prefix so a
# rule's path predicate fires on its violation fixture
# (tests/lint_fixtures/gubernator_tpu/runtime/... scans as
# gubernator_tpu/runtime/...). The default scan roots never include
# tests/, so fixtures only load when passed explicitly.
_FIXTURE_PREFIX = "tests/lint_fixtures/"


def scan_path(relpath: str) -> str:
    if relpath.startswith(_FIXTURE_PREFIX):
        return relpath[len(_FIXTURE_PREFIX):]
    return relpath


def walk_scoped(
    tree: ast.AST,
) -> Iterator[Tuple[ast.AST, Tuple[ast.AST, ...]]]:
    """Yield (node, enclosing-function-stack) pairs, depth-first."""

    def rec(node: ast.AST, stack: Tuple[ast.AST, ...]):
        for child in ast.iter_child_nodes(node):
            yield child, stack
            if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                yield from rec(child, stack + (child,))
            else:
                yield from rec(child, stack)

    yield from rec(tree, ())


def func_name(stack: Tuple[ast.AST, ...]) -> str:
    for node in reversed(stack):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return node.name
    return "<module>"


def _is_name_attr(node: ast.AST, base: str, attr: str) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr == attr
        and isinstance(node.value, ast.Name)
        and node.value.id == base
    )


def unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover
        return "<unprintable>"


# ---------------------------------------------------------------------------
# GL000 — metrics catalog <-> docs/monitoring.md drift (folded in from
# tools/check_metrics_names.py, which remains as a thin shim).

MONITORING_DOC = "docs/monitoring.md"
_METRIC_NAME_RE = re.compile(r"`(gubernator_[a-z0-9_]+)`")


def metrics_doc_names(path: Optional[str] = None) -> Set[str]:
    """Backticked gubernator_* names from the doc's table rows (prose
    may mention derived sample names like *_bucket without pinning
    them)."""
    path = path or os.path.join(REPO_ROOT, MONITORING_DOC)
    names: Set[str] = set()
    with open(path, encoding="utf-8") as f:
        for line in f:
            if not line.lstrip().startswith("|"):
                continue
            names.update(_METRIC_NAME_RE.findall(line))
    return names


def metrics_code_names() -> Set[str]:
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from gubernator_tpu.metrics import catalog_names

    return catalog_names()


def metrics_drift_errors() -> List[str]:
    """Human-readable drift list (empty = in sync); the
    tools/check_metrics_names.py shim's check() delegates here."""
    code = metrics_code_names()
    doc = metrics_doc_names()
    errors = []
    for name in sorted(code - doc):
        errors.append(
            f"{name}: exposed by the code catalog but missing from "
            f"docs/monitoring.md"
        )
    for name in sorted(doc - code):
        errors.append(
            f"{name}: documented in docs/monitoring.md but absent from "
            f"gubernator_tpu.metrics.catalog_names()"
        )
    return errors


class GL000MetricsDrift(Rule):
    code = "GL000"
    name = "metrics-drift"
    description = (
        "docs/monitoring.md must stay in lockstep with "
        "metrics.catalog_names() (both directions)"
    )

    def check_repo(self, ctx: Context) -> List[Finding]:
        if not ctx.full_repo:
            return []
        return [
            self.finding(MONITORING_DOC, 1, err, f"drift:{err.split(':')[0]}")
            for err in metrics_drift_errors()
        ]


# ---------------------------------------------------------------------------
# GL001 — host syncs in the serving path.

_SERVING_PREFIXES = ("gubernator_tpu/runtime/", "gubernator_tpu/ops/")
_SERVING_FILES = ("gubernator_tpu/parallel/ici.py",)


def _in_serving_path(relpath: str) -> bool:
    relpath = scan_path(relpath)
    return relpath.startswith(_SERVING_PREFIXES) or relpath in _SERVING_FILES


class GL001HostSync(Rule):
    code = "GL001"
    name = "host-sync"
    description = (
        "device->host syncs (block_until_ready / device_get / "
        "np.asarray / float()/int() on indexed values) in serving-path "
        "modules must be explicit (pragma) or grandfathered"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        if not _in_serving_path(mod.relpath):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.Call):
                continue
            fn = func_name(stack)
            kind = None
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"
            ):
                kind = "block_until_ready"
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "device_get"
            ) or (
                isinstance(node.func, ast.Name)
                and node.func.id == "device_get"
            ):
                kind = "device_get"
            elif isinstance(node.func, ast.Attribute) and node.func.attr == (
                "asarray"
            ) and isinstance(node.func.value, ast.Name) and (
                node.func.value.id in ("np", "numpy")
            ):
                kind = "np.asarray"
            elif (
                isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int")
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Subscript)
            ):
                kind = f"{node.func.id}(subscript)"
            if kind is None:
                continue
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"{kind} in serving-path code pulls device data to "
                    f"the host ({unparse(node)[:60]})",
                    f"{kind}:{fn}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL002 — purity of jit-traced code.


class GL002JitPurity(Rule):
    code = "GL002"
    name = "jit-purity"
    description = (
        "time.* / random.* / os.environ inside jit-compiled or "
        "make_sync_step-traced functions bakes trace-time values into "
        "compiled code"
    )

    _IMPURE_BASES = ("time", "random")

    def _traced_defs(self, mod: Module) -> List[ast.AST]:
        jit_wrapped_names: Set[str] = set()
        for node in mod.nodes():
            if (
                isinstance(node, ast.Call)
                and node.args
                and isinstance(node.args[0], ast.Name)
                and unparse(node.func).split(".")[-1] == "jit"
            ):
                jit_wrapped_names.add(node.args[0].id)
        traced: Dict[int, ast.AST] = {}
        for node, stack in mod.scoped():
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            decorated = any("jit" in unparse(d) for d in node.decorator_list)
            in_sync_builder = any(
                isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
                and s.name == "make_sync_step"
                for s in stack
            )
            if decorated or in_sync_builder or node.name in jit_wrapped_names:
                traced[id(node)] = node
        return list(traced.values())

    def check_module(self, mod: Module) -> List[Finding]:
        out = []
        flagged: Set[int] = set()
        for fdef in self._traced_defs(mod):
            for node in ast.walk(fdef):
                if id(node) in flagged:
                    continue
                bad = None
                if isinstance(node, ast.Attribute) and isinstance(
                    node.value, ast.Name
                ):
                    if node.value.id in self._IMPURE_BASES:
                        bad = f"{node.value.id}.{node.attr}"
                    elif node.value.id == "os" and node.attr in (
                        "environ",
                        "getenv",
                    ):
                        bad = f"os.{node.attr}"
                elif (
                    isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Attribute)
                    and node.value.attr == "random"
                    and isinstance(node.value.value, ast.Name)
                    and node.value.value.id in ("np", "numpy")
                ):
                    bad = f"np.random.{node.attr}"
                if bad is None:
                    continue
                flagged.add(id(node))
                out.append(
                    self.finding(
                        mod.relpath,
                        node.lineno,
                        f"{bad} inside jit-traced function "
                        f"'{fdef.name}' is evaluated at trace time, not "
                        f"per call",
                        f"{bad}:{fdef.name}",
                    )
                )
        return out


# ---------------------------------------------------------------------------
# GL003 — env-knob drift: every GUBER_* literal the package reads must be
# documented in docs/config.md AND example.conf, and vice versa.

CONFIG_DOC = "docs/config.md"
EXAMPLE_CONF = "example.conf"
_KNOB_LITERAL_RE = re.compile(r"^GUBER_[A-Z0-9_]*[A-Z0-9]$")
_KNOB_DOC_RE = re.compile(r"(GUBER_[A-Z0-9_]*[A-Z0-9])")


def _doc_knobs(text: str) -> Dict[str, int]:
    """knob -> first line number (1-based) it appears on."""
    out: Dict[str, int] = {}
    for i, line in enumerate(text.splitlines(), start=1):
        for m in _KNOB_DOC_RE.finditer(line):
            out.setdefault(m.group(1), i)
    return out


def code_knobs(
    modules: List[Module],
) -> Dict[str, Tuple[str, int]]:
    """knob -> (relpath, line) of its first string-literal read in the
    package. Trailing-underscore prefix literals (GUBER_ETCD_) are
    namespace scans, not knob reads, and are excluded by the regex."""
    out: Dict[str, Tuple[str, int]] = {}
    for mod in modules:
        if not scan_path(mod.relpath).startswith("gubernator_tpu/"):
            continue
        for node in mod.nodes():
            if isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                if _KNOB_LITERAL_RE.match(node.value):
                    out.setdefault(
                        node.value, (mod.relpath, node.lineno)
                    )
    return out


class GL003EnvDrift(Rule):
    code = "GL003"
    name = "env-drift"
    description = (
        "GUBER_* knobs read in code must appear in docs/config.md and "
        "example.conf; documented knobs must be read somewhere"
    )

    def check_repo(self, ctx: Context) -> List[Finding]:
        code = code_knobs(ctx.modules)
        out = []
        try:
            doc_text = ctx.read_doc(CONFIG_DOC)
            conf_text = ctx.read_doc(EXAMPLE_CONF)
        except OSError:
            return []
        doc = _doc_knobs(doc_text)
        conf = _doc_knobs(conf_text)
        for name, (path, line) in sorted(code.items()):
            if name not in doc:
                out.append(
                    self.finding(
                        path,
                        line,
                        f"{name} is read here but undocumented in "
                        f"{CONFIG_DOC}",
                        f"undoc:{name}",
                    )
                )
            if name not in conf:
                out.append(
                    self.finding(
                        path,
                        line,
                        f"{name} is read here but missing from "
                        f"{EXAMPLE_CONF}",
                        f"noconf:{name}",
                    )
                )
        if ctx.full_repo:
            for name, line in sorted(doc.items()):
                if name not in code:
                    out.append(
                        self.finding(
                            CONFIG_DOC,
                            line,
                            f"{name} is documented but never read by "
                            f"gubernator_tpu (ghost knob)",
                            f"ghost:{name}",
                        )
                    )
            for name, line in sorted(conf.items()):
                if name not in code and name not in doc:
                    out.append(
                        self.finding(
                            EXAMPLE_CONF,
                            line,
                            f"{name} appears in example.conf but is "
                            f"neither read by code nor in {CONFIG_DOC}",
                            f"ghost-conf:{name}",
                        )
                    )
        return out


# ---------------------------------------------------------------------------
# GL004 — import-time env reads silently ignore --config file injection.


class GL004ImportEnv(Rule):
    code = "GL004"
    name = "import-env"
    description = (
        "module-level os.environ/os.getenv reads bind before --config "
        "file injection; read at call or daemon-init time instead"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith("gubernator_tpu/"):
            return []
        out = []
        for node, stack in mod.scoped():
            if any(
                isinstance(
                    s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                )
                for s in stack
            ):
                continue
            expr = None
            if isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) and (
                    (
                        f.attr in ("get", "__getitem__", "setdefault")
                        and _is_name_attr(f.value, "os", "environ")
                    )
                    or _is_name_attr(f, "os", "getenv")
                ):
                    expr = node
            elif isinstance(node, ast.Subscript) and _is_name_attr(
                node.value, "os", "environ"
            ):
                expr = node
            elif isinstance(node, ast.Compare) and any(
                _is_name_attr(c, "os", "environ") for c in node.comparators
            ):
                expr = node
            if expr is None:
                continue
            snippet = unparse(expr)
            knob = ""
            m = re.search(r"GUBER_[A-Z0-9_]+|[A-Z][A-Z0-9_]{2,}", snippet)
            if m:
                knob = m.group(0)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"import-time environment read ({snippet[:70]}) — "
                    f"--config file injection happens after import",
                    f"import-env:{knob or snippet[:40]}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL005 — dtype discipline in ops/.

_DTYPE_CTORS = {
    "zeros": 2,
    "ones": 2,
    "empty": 2,
    "asarray": 2,
    "array": 2,
    "eye": 3,
    "full": 3,
    "arange": 99,  # positional dtype is ambiguous; require dtype=
}


class GL005DtypeDiscipline(Rule):
    code = "GL005"
    name = "dtype"
    description = (
        "jnp constructors in ops/ must pass an explicit dtype (XLA's "
        "default int32/float32 silently truncates slot-table words); "
        "int32 casts must not touch word data"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith("gubernator_tpu/ops/"):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.Call):
                continue
            fn = func_name(stack)
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id == "jnp"
                and f.attr in _DTYPE_CTORS
            ):
                has_dtype = any(
                    kw.arg == "dtype" for kw in node.keywords
                ) or len(node.args) >= _DTYPE_CTORS[f.attr]
                if not has_dtype:
                    out.append(
                        self.finding(
                            mod.relpath,
                            node.lineno,
                            f"jnp.{f.attr} without explicit dtype "
                            f"({unparse(node)[:60]})",
                            f"ctor:{f.attr}:{fn}",
                        )
                    )
            elif (
                isinstance(f, ast.Attribute)
                and f.attr == "astype"
                and len(node.args) == 1
                and "int32" in unparse(node.args[0])
                and "word" in unparse(f.value).lower()
            ):
                out.append(
                    self.finding(
                        mod.relpath,
                        node.lineno,
                        f"int32 cast on slot-table word data "
                        f"({unparse(node)[:60]}) — words must stay int64",
                        f"int32-word:{fn}",
                    )
                )
        return out


# ---------------------------------------------------------------------------
# GL006 — swallowed exceptions in transport/flush paths.

_SWALLOW_SCOPES = ("gubernator_tpu/parallel/", "gubernator_tpu/service/")
# Calls that count as "handling": logging, metrics, or re-propagation
# (json_response/on_error ship the error to the caller or an error hook).
_HANDLED_ATTRS = {
    "debug",
    "info",
    "warning",
    "warn",
    "error",
    "exception",
    "critical",
    "log",
    "inc",
    "observe",
    "record_failure",
    "set_exception",
    "abort",
    "json_response",
    "on_error",
}


def _catches_everything(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    if isinstance(t, ast.Name):
        return t.id in ("Exception", "BaseException")
    if isinstance(t, ast.Tuple):
        return any(
            isinstance(e, ast.Name) and e.id in ("Exception", "BaseException")
            for e in t.elts
        )
    return False


def _body_handles(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _HANDLED_ATTRS:
                return True
            if isinstance(f, ast.Name) and f.id.startswith("log"):
                return True
            # Building an error-bearing response object propagates the
            # failure to the caller (per-item degradation contract).
            if (
                isinstance(f, ast.Name)
                and f.id.endswith("Resp")
                and any(kw.arg == "error" for kw in node.keywords)
            ):
                return True
    return False


class GL006Swallow(Rule):
    code = "GL006"
    name = "swallow"
    description = (
        "bare `except`/`except Exception` in transport/flush paths must "
        "log, count, or re-raise — or carry an allow-swallow pragma "
        "with a reason"
    )
    requires_reason = True

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith(_SWALLOW_SCOPES):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not _catches_everything(node):
                continue
            if _body_handles(node):
                continue
            fn = func_name(stack)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"swallowed exception in '{fn}': catch-all handler "
                    f"with no logging/metric/re-raise",
                    f"swallow:{fn}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL007 — span calls must be consciously leveled.

_SPAN_SCOPES = (
    "gubernator_tpu/runtime/",
    "gubernator_tpu/parallel/",
    "gubernator_tpu/service/",
)


class GL007SpanLevel(Rule):
    code = "GL007"
    name = "span-level"
    description = (
        "tracing.span()/start_span() calls in runtime//parallel//"
        "service/ must pass an explicit level= — serving-path spans are "
        "consciously leveled (ERROR/INFO/DEBUG), never default-INFO by "
        "omission (the reference levels every span at creation, "
        "config.go:736-752)"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith(_SPAN_SCOPES):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            is_span = (
                isinstance(f, ast.Attribute)
                and f.attr in ("span", "start_span")
            ) or (
                isinstance(f, ast.Name) and f.id in ("span", "start_span")
            )
            if not is_span:
                continue
            if any(kw.arg == "level" for kw in node.keywords):
                continue
            # Positional level (span(name, "DEBUG")) also counts.
            if len(node.args) >= 2 and isinstance(
                node.args[1], ast.Constant
            ):
                continue
            fn = func_name(stack)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"span call without explicit level= in '{fn}' "
                    f"({unparse(node)[:60]}) — pass "
                    f"level=\"ERROR|INFO|DEBUG\" so the serving path's "
                    f"span verbosity is a conscious choice",
                    f"span-level:{fn}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL008 — /debug/* routes register through add_debug_routes only.

_DEBUG_ROUTE_SCOPES = ("gubernator_tpu/service/",)
_ROUTE_ADDERS = ("add_get", "add_post", "add_put", "add_delete", "add_route")


class GL008DebugRouteParity(Rule):
    code = "GL008"
    name = "debug-route-parity"
    description = (
        "/debug/* HTTP routes in service/ must be registered inside "
        "add_debug_routes() — it is the single registrar both the main "
        "gateway and the status listener call, so a route added "
        "anywhere else silently serves on one listener and 404s on the "
        "other (docs/monitoring.md \"Debug endpoints\")"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith(_DEBUG_ROUTE_SCOPES):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (
                isinstance(f, ast.Attribute) and f.attr in _ROUTE_ADDERS
            ):
                continue
            args = node.args
            path_arg = None
            # add_route(method, path, ...) carries the path second.
            idx = 1 if f.attr == "add_route" else 0
            if len(args) > idx and isinstance(args[idx], ast.Constant):
                path_arg = args[idx].value
            if not (
                isinstance(path_arg, str) and path_arg.startswith("/debug/")
            ):
                continue
            if any(
                isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
                and s.name == "add_debug_routes"
                for s in stack
            ):
                continue
            fn = func_name(stack)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"debug route '{path_arg}' registered in '{fn}' "
                    f"instead of add_debug_routes() — it will be "
                    f"missing from the other listener",
                    f"debug-route:{path_arg}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL009 — scrape-path device work must go through the cached census.

_SCRAPE_SCOPES = ("gubernator_tpu/runtime/", "gubernator_tpu/service/")
# Functions a /metrics scrape or /debug/* poll reaches: the engine's
# snapshot surface, the metrics sync bridge, and every handler closed
# over by the debug-route registrar. Device work here ran UNDER the
# engine lock on every exposition until the TTL-cached table_census()
# (ISSUE 10 satellite 1) — this rule keeps that bug class from
# regressing.
_SCRAPE_FUNCS = {
    "live_count",
    "occupancy_stats",
    "debug_snapshot",
    "hotkeys_snapshot",
    "local_debug_info",
}
_SCRAPE_ENCLOSERS = ("add_debug_routes", "engine_sync")


class GL009ScrapeDeviceWork(Rule):
    code = "GL009"
    name = "scrape-device-work"
    description = (
        "jnp/jax.numpy device work inside scrape-reachable functions "
        "(metrics sync callbacks, /debug/* handlers, the engine's "
        "snapshot surface) must go through the TTL-cached "
        "table_census() — per-scrape device reductions stall the pump "
        "under the engine lock — or carry an allow-scrape-device-work "
        "pragma with a reason"
    )
    requires_reason = True

    def _scrape_reachable(self, stack: Tuple[ast.AST, ...]) -> Optional[str]:
        """Innermost scrape-reachable function name, or None."""
        for node in reversed(stack):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if (
                node.name in _SCRAPE_FUNCS
                or node.name.startswith("debug_")
            ):
                return node.name
        # Closures inside the registrar / sync-bridge factories are the
        # handlers themselves, whatever their names.
        for node in stack:
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in _SCRAPE_ENCLOSERS
            ):
                return node.name
        return None

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith(_SCRAPE_SCOPES):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.Attribute):
                continue
            is_jnp = isinstance(
                node.value, ast.Name
            ) and node.value.id == "jnp"
            is_jax_numpy = _is_name_attr(node.value, "jax", "numpy")
            if not (is_jnp or is_jax_numpy):
                continue
            fn = self._scrape_reachable(stack)
            if fn is None:
                continue
            base = "jnp" if is_jnp else "jax.numpy"
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"{base}.{node.attr} in scrape-reachable "
                    f"'{fn}' runs device work per exposition — read the "
                    f"TTL-cached table_census() instead",
                    f"scrape-device:{node.attr}:{fn}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL010 — host->device uploads in runtime//parallel/ must be accounted.

_TRANSFER_SCOPES = ("gubernator_tpu/runtime/", "gubernator_tpu/parallel/")


class GL010UnaccountedTransfer(Rule):
    code = "GL010"
    name = "unaccounted-transfer"
    description = (
        "raw jax.device_put in runtime//parallel/ bypasses the "
        "host<->device transfer ledger (gubernator_transfer_* families, "
        "docs/monitoring.md \"Device resources\") — route uploads "
        "through utils/transfer.device_put/put_tree or wrap the site in "
        "transfer.account(), or carry an allow-unaccounted-transfer "
        "pragma with a reason"
    )
    requires_reason = True

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith(_TRANSFER_SCOPES):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            # jax.device_put(...) or a bare device_put(...) pulled in via
            # `from jax import device_put`. The accounted wrapper is
            # always called through its module (transfer.device_put /
            # _transfer.device_put), so attribute calls on other bases
            # pass.
            if not (
                _is_name_attr(f, "jax", "device_put")
                or (isinstance(f, ast.Name) and f.id == "device_put")
            ):
                continue
            fn = func_name(stack)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"raw device_put in '{fn}' bypasses the transfer "
                    f"ledger ({unparse(node)[:60]}) — use "
                    f"utils/transfer.device_put/put_tree so the upload "
                    f"lands in gubernator_transfer_*",
                    f"device_put:{fn}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL011 — raw slot-table tensor indexing in runtime/ bypasses paging.

_PAGED_SCOPES = ("gubernator_tpu/runtime/",)

# ops/layout.py SlotTable._fields, hardcoded so the linter stays
# jax-free (importing ops.layout pulls in jax.numpy). A registry test
# in tests/test_lint.py asserts this tuple equals SlotTable._fields.
_SLOT_FIELDS = (
    "key_hi", "key_lo", "used", "algo", "status", "limit", "duration",
    "remaining", "stamp", "expire_at", "invalid_at", "burst", "lru",
)


class GL011RawTableIndex(Rule):
    code = "GL011"
    name = "raw-table-index"
    description = (
        "direct indexing / host materialization of a raw slot-table "
        "field tensor in runtime/ reads PHYSICAL rows — under paging "
        "(GUBER_TABLE_PAGE_GROUPS) physical position is a page frame, "
        "not a logical group, and host-demoted rows are invisible. "
        "Route reads through the paged addressing layer "
        "(PagedKernels.gather_rows/extract_page, ops/paged.py) or the "
        "census view, or carry an allow-raw-table-index pragma with a "
        "reason"
    )
    requires_reason = True

    def _table_field(self, node: ast.AST) -> Optional[str]:
        """Return the field name if node is `<table>.<slot-field>`.

        A table base is the bare name `table`/`tbl` or any attribute
        chain ending in `.table` (self.table, eng.table, …). Batch
        structs (ib.*, wb.*, cols.*) reuse some field names but never
        hang off a `table` base, which is what keeps this precise.
        """
        if not isinstance(node, ast.Attribute) or node.attr not in _SLOT_FIELDS:
            return None
        base = node.value
        if isinstance(base, ast.Name) and base.id in ("table", "tbl"):
            return node.attr
        if isinstance(base, ast.Attribute) and base.attr == "table":
            return node.attr
        return None

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith(_PAGED_SCOPES):
            return []
        if scan_path(mod.relpath).endswith("runtime/pager.py"):
            # the residency manager IS the paging layer's host half
            return []
        out = []
        for node, stack in mod.scoped():
            field = None
            how = None
            if isinstance(node, ast.Subscript):
                # table.used[idx] — physical-row indexing
                field = self._table_field(node.value)
                how = "indexes"
            elif isinstance(node, ast.Call) and _is_name_attr(
                node.func, "np", "asarray"
            ):
                # np.asarray(table.used) — whole-tensor host pull of
                # physical rows (usually followed by fancy indexing)
                for arg in node.args[:1]:
                    field = self._table_field(arg)
                how = "materializes"
            if field is None:
                continue
            fn = func_name(stack)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"'{fn}' {how} raw table field '{field}' — physical "
                    f"rows are page frames under paging; go through the "
                    f"paged addressing layer (ops/paged.py) or the "
                    f"census view",
                    f"raw-table:{field}:{fn}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL012 — rate-limit answers constructed without decision provenance.

_PROVENANCE_SCOPES = ("gubernator_tpu/service/",)
_PROVENANCE_FILES = (
    "gubernator_tpu/parallel/leases.py",
    "gubernator_tpu/parallel/peers.py",
)

# A function that calls any of these is considered provenance-aware:
# stamp_decision writes the decision_path metadata,
# record_decision/record_columnar feed the counters + flight recorder
# (service/admission.py).
_STAMP_CALLS = ("stamp_decision", "record_decision", "record_columnar")


class GL012DecisionProvenance(Rule):
    code = "GL012"
    name = "decision-provenance"
    description = (
        "a RateLimitResp constructed on a serving path without an "
        "error= kwarg is an ANSWER, and every answer must name the "
        "path that produced it (docs/monitoring.md \"Admission\"): the "
        "enclosing function must call stamp_decision / record_decision "
        "/ record_columnar (service/admission.py), or carry an "
        "allow-decision-provenance pragma with a reason"
    )
    requires_reason = True

    def _is_resp_ctor(self, node: ast.AST) -> bool:
        """A call to the bare name RateLimitResp. Attribute forms
        (pb.RateLimitResp) are the WIRE message class — serialization,
        not a decision — and stay out of scope."""
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "RateLimitResp"
        )

    def _has_error_kwarg(self, node: ast.Call) -> bool:
        return any(kw.arg == "error" for kw in node.keywords)

    def _stamps(self, fn: Optional[ast.AST]) -> bool:
        if fn is None:
            return False
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            name = (
                f.id
                if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else None
            )
            if name in _STAMP_CALLS:
                return True
        return False

    def check_module(self, mod: Module) -> List[Finding]:
        rel = scan_path(mod.relpath)
        if not (
            rel.startswith(_PROVENANCE_SCOPES) or rel in _PROVENANCE_FILES
        ):
            return []
        if rel == "gubernator_tpu/service/admission.py":
            return []  # the provenance module itself
        out = []
        for node, stack in mod.scoped():
            if not self._is_resp_ctor(node):
                continue
            if self._has_error_kwarg(node):
                # Error answers carry their provenance in the error
                # string itself; status/remaining are meaningless.
                continue
            enclosing = None
            for s in reversed(stack):
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    enclosing = s
                    break
            if self._stamps(enclosing):
                continue
            fn = func_name(stack)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"'{fn}' constructs a RateLimitResp answer without "
                    f"decision provenance — call stamp_decision / "
                    f"record_decision (service/admission.py) in this "
                    f"function, or carry an allow-decision-provenance "
                    f"pragma with a reason",
                    f"provenance:{fn}",
                )
            )
        return out


# ---------------------------------------------------------------------------
# GL013 — engine-core-drift: topology shells must not re-fork the core.

# Files allowed to SUBCLASS / parameterize MeshEngine: a method defined
# here whose name shadows a core method re-forks logic the unification
# collapsed (the pre-PR-15 state was ~800 duplicated LoC whose halves
# drifted independently).
_CORE_SHELL_FILES = (
    "gubernator_tpu/runtime/ici_engine.py",
    "gubernator_tpu/runtime/topology.py",
    # fixture twin — only ever scanned when passed explicitly
    # (tests/lint_fixtures/; the default roots never include tests/)
    "gubernator_tpu/runtime/gl013_core_drift.py",
)
_CORE_FILE = "gubernator_tpu/runtime/engine.py"
_CORE_CLASSES = ("EngineBase", "MeshEngine")

_core_methods_cache: Optional[Set[str]] = None


def engine_core_methods() -> Set[str]:
    """Method names of the unified engine core (EngineBase + MeshEngine
    in runtime/engine.py), dunders excluded. Parsed from disk so the
    rule works on partial scans (fixtures); cached per process."""
    global _core_methods_cache
    if _core_methods_cache is None:
        with open(
            os.path.join(REPO_ROOT, _CORE_FILE), encoding="utf-8"
        ) as f:
            tree = ast.parse(f.read())
        names: Set[str] = set()
        for node in tree.body:
            if (
                isinstance(node, ast.ClassDef)
                and node.name in _CORE_CLASSES
            ):
                for item in node.body:
                    if isinstance(
                        item, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ) and not item.name.startswith("__"):
                        names.add(item.name)
        _core_methods_cache = names
    return _core_methods_cache


class GL013EngineCoreDrift(Rule):
    code = "GL013"
    name = "engine-core-drift"
    description = (
        "a method defined in a topology shell (runtime/ici_engine.py, "
        "runtime/topology.py) whose name shadows a MeshEngine core "
        "method (runtime/engine.py) re-forks dispatch/complete/recovery "
        "logic the engine unification collapsed — move the delta into "
        "the core or the strategy object (see runtime/topology.py "
        "docstring), or carry an allow-engine-core-drift pragma with a "
        "reason"
    )
    requires_reason = True

    def check_module(self, mod: Module) -> List[Finding]:
        if scan_path(mod.relpath) not in _CORE_SHELL_FILES:
            return []
        core = engine_core_methods()
        out = []
        for node in mod.nodes():
            if not isinstance(node, ast.ClassDef):
                continue
            for item in node.body:
                if not isinstance(
                    item, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                if item.name.startswith("__") or item.name not in core:
                    continue
                out.append(
                    self.finding(
                        mod.relpath,
                        item.lineno,
                        f"'{node.name}.{item.name}' shadows the unified "
                        f"engine core's '{item.name}' "
                        f"(runtime/engine.py) — fold the delta into the "
                        f"core or the topology strategy instead of "
                        f"re-forking it",
                        f"core-drift:{node.name}.{item.name}",
                    )
                )
        return out


# Files that define SloSpec catalog entries (GL015): the observatory's
# default catalog and the fixture twin.
_SLO_CATALOG_FILES = (
    "gubernator_tpu/service/slo.py",
    # fixture twin — only ever scanned when passed explicitly
    "gubernator_tpu/service/gl015_slo_parity.py",
)
_SLO_DOC_FILE = "docs/monitoring.md"
_SLO_DOC_SECTION = "### SLO catalog"
# First cell of a catalog table row: | `spec-id` | ...
_SLO_DOC_ROW_RE = re.compile(r"^\|\s*`([a-z0-9-]+)`\s*\|")

_slo_doc_ids_cache: Optional[Set[str]] = None


def slo_doc_ids() -> Set[str]:
    """Spec ids listed in docs/monitoring.md's "### SLO catalog" table —
    parsed from disk so the rule works on partial scans (fixtures);
    cached per process. Scoped to the subsection so underscore metric
    names elsewhere in the doc never alias a kebab-case spec id."""
    global _slo_doc_ids_cache
    if _slo_doc_ids_cache is None:
        ids: Set[str] = set()
        path = os.path.join(REPO_ROOT, _SLO_DOC_FILE)
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
        except OSError:
            lines = []
        in_section = False
        for line in lines:
            if line.strip().startswith("#"):
                in_section = line.strip() == _SLO_DOC_SECTION
                continue
            if in_section:
                m = _SLO_DOC_ROW_RE.match(line.strip())
                if m:
                    ids.add(m.group(1))
        _slo_doc_ids_cache = ids
    return _slo_doc_ids_cache


class GL015SloCatalogParity(Rule):
    code = "GL015"
    name = "slo-catalog-parity"
    requires_reason = True
    description = (
        "every SloSpec the observatory catalog (service/slo.py) "
        'constructs must have a row in docs/monitoring.md\'s "### SLO '
        'catalog" table, and every row there must name a spec the code '
        "still constructs — an SLO an operator cannot look up (or a "
        "documented alert the code no longer evaluates) breaks the "
        "paging runbook both ways"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        if scan_path(mod.relpath) not in _SLO_CATALOG_FILES:
            return []
        doc_ids = slo_doc_ids()
        # Spec ids this module constructs: SloSpec(id="...") keyword
        # constants. Dynamic ids (merge overrides at runtime) are
        # invisible here by design — the catalog table documents the
        # built-ins.
        declared: Dict[str, int] = {}
        for node in mod.nodes():
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "SloSpec"
            ):
                for kw in node.keywords:
                    if (
                        kw.arg == "id"
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)
                    ):
                        declared.setdefault(kw.value.value, node.lineno)
        out = []
        for sid in sorted(declared):
            if sid not in doc_ids:
                out.append(
                    self.finding(
                        mod.relpath,
                        declared[sid],
                        f"SloSpec '{sid}' has no row in {_SLO_DOC_FILE} "
                        f'"{_SLO_DOC_SECTION}" — document the SLO (or '
                        f"add an allow-slo-catalog-parity pragma)",
                        f"slo-catalog:{sid}",
                    )
                )
        # Ghost rows (doc id with no constructing SloSpec) only make
        # sense against the REAL full catalog, not the fixture twin.
        if scan_path(mod.relpath) == _SLO_CATALOG_FILES[0]:
            for sid in sorted(doc_ids - set(declared)):
                out.append(
                    self.finding(
                        mod.relpath,
                        1,
                        f'{_SLO_DOC_FILE} "{_SLO_DOC_SECTION}" lists '
                        f"'{sid}' but service/slo.py constructs no such "
                        f"SloSpec — the documented alert is a ghost",
                        f"slo-catalog-ghost:{sid}",
                    )
                )
        return out


# ---------------------------------------------------------------------------
# GL017/GL018: lock discipline. Both rules share one per-module pass
# that resolves each class's guarded-by declaration (the
# raceguard.guarded_by(Cls, {...}) call at module bottom), its lock
# attributes (self.<attr> = lockorder.make_lock("name")), and the
# local-inheritance merge (DeviceEngine inherits MeshEngine's locks and
# guards when both ClassDefs live in the same module).

_MUTATOR_METHODS = {
    "append", "appendleft", "add", "pop", "popleft", "popitem", "clear",
    "update", "extend", "remove", "discard", "insert", "setdefault",
    "sort", "fill",
}

# Calls that block (host sync, RPC turnaround, timed wait) and must not
# run inside a `with <hot lock>` body: every thread needing the lock
# stalls behind device/network latency — the hazard class the PR 6
# pipeline split exists to kill.
_BLOCKING_ATTRS = {"block_until_ready", "device_get", "result"}
_BLOCKING_NAME_ATTRS = (("time", "sleep"),)
_BLOCKING_FUNCS = {"urlopen", "device_get"}

_HOT_LOCKS = {
    "engine.table", "engine.keys", "engine.bulks", "engine.dirty",
    "engine.pipeline", "engine.shards", "engine.census",
    "engine.admission", "standby.shadow", "service.admission_ring",
    "metrics.hotkeys", "timeseries.ring", "timeseries.ringset",
}


def _decorator_names(fn) -> List[Tuple[str, Optional[str]]]:
    """(name, first-str-arg) per decorator; 'raceguard.holds_lock'
    normalizes to 'holds_lock'."""
    out = []
    for dec in fn.decorator_list:
        target, arg = dec, None
        if isinstance(dec, ast.Call):
            target = dec.func
            if dec.args and isinstance(dec.args[0], ast.Constant):
                if isinstance(dec.args[0].value, str):
                    arg = dec.args[0].value
        if isinstance(target, ast.Attribute):
            out.append((target.attr, arg))
        elif isinstance(target, ast.Name):
            out.append((target.id, arg))
    return out


class _ClassLockInfo:
    """Per-ClassDef lock protocol, pre-merge."""

    def __init__(self):
        self.bases: List[str] = []
        self.lock_attrs: Dict[str, str] = {}  # self-attr -> lock name
        self.guarded: Dict[str, str] = {}  # field -> mode spec


def _module_lock_info(mod: Module) -> Dict[str, "_ClassLockInfo"]:
    """Resolve every class's declared lock protocol in one pass, cached
    on the Module (GL017 and GL018 share it)."""
    cached = getattr(mod, "_lockinfo", None)
    if cached is not None:
        return cached
    info: Dict[str, _ClassLockInfo] = {}
    classes: List[ast.ClassDef] = []
    for node in mod.nodes():
        if isinstance(node, ast.ClassDef):
            classes.append(node)
            ci = info.setdefault(node.name, _ClassLockInfo())
            ci.bases = [
                b.id for b in node.bases if isinstance(b, ast.Name)
            ]
    for cls in classes:
        ci = info[cls.name]
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            if not (
                isinstance(v, ast.Call)
                and (
                    (
                        isinstance(v.func, ast.Attribute)
                        and v.func.attr in ("make_lock", "make_rlock")
                    )
                    or (
                        isinstance(v.func, ast.Name)
                        and v.func.id in ("make_lock", "make_rlock")
                    )
                )
                and v.args
                and isinstance(v.args[0], ast.Constant)
                and isinstance(v.args[0].value, str)
            ):
                continue
            for tgt in node.targets:
                if (
                    isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                ):
                    ci.lock_attrs[tgt.attr] = v.args[0].value
    # guarded_by(ClassName, {...}) calls anywhere at module level.
    for node in mod.nodes():
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None
        )
        if name != "guarded_by" or len(node.args) < 2:
            continue
        cls_arg, map_arg = node.args[0], node.args[1]
        if not (
            isinstance(cls_arg, ast.Name) and isinstance(map_arg, ast.Dict)
        ):
            continue
        ci = info.setdefault(cls_arg.id, _ClassLockInfo())
        for k, v in zip(map_arg.keys, map_arg.values):
            if (
                isinstance(k, ast.Constant)
                and isinstance(k.value, str)
                and isinstance(v, ast.Constant)
                and isinstance(v.value, str)
            ):
                ci.guarded[k.value] = v.value
    # Merge along same-module base chains (subclass methods mutate
    # inherited fields under inherited locks).
    merged: Dict[str, _ClassLockInfo] = {}

    def resolve(name: str, seen: Tuple[str, ...] = ()) -> _ClassLockInfo:
        if name in merged:
            return merged[name]
        ci = info.get(name)
        out = _ClassLockInfo()
        if ci is None or name in seen:
            return out
        for base in ci.bases:
            b = resolve(base, seen + (name,))
            out.lock_attrs.update(b.lock_attrs)
            out.guarded.update(b.guarded)
        out.bases = ci.bases
        out.lock_attrs.update(ci.lock_attrs)
        out.guarded.update(ci.guarded)
        merged[name] = out
        return out

    for name in info:
        resolve(name)
    mod._lockinfo = merged
    return merged


def _self_field(node: ast.AST) -> Optional[str]:
    """The `field` of a self.<field> target, digging through
    subscripts/attribute chains (self._shadow[k] -> _shadow)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


class GL017LockDiscipline(Rule):
    code = "GL017"
    name = "lock-discipline"
    requires_reason = True
    description = (
        "a field in a class's raceguard.guarded_by declaration may only "
        "be mutated lexically inside `with self.<lock>` for the declared "
        "lock, or in a method marked @holds_lock(<lock>) / @init_path "
        "(or __init__) — the static twin of the GUBER_RACE_SANITIZER "
        "runtime check"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        lockinfo = _module_lock_info(mod)
        if not any(ci.guarded for ci in lockinfo.values()):
            return []
        out: List[Finding] = []
        for node in mod.nodes():
            if not isinstance(node, ast.ClassDef):
                continue
            ci = lockinfo.get(node.name)
            if ci is None or not ci.guarded:
                continue
            # field -> required lock name (None for @thread: unchecked
            # statically, the runtime affinity pin owns that mode)
            req: Dict[str, Optional[str]] = {}
            for field, spec in ci.guarded.items():
                if spec == "@thread":
                    continue
                req[field] = spec.split(":", 1)[1] if ":" in spec else spec
            if not req:
                continue
            for meth in node.body:
                if not isinstance(
                    meth, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                decs = _decorator_names(meth)
                if meth.name == "__init__" or any(
                    d == "init_path" for d, _ in decs
                ):
                    continue
                held = {
                    arg for d, arg in decs if d == "holds_lock" and arg
                }
                self._scan(mod, node.name, meth, meth.body, held,
                           ci.lock_attrs, req, out)
        return out

    def _check_exprs(self, mod, cls_name, meth, roots, held, req, out):
        """Flag guarded-field mutations in a statement's expression
        parts: subscript/attr assignment targets are handled by the
        caller; here we catch mutating METHOD calls (append/update/...)."""
        for root in roots:
            for sub in ast.walk(root):
                if (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _MUTATOR_METHODS
                ):
                    field = _self_field(sub.func.value)
                    if field in req and req[field] not in held:
                        self._flag(mod, cls_name, meth, sub, field,
                                   req[field], out)

    def _scan(self, mod, cls_name, meth, body, held, lock_attrs, req, out):
        for node in body:
            if isinstance(node, (ast.With, ast.AsyncWith)):
                added = set()
                for item in node.items:
                    ce = item.context_expr
                    if (
                        isinstance(ce, ast.Attribute)
                        and isinstance(ce.value, ast.Name)
                        and ce.value.id == "self"
                        and ce.attr in lock_attrs
                    ):
                        added.add(lock_attrs[ce.attr])
                self._scan(mod, cls_name, meth, node.body,
                           held | added, lock_attrs, req, out)
                continue
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                # Nested defs escape the lexical lock scope (a closure
                # may run after release); flow-insensitivity can't
                # decide either way, so they are out of scope here —
                # the runtime sanitizer still covers them.
                continue
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = list(node.targets)
            for tgt in targets:
                field = _self_field(tgt)
                if field in req and req[field] not in held:
                    self._flag(mod, cls_name, meth, node, field,
                               req[field], out)
            # Expression parts of this statement only — nested
            # statement bodies recurse below so a `with` inside an
            # `if` still extends the held set.
            exprs = [
                c for c in ast.iter_child_nodes(node)
                if isinstance(c, ast.expr)
            ]
            self._check_exprs(mod, cls_name, meth, exprs, held, req, out)
            for attr in ("body", "orelse", "finalbody"):
                sub_body = getattr(node, attr, None)
                if sub_body and isinstance(sub_body, list):
                    self._scan(mod, cls_name, meth, sub_body, held,
                               lock_attrs, req, out)
            for h in getattr(node, "handlers", ()) or ():
                self._scan(mod, cls_name, meth, h.body, held,
                           lock_attrs, req, out)

    def _flag(self, mod, cls_name, meth, node, field, lock, out):
        out.append(
            self.finding(
                mod.relpath,
                node.lineno,
                f"{cls_name}.{field} is guarded by '{lock}' but this "
                f"mutation in {meth.name}() is not inside "
                f"`with self.<{lock} lock>` or a @holds_lock({lock!r}) "
                f"method (or add an allow-lock-discipline pragma with a "
                f"reason)",
                f"{cls_name}.{meth.name}.{field}",
            )
        )


class GL018BlockingUnderLock(Rule):
    code = "GL018"
    name = "blocking-under-lock"
    requires_reason = True
    description = (
        "no block_until_ready / device_get / .result() / time.sleep / "
        "urlopen inside a `with` block holding a named hot lock — every "
        "thread needing that lock then stalls behind device or network "
        "latency (the hazard the PR 6 pipeline split removed)"
    )

    def check_module(self, mod: Module) -> List[Finding]:
        lockinfo = _module_lock_info(mod)
        if not any(ci.lock_attrs for ci in lockinfo.values()):
            return []
        out: List[Finding] = []
        for node in mod.nodes():
            if not isinstance(node, ast.ClassDef):
                continue
            ci = lockinfo.get(node.name)
            if ci is None or not ci.lock_attrs:
                continue
            hot_attrs = {
                attr: lock
                for attr, lock in ci.lock_attrs.items()
                if lock in _HOT_LOCKS
            }
            if not hot_attrs:
                continue
            for meth in node.body:
                if isinstance(
                    meth, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    self._scan(mod, node.name, meth, meth.body,
                               hot_attrs, None, out)
        return out

    def _blocking_call(self, call: ast.Call) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Attribute):
            if f.attr in _BLOCKING_ATTRS:
                return f.attr
            for base, attr in _BLOCKING_NAME_ATTRS:
                if _is_name_attr(f, base, attr):
                    return f"{base}.{attr}"
        elif isinstance(f, ast.Name) and f.id in _BLOCKING_FUNCS:
            return f.id
        return None

    def _scan(self, mod, cls_name, meth, body, hot_attrs, lock, out):
        for node in body:
            if isinstance(node, (ast.With, ast.AsyncWith)):
                inner_lock = lock
                for item in node.items:
                    ce = item.context_expr
                    if (
                        isinstance(ce, ast.Attribute)
                        and isinstance(ce.value, ast.Name)
                        and ce.value.id == "self"
                        and ce.attr in hot_attrs
                    ):
                        inner_lock = hot_attrs[ce.attr]
                self._scan(mod, cls_name, meth, node.body, hot_attrs,
                           inner_lock, out)
                continue
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue  # closures run outside the lexical lock scope
            if lock is not None:
                # Whole-subtree walk: everything nested in this
                # statement executes with the lock held.
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call):
                        what = self._blocking_call(sub)
                        if what is not None:
                            out.append(
                                self.finding(
                                    mod.relpath,
                                    sub.lineno,
                                    f"blocking call {what}() inside a "
                                    f"`with` holding hot lock '{lock}' "
                                    f"in {cls_name}.{meth.name}() — "
                                    f"move it outside the critical "
                                    f"section (or add an "
                                    f"allow-blocking-under-lock pragma "
                                    f"with a reason)",
                                    f"{cls_name}.{meth.name}.{what}",
                                )
                            )
                continue
            for attr in ("body", "orelse", "finalbody"):
                sub_body = getattr(node, attr, None)
                if sub_body and isinstance(sub_body, list):
                    self._scan(mod, cls_name, meth, sub_body, hot_attrs,
                               lock, out)
            for h in getattr(node, "handlers", ()) or ():
                self._scan(mod, cls_name, meth, h.body, hot_attrs,
                           lock, out)


# ---------------------------------------------------------------------------
# GL019 — queues on serving paths must be bounded.

_QUEUE_SCOPES = (
    "gubernator_tpu/runtime/",
    "gubernator_tpu/parallel/",
    "gubernator_tpu/service/",
)


class GL019UnboundedQueue(Rule):
    code = "GL019"
    name = "unbounded-queue"
    description = (
        "queue.SimpleQueue()/queue.Queue()/asyncio.Queue() without a "
        "positive bound in runtime//parallel//service/ is an invisible "
        "buffer: under overload it converts memory into latency until "
        "the process dies (the overload control plane bounds engine "
        "intake at GUBER_INTAKE_LIMIT for exactly this reason) — pass "
        "maxsize, or carry an allow-unbounded-queue pragma arguing why "
        "the producer is bounded elsewhere"
    )
    requires_reason = True

    def check_module(self, mod: Module) -> List[Finding]:
        if not scan_path(mod.relpath).startswith(_QUEUE_SCOPES):
            return []
        out = []
        for node, stack in mod.scoped():
            if not isinstance(node, ast.Call):
                continue
            ctor = self._queue_ctor(node.func)
            if ctor is None:
                continue
            # SimpleQueue has no maxsize parameter at all; the others
            # are unbounded only when maxsize is absent or a literal
            # <= 0 (a computed bound — validated knob, min(...) — is
            # trusted).
            if not ctor.endswith("SimpleQueue") and self._bounded(node):
                continue
            fn = func_name(stack)
            out.append(
                self.finding(
                    mod.relpath,
                    node.lineno,
                    f"unbounded {ctor}() in '{fn}': pass a maxsize (or "
                    f"add an allow-unbounded-queue pragma stating what "
                    f"bounds the producer)",
                    f"{fn}.{ctor}",
                )
            )
        return out

    @staticmethod
    def _queue_ctor(f) -> Optional[str]:
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if f.value.id == "queue" and f.attr in (
                "Queue", "SimpleQueue", "LifoQueue", "PriorityQueue",
            ):
                return f"queue.{f.attr}"
            if f.value.id == "asyncio" and f.attr in (
                "Queue", "LifoQueue", "PriorityQueue",
            ):
                return f"asyncio.{f.attr}"
        return None

    @staticmethod
    def _bounded(call: ast.Call) -> bool:
        bound = call.args[0] if call.args else None
        for kw in call.keywords:
            if kw.arg == "maxsize":
                bound = kw.value
        if bound is None:
            return False
        if isinstance(bound, ast.Constant):
            try:
                return int(bound.value) > 0
            except (TypeError, ValueError):
                return False
        return True


# ---------------------------------------------------------------------------
# --fix-docs support (GL003 auto-stub).


def fix_docs(findings: List[Finding]) -> List[str]:
    """Append stub entries for undocumented knobs to docs/config.md and
    example.conf. Returns a list of human-readable actions taken. Stubs
    are deliberately marked TODO: the linter gets the catalog complete;
    a human gets it true."""
    undoc = sorted(
        {
            f.key.split("undoc:", 1)[1]
            for f in findings
            if f.rule == "GL003" and ":undoc:" in f.key
        }
    )
    noconf = sorted(
        {
            f.key.split("noconf:", 1)[1]
            for f in findings
            if f.rule == "GL003" and ":noconf:" in f.key
        }
    )
    actions = []
    if undoc:
        path = os.path.join(REPO_ROOT, CONFIG_DOC)
        with open(path, encoding="utf-8") as f:
            text = f.read()
        header = "## Uncatalogued knobs (guberlint --fix-docs stubs)"
        if header not in text:
            text += (
                f"\n{header}\n\n"
                "| Key | Maps to | Notes |\n|---|---|---|\n"
            )
        for name in undoc:
            text += f"| {name} | — | TODO: document (stub added by guberlint) |\n"
            actions.append(f"{CONFIG_DOC}: stub row for {name}")
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
    if noconf:
        path = os.path.join(REPO_ROOT, EXAMPLE_CONF)
        with open(path, encoding="utf-8") as f:
            text = f.read()
        header = "# Uncatalogued knobs (guberlint --fix-docs stubs)"
        if header not in text:
            text += f"\n{header}\n"
        for name in noconf:
            text += f"# {name}=\n"
            actions.append(f"{EXAMPLE_CONF}: stub line for {name}")
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
    return actions
