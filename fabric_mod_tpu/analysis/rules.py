"""The fmtlint rule catalog.

Each rule is the static mirror of a runtime discipline this framework
already enforces dynamically — the rule text names the sanctioned
primitive, so a finding is an instruction, not a style opinion.

Scoping convention: rules apply to the whole package unless noted.
``concurrency/`` is exempt from the thread/lock rules (it IS the
sanctioned layer), ``faults/`` from the fault-point rule, and the
tracing module from the span rule, for the same reason.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Set

from fabric_mod_tpu.analysis.engine import (KNOB_RE, Finding, ModuleInfo,
                                            ProjectContext)


def _aliases(tree: ast.AST) -> Dict[str, Set[str]]:
    """module name -> local alias set, plus from-imported names under
    the pseudo-module key "from:<module>"."""
    out: Dict[str, Set[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out.setdefault(a.name, set()).add(a.asname or a.name)
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out.setdefault(f"from:{node.module}", set()).add(
                    a.asname or a.name)
    return out


def _is_module_attr(node: ast.expr, modnames: Set[str],
                    attr: str) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == attr
            and isinstance(node.value, ast.Name)
            and node.value.id in modnames)


def _str_const(node: ast.expr):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class Rule:
    name: str = ""
    doc: str = ""

    def check(self, mod: ModuleInfo,
              ctx: ProjectContext) -> Iterable[Finding]:
        raise NotImplementedError

    def _f(self, mod: ModuleInfo, node: ast.AST, msg: str) -> Finding:
        return Finding(mod.relpath, getattr(node, "lineno", 1),
                       self.name, msg)


class KnobRule(Rule):
    name = "knobs"
    doc = ("every FABRIC_MOD_TPU_*/FMT_* access goes through the typed "
           "utils/knobs.py registry: raw os.environ reads of a knob, "
           "env_int/env_float calls outside utils/, and undeclared "
           "knob-name literals are errors")

    EXEMPT = {"utils/env.py", "utils/knobs.py"}
    RAW_HELPERS = {"env_int", "env_float", "_env_int", "_env_float"}

    def check(self, mod, ctx):
        if mod.pkgpath in self.EXEMPT:
            return
        from fabric_mod_tpu.utils import knobs
        al = _aliases(mod.tree)
        os_names = al.get("os", set())
        environ_names = al.get("from:os", set()) & {"environ"}
        getenv_names = al.get("from:os", set()) & {"getenv"}
        helper_names = (al.get("from:fabric_mod_tpu.utils.env", set())
                        | self.RAW_HELPERS)

        def is_environ(node: ast.expr) -> bool:
            return (_is_module_attr(node, os_names, "environ")
                    or (isinstance(node, ast.Name)
                        and node.id in environ_names))

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                fn = node.func
                if ((isinstance(fn, ast.Attribute)
                        and fn.attr in ("get", "pop", "setdefault")
                        and is_environ(fn.value))
                        or _is_module_attr(fn, os_names, "getenv")
                        or (isinstance(fn, ast.Name)
                            and fn.id in getenv_names)) and node.args:
                    key = _str_const(node.args[0])
                    if key is not None and KNOB_RE.match(key):
                        yield self._f(
                            mod, node,
                            f"raw os.environ read of knob {key!r} — "
                            f"use fabric_mod_tpu.utils.knobs.get_*")
                elif (isinstance(fn, ast.Name)
                        and fn.id in helper_names
                        and fn.id.lstrip("_").startswith("env_")):
                    yield self._f(
                        mod, node,
                        f"{fn.id}() outside utils/ — knob parsing goes "
                        f"through utils/knobs.py (get_int/get_float)")
                elif (isinstance(fn, ast.Attribute)
                        and fn.attr in ("env_int", "env_float")):
                    yield self._f(
                        mod, node,
                        f"{fn.attr}() outside utils/ — knob parsing goes "
                        f"through utils/knobs.py (get_int/get_float)")
            elif isinstance(node, ast.Subscript) and \
                    is_environ(node.value):
                key = _str_const(node.slice)
                if key is not None and KNOB_RE.match(key):
                    yield self._f(
                        mod, node,
                        f"raw os.environ[{key!r}] — use "
                        f"fabric_mod_tpu.utils.knobs")
            elif isinstance(node, ast.Constant):
                val = node.value
                if (isinstance(val, str) and KNOB_RE.match(val)
                        and not knobs.is_declared(val)):
                    yield self._f(
                        mod, node,
                        f"undeclared knob {val!r}: declare it in "
                        f"utils/knobs.py (name/type/default/doc)")


class FaultPointRule(Rule):
    name = "fault-points"
    doc = ("faults.point(...) takes a string LITERAL declared in "
           "faults/points.py — enables arm-time validation of "
           "FMT_FAULTS plans; declared-but-unused points are flagged "
           "on whole-package runs")

    def check(self, mod, ctx):
        if mod.pkgpath.startswith("faults/"):
            return
        from fabric_mod_tpu.faults import points
        al = _aliases(mod.tree)
        faults_names = (al.get("fabric_mod_tpu.faults", set())
                        | (al.get("from:fabric_mod_tpu", set())
                           & {"faults"}) | {"faults"})
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if not _is_module_attr(node.func, faults_names, "point"):
                continue
            name = _str_const(node.args[0]) if node.args else None
            if name is None:
                yield self._f(
                    mod, node,
                    "faults.point() with a non-literal name defeats "
                    "the registry — pass a declared literal")
                continue
            ctx.fault_points_used.add(name)
            if not points.is_declared(name):
                yield self._f(
                    mod, node,
                    f"fault point {name!r} not declared in "
                    f"faults/points.py")


class SpanNameRule(Rule):
    name = "span-names"
    doc = ("tracing.span(...) takes a string LITERAL declared in "
           "observability/spannames.py — span names key the timeline "
           "sub-stages, metrics, and the Perfetto export; "
           "declared-but-unused names are flagged on whole-package "
           "runs")

    EXEMPT = {"observability/spannames.py"}
    # records spans itself (the collector's pauses): `Span(NAME, ...)`,
    # NAME a module-level string constant, is a use of that name
    TRACER = "observability/tracing.py"

    def check(self, mod, ctx):
        if mod.pkgpath in self.EXEMPT:
            return
        if mod.pkgpath == self.TRACER:
            consts = {t.id: _str_const(node.value)
                      for node in mod.tree.body
                      if isinstance(node, ast.Assign)
                      for t in node.targets if isinstance(t, ast.Name)}
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id == "Span" and node.args
                        and isinstance(node.args[0], ast.Name)
                        and consts.get(node.args[0].id) is not None):
                    yield from self._use(mod, node,
                                         consts[node.args[0].id], ctx)
            return
        al = _aliases(mod.tree)
        tracing_names = (al.get("fabric_mod_tpu.observability.tracing",
                                set())
                         | (al.get("from:fabric_mod_tpu.observability",
                                   set()) & {"tracing"}) | {"tracing"})
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if not _is_module_attr(node.func, tracing_names, "span"):
                continue
            name = _str_const(node.args[0]) if node.args else None
            if name is None:
                yield self._f(
                    mod, node,
                    "tracing.span() with a non-literal name falls out "
                    "of every timeline/metric view — pass a declared "
                    "literal")
                continue
            yield from self._use(mod, node, name, ctx)

    def _use(self, mod, node, name, ctx):
        from fabric_mod_tpu.observability import spannames
        ctx.span_names_used.add(name)
        if not spannames.is_declared(name):
            yield self._f(
                mod, node,
                f"span name {name!r} not declared in "
                f"observability/spannames.py")


class ThreadRule(Rule):
    name = "threads"
    doc = ("no bare threading.Thread/Timer in production code — use "
           "concurrency.RegisteredThread so the leak-checked teardown "
           "sweep sees every worker")

    def check(self, mod, ctx):
        if mod.pkgpath.startswith("concurrency/"):
            return
        al = _aliases(mod.tree)
        thr = al.get("threading", set())
        from_thr = al.get("from:threading", set())
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            bare = None
            for cls in ("Thread", "Timer"):
                if _is_module_attr(fn, thr, cls) or (
                        isinstance(fn, ast.Name) and fn.id == cls
                        and cls in from_thr):
                    bare = cls
            if bare:
                yield self._f(
                    mod, node,
                    f"bare threading.{bare} — use "
                    f"concurrency.RegisteredThread (leak-checked, "
                    f"named, swept at teardown)")


class LockRule(Rule):
    name = "locks"
    doc = ("no bare threading.Lock()/RLock() in production code — use "
           "concurrency.OrderedLock (ranked hierarchy) or "
           "RegisteredLock (dynamic cycle detection), or pragma with "
           "the reason ordering cannot apply")

    def check(self, mod, ctx):
        if mod.pkgpath.startswith("concurrency/"):
            return
        al = _aliases(mod.tree)
        thr = al.get("threading", set())
        from_thr = al.get("from:threading", set())
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            for cls in ("Lock", "RLock"):
                if _is_module_attr(fn, thr, cls) or (
                        isinstance(fn, ast.Name) and fn.id == cls
                        and cls in from_thr):
                    yield self._f(
                        mod, node,
                        f"bare threading.{cls}() — use "
                        f"concurrency.OrderedLock/RegisteredLock so "
                        f"lock-order cycles are caught at acquire "
                        f"time")


class ClockRule(Rule):
    name = "clocks"
    doc = ("no time.time()/time.sleep() calls inside subsystems that "
           "already have injectable clocks (retry, admission, "
           "tracing, discovery, deliver failover, soak, fakeclock) — "
           "route through the injected clock.  time.monotonic() is "
           "exempt: measuring a real duration is not scheduling")

    SCOPED = {"utils/retry.py", "utils/fakeclock.py",
              "orderer/admission.py", "observability/tracing.py",
              "gossip/discovery.py", "peer/blocksprovider.py"}
    SCOPED_PREFIXES = ("soak/",)

    def _in_scope(self, pkgpath: str) -> bool:
        return (pkgpath in self.SCOPED
                or pkgpath.startswith(self.SCOPED_PREFIXES))

    def check(self, mod, ctx):
        if not self._in_scope(mod.pkgpath):
            return
        al = _aliases(mod.tree)
        time_names = al.get("time", set())
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            for fn_name in ("time", "sleep"):
                if _is_module_attr(node.func, time_names, fn_name):
                    yield self._f(
                        mod, node,
                        f"time.{fn_name}() in a clocked subsystem — "
                        f"use the injectable clock (or pragma why "
                        f"real OS time is required here)")


class SwallowRule(Rule):
    name = "swallowed-exceptions"
    doc = ("`except Exception: pass` (or bare except) with no "
           "log/metric/re-raise swallows failures invisibly — log it, "
           "count it, or pragma why silence is the contract")

    _BROAD = {"Exception", "BaseException"}

    def check(self, mod, ctx):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = node.type is None or (
                isinstance(node.type, ast.Name)
                and node.type.id in self._BROAD)
            if not broad:
                continue
            if len(node.body) == 1 and isinstance(node.body[0], ast.Pass):
                yield self._f(
                    mod, node,
                    "swallowed exception: except "
                    f"{'Exception' if node.type is not None else ''}"
                    ": pass with no log/metric/re-raise")


class JaxHotPathRule(Rule):
    name = "jax-hot-path"
    doc = ("host syncs (.item(), np.asarray/np.array of a "
           "freshly-computed value, jax.device_get, "
           "block_until_ready) flagged inside the device-dispatch "
           "files (bccsp/tpu.py, ops/*, parallel/*) — a sync inside "
           "the dispatch path serializes the pipeline; pragma the "
           "sanctioned resolve seams")

    SCOPED = {"bccsp/tpu.py"}
    SCOPED_PREFIXES = ("ops/", "parallel/")

    def _in_scope(self, pkgpath: str) -> bool:
        return (pkgpath in self.SCOPED
                or pkgpath.startswith(self.SCOPED_PREFIXES))

    def check(self, mod, ctx):
        if not self._in_scope(mod.pkgpath):
            return
        al = _aliases(mod.tree)
        np_names = (al.get("numpy", set()) | {"np"})
        jax_names = al.get("jax", set())
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "item" \
                    and not node.args:
                yield self._f(
                    mod, node,
                    ".item() is a device->host sync — keep verdicts "
                    "on device or pragma the resolve seam")
            elif (isinstance(fn, ast.Attribute)
                    and fn.attr in ("asarray", "array")
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in np_names
                    and node.args
                    and isinstance(node.args[0], ast.Call)):
                yield self._f(
                    mod, node,
                    f"np.{fn.attr}(<call>) syncs a freshly-computed "
                    f"device value to host — pragma if this is a "
                    f"sanctioned resolve/fallback seam")
            elif _is_module_attr(fn, jax_names, "device_get"):
                yield self._f(
                    mod, node,
                    "jax.device_get is a host sync — pragma the "
                    "sanctioned resolve seam")
            elif isinstance(fn, ast.Attribute) and \
                    fn.attr == "block_until_ready":
                yield self._f(
                    mod, node,
                    "block_until_ready() stalls dispatch — pragma if "
                    "this is a bench/trace seam")


ALL_RULES: List[Rule] = [
    KnobRule(), FaultPointRule(), SpanNameRule(), ThreadRule(),
    LockRule(), ClockRule(), SwallowRule(), JaxHotPathRule(),
]


class PragmaRuleDoc(Rule):
    """Placeholder for --list-rules: pragma findings are emitted by the
    engine's pragma parser, not an AST visitor."""
    name = "pragma"
    doc = ("fmtlint pragmas must be well-formed: "
           "'fmtlint: allow[rule] -- reason' (as a comment) with a "
           "known rule name and a non-empty reason")

    def check(self, mod, ctx):
        return ()


LISTED_RULES: List[Rule] = ALL_RULES + [PragmaRuleDoc()]


def project_checks(ctx: ProjectContext) -> List[Finding]:
    """Whole-tree cross-checks: a registry entry nothing references is
    dead documentation — drift in the other direction."""
    from fabric_mod_tpu.faults import points
    from fabric_mod_tpu.observability import spannames
    findings: List[Finding] = []
    for name in sorted(points.DECLARED_POINTS - ctx.fault_points_used):
        findings.append(Finding(
            "fabric_mod_tpu/faults/points.py", 1, "fault-points",
            f"declared fault point {name!r} has no faults.point() "
            f"seam in production code"))
    for name in sorted(spannames.DECLARED_SPANS - ctx.span_names_used):
        findings.append(Finding(
            "fabric_mod_tpu/observability/spannames.py", 1,
            "span-names",
            f"declared span {name!r} has no tracing.span() call in "
            f"production code"))
    return findings
