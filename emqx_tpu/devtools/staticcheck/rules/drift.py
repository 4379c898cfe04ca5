"""registry-drift: names used must exist where they are registered.

Four fixed-vocabulary registries back the observability/config/chaos
surfaces; a typo'd name at a call site either raises at runtime on a
cold path nothing exercises (metrics/config) or silently never fires
(faultinject points, alarm deactivation).  This rule cross-checks every
*literal* name at a call site against its registration site:

* ``metrics.inc/dec/set("name")`` → a ``*_METRIC_NAMES`` list in
  ``observe/metrics.py``;
* ``cfg.get/put("dotted.key")`` → the ``SCHEMA`` dict in ``config.py``;
* ``_injector.act/check("point")`` → ``faultinject.POINTS``;
* ``hooks.add/run/run_fold/has/delete("point", ...)`` → the
  ``HOOK_POINTS`` list in ``broker/hooks.py`` — the chain dispatch is
  by exact string, so a typo'd point name registers a callback (or
  runs a chain) that nothing ever fires;
* ``hooks.run("message.dropped", (msg, "reason"))`` → the derived
  counter ``messages.dropped.<reason>`` must be registered (after the
  ``wiring.py`` remap) — ``Metrics.inc_msg_dropped`` guards the detail
  key with ``in self._c`` and silently under-counts on a typo;
* ``alarms.deactivate("name")`` → some ``alarms.activate`` with a
  matching name (f-string prefixes compared prefix-wise), anywhere in
  the tree — a deactivate that can never match leaks the alarm active
  forever;
* **dead seams** (the reverse direction): every point a
  ``faultinject`` module declares in ``POINTS`` must have ≥1 literal
  ``_injector.act/check`` gate somewhere in the tree — a
  registered-but-never-fired chaos point is a hole in the chaos
  story: scenarios can target it, but nothing ever trips;
* ``hists.hist("name")`` → the ``HIST_NAMES`` list in
  ``observe/hist.py`` — ``HistSet.hist`` raises KeyError on a typo,
  at a COLD setup site nothing in tier-1 may exercise;
* ``flightrec.dump("reason")`` → the ``DUMP_REASONS`` tuple in
  ``observe/flightrec.py`` — an undeclared reason raises at the
  trigger site, which is the breaker-trip / escalation path;
* ``stage_span("stage", hists, ring)`` → the ``STAGES`` tuple in
  ``observe/flightrec.py`` AND ``obs.stage.<stage>`` in ``HIST_NAMES``
  — ``observe/span.py`` resolves a stage's ring id and its histogram
  from the one name, at a set-up site that may run only with the
  device attached.

Dynamic names (f-strings, variables) are skipped except for the alarm
prefix check; the registries are extracted statically (``registry.py``).
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional, Tuple

from ..core import FileContext, Finding, Rule, str_arg, terminal_name
from ..registry import Registries

__all__ = ["RegistryDrift"]

#: registry-name shape: lowercase dotted identifiers ("broker.fanout.x")
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")

_METRIC_METHODS = {"inc", "dec", "set", "get"}
_CONFIG_METHODS = {"get", "put"}
_FAULT_METHODS = {"act", "check"}
_ALARM_METHODS = {"activate", "deactivate"}
_HOOK_METHODS = {"add", "run", "run_fold", "has", "delete"}
_HIST_METHODS = {"hist"}
_DUMP_METHODS = {"dump"}

#: drop reasons observe/wiring.py rewrites before deriving the counter
#: name (mirrors ``on_dropped``: shared_no_available counts against
#: no_subscribers, matching the reference's accounting)
_DROP_REASON_REMAP = {"shared_no_available": "no_subscribers"}


def _receiver(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Attribute):
        return terminal_name(node.func.value)
    return None


class RegistryDrift(Rule):
    name = "registry-drift"
    description = "name not present at its registration site"
    node_types = (ast.Call,)

    #: files that ARE the registration sites (their internal dynamic
    #: key construction is the registry, not a use of it)
    _REGISTRY_FILES = (
        "emqx_tpu/observe/metrics.py", "emqx_tpu/config.py",
        "emqx_tpu/faultinject.py", "emqx_tpu/broker/hooks.py",
        "emqx_tpu/observe/hist.py", "emqx_tpu/observe/flightrec.py",
    )

    def __init__(self, registries: Optional[Registries] = None) -> None:
        self._registries = registries
        self._project = None

    @property
    def registries(self) -> Registries:
        if self._registries is None:
            self._registries = Registries.load()
        return self._registries

    def begin_run(self) -> None:
        self._project = None

    def begin_project(self, project) -> None:
        # alarm activate/deactivate pairing reads the pass-1 summaries
        # (so it stays correct when per-file walks are cache-skipped)
        self._project = project

    def visit(self, node: ast.Call, ctx: FileContext) -> None:
        if ctx.relpath in self._REGISTRY_FILES:
            return
        func = node.func
        if terminal_name(func) == "stage_span":
            self._check_stage(node, ctx)
            return
        if not isinstance(func, ast.Attribute):
            return
        method = func.attr
        recv = _receiver(node)
        if recv is None:
            return
        if method in _METRIC_METHODS and (
                "metric" in recv or recv == "m"):
            self._check_metric(node, ctx)
        elif method in _CONFIG_METHODS and recv in ("cfg", "config"):
            self._check_config(node, ctx)
        elif method in _FAULT_METHODS and "injector" in recv:
            self._check_fault(node, ctx)
        elif method in _HOOK_METHODS and recv == "hooks":
            self._check_hook_point(node, ctx)
            if method == "run":
                self._check_drop_reason(node, ctx)
        elif method in _HIST_METHODS and "hist" in recv:
            self._check_hist(node, ctx)
        elif method in _DUMP_METHODS and "flightrec" in recv:
            self._check_dump_reason(node, ctx)

    # ------------------------------------------------------------------

    def _check_metric(self, node: ast.Call, ctx: FileContext) -> None:
        name = str_arg(node)
        if name is None or not _NAME_RE.match(name):
            return
        if name not in self.registries.metric_names:
            ctx.report(
                self.name, node,
                f"metric {name!r} is not registered in any "
                "*_METRIC_NAMES list (emqx_tpu/observe/metrics.py) — "
                "Metrics.inc would raise KeyError at runtime",
            )

    def _check_config(self, node: ast.Call, ctx: FileContext) -> None:
        key = str_arg(node)
        if key is None or not _NAME_RE.match(key):
            return
        if key not in self.registries.config_keys:
            ctx.report(
                self.name, node,
                f"config key {key!r} is not in the SCHEMA dict "
                "(emqx_tpu/config.py) — the read always returns the "
                "fallback, silently ignoring configuration",
            )

    def _check_fault(self, node: ast.Call, ctx: FileContext) -> None:
        point = str_arg(node)
        if point is None:
            return
        if point not in self.registries.fault_points:
            ctx.report(
                self.name, node,
                f"fault-injection point {point!r} is not declared in "
                "faultinject.POINTS — no scenario can ever target it "
                "(FaultInjector rejects unknown points)",
            )

    def _check_hook_point(self, node: ast.Call, ctx: FileContext) -> None:
        name = str_arg(node)
        if name is None or not _NAME_RE.match(name):
            return
        if name not in self.registries.hook_points:
            ctx.report(
                self.name, node,
                f"hook point {name!r} is not in HOOK_POINTS "
                "(emqx_tpu/broker/hooks.py) — the chain dispatches by "
                "exact string, so this callback/run can never pair "
                "with the rest of the tree",
            )

    def _check_hist(self, node: ast.Call, ctx: FileContext) -> None:
        name = str_arg(node)
        if name is None or not _NAME_RE.match(name):
            return
        if name not in self.registries.hist_names:
            ctx.report(
                self.name, node,
                f"histogram {name!r} is not registered in HIST_NAMES "
                "(emqx_tpu/observe/hist.py) — HistSet.hist raises "
                "KeyError at this (cold, setup-time) lookup",
            )

    def _check_stage(self, node: ast.Call, ctx: FileContext) -> None:
        stage = str_arg(node)
        if stage is None:
            return
        if stage not in self.registries.stage_names:
            ctx.report(
                self.name, node,
                f"stage {stage!r} is not declared in STAGES "
                "(emqx_tpu/observe/flightrec.py) — stage_span raises "
                "ValueError at this (cold, setup-time) lookup",
            )
        elif f"obs.stage.{stage}" not in self.registries.hist_names:
            ctx.report(
                self.name, node,
                f"stage {stage!r} has no histogram "
                f"'obs.stage.{stage}' in HIST_NAMES (emqx_tpu/observe/"
                "hist.py) — stage_span raises KeyError wherever "
                "histograms are on",
            )

    def _check_dump_reason(self, node: ast.Call, ctx: FileContext) -> None:
        reason = str_arg(node)
        if reason is None:
            return
        if reason not in self.registries.dump_reasons:
            ctx.report(
                self.name, node,
                f"flight-recorder dump reason {reason!r} is not "
                "declared in DUMP_REASONS (emqx_tpu/observe/"
                "flightrec.py) — FlightRecorder.dump raises at the "
                "trigger site",
            )

    def _check_drop_reason(self, node: ast.Call, ctx: FileContext) -> None:
        hook = str_arg(node)
        if hook not in ("message.dropped", "delivery.dropped") \
                or len(node.args) < 2:
            return
        args = node.args[1]
        if not isinstance(args, ast.Tuple) or len(args.elts) < 2:
            return
        reason_node = args.elts[1]
        if not (isinstance(reason_node, ast.Constant)
                and isinstance(reason_node.value, str)):
            return
        reason = _DROP_REASON_REMAP.get(
            reason_node.value, reason_node.value)
        family = ("messages.dropped" if hook == "message.dropped"
                  else "delivery.dropped")
        derived = f"{family}.{reason}"
        if derived not in self.registries.metric_names:
            ctx.report(
                self.name, node,
                f"drop reason {reason_node.value!r} derives metric "
                f"{derived!r}, which is not registered in "
                "observe/metrics.py — inc_msg_dropped silently skips "
                "the detail counter (only the total moves)",
            )

    def finalize(self) -> List[Finding]:
        """Alarm activate/deactivate pairing over the whole project:
        a deactivate whose name can never match any activate leaks the
        alarm active forever.  Reads the pass-1 summaries so the check
        stays whole-program even when per-file walks were served from
        the analysis cache."""
        if self._project is None:
            return []
        activations: List[Tuple[str, bool]] = []
        registry_files = set(self._REGISTRY_FILES)
        deacts = []
        for s in self._project.modules.values():
            if s.relpath in registry_files:
                continue
            activations.extend(s.alarm_acts)
            for name, is_prefix, line, col, qualname in s.alarm_deacts:
                deacts.append((name, is_prefix, s.relpath, line, col,
                               qualname))
        out: List[Finding] = []
        for name, is_prefix, relpath, line, col, qualname in deacts:
            if any(self._alarm_match(name, is_prefix, act, act_pfx)
                   for act, act_pfx in activations):
                continue
            out.append(Finding(
                rule=self.name, path=relpath, line=line, col=col,
                message=(
                    f"alarm {name!r} is deactivated but never "
                    "activated anywhere in the tree — the deactivate "
                    "can never match and the alarm name has drifted"
                ),
                context=qualname,
            ))
        out.extend(self._dead_seams())
        return out

    def _dead_seams(self) -> List[Finding]:
        """Declared-but-never-gated fault points, summary-driven: the
        check only engages when a scanned module DECLARES points (the
        fixture trees that don't ship a faultinject module stay
        silent), and the use set is the project-wide union of literal
        ``.act``/``.check`` gates from pass 1."""
        declared: List[Tuple[str, str, int]] = []
        used = set()
        for s in self._project.modules.values():
            declared.extend((p, s.relpath, line)
                            for p, line in s.fault_points)
            used.update(s.fault_uses)
        out: List[Finding] = []
        for point, relpath, line in sorted(declared):
            if point in used:
                continue
            out.append(Finding(
                rule=self.name, path=relpath, line=line, col=0,
                message=(
                    f"fault-injection point {point!r} is declared in "
                    "faultinject.POINTS but no call site ever gates "
                    "on it — a registered-but-never-fired chaos point "
                    "is a hole in the chaos story; wire an "
                    "_injector.act/check seam or drop the point"
                ),
                context="<module>",
            ))
        return out

    @staticmethod
    def _alarm_match(deact: str, deact_pfx: bool, act: str,
                     act_pfx: bool) -> bool:
        if deact_pfx or act_pfx:
            shorter = min(len(deact), len(act))
            return deact[:shorter] == act[:shorter]
        return deact == act
