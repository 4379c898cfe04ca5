"""Registration-site extraction for the registry-drift rule.

Reads the four registries *statically* (AST, never import) so the
checker works on a broken tree and never executes runtime code:

* metric names — every string element of the ``*_METRIC_NAMES`` lists in
  ``emqx_tpu/observe/metrics.py`` (the fixed-at-boot counter table);
* config keys — the literal keys of the ``SCHEMA`` dict in
  ``emqx_tpu/config.py``;
* fault-injection points — the ``POINTS`` tuple in
  ``emqx_tpu/faultinject.py`` (the scenario-table vocabulary);
* hook points — the ``HOOK_POINTS`` list in
  ``emqx_tpu/broker/hooks.py`` (a typo'd ``hooks.add``/``run`` name
  silently never fires — the chain dispatch is by exact string);
* histogram names — the ``HIST_NAMES`` list in
  ``emqx_tpu/observe/hist.py`` (a typo'd ``.hist("...")`` lookup
  raises KeyError at a cold setup site nothing may exercise);
* flight-recorder dump reasons — the ``DUMP_REASONS`` tuple in
  ``emqx_tpu/observe/flightrec.py`` (an undeclared reason raises at
  the trigger site — which is the breaker-trip path).
"""

from __future__ import annotations

import ast
import os
from typing import Optional, Set

__all__ = ["Registries"]


def _parse(path: str) -> ast.Module:
    with open(path, "r", encoding="utf-8") as f:
        return ast.parse(f.read(), filename=path)


def _str_elements(node: ast.AST) -> Set[str]:
    return {
        el.value
        for el in ast.walk(node)
        if isinstance(el, ast.Constant) and isinstance(el.value, str)
    }


class Registries:
    """The project's four name registries, extracted once per run."""

    def __init__(self, metric_names: Set[str], config_keys: Set[str],
                 fault_points: Set[str],
                 hook_points: Optional[Set[str]] = None,
                 hist_names: Optional[Set[str]] = None,
                 dump_reasons: Optional[Set[str]] = None,
                 stage_names: Optional[Set[str]] = None) -> None:
        self.metric_names = metric_names
        self.config_keys = config_keys
        self.fault_points = fault_points
        self.hook_points = hook_points if hook_points is not None else set()
        self.hist_names = hist_names if hist_names is not None else set()
        self.dump_reasons = (dump_reasons if dump_reasons is not None
                             else set())
        self.stage_names = (stage_names if stage_names is not None
                            else set())

    @classmethod
    def load(cls, package_root: Optional[str] = None) -> "Registries":
        """Extract from the live tree.  ``package_root`` is the
        ``emqx_tpu`` package directory (defaults to the one this module
        ships in)."""
        if package_root is None:
            package_root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
        return cls(
            metric_names=cls._metric_names(
                os.path.join(package_root, "observe", "metrics.py")),
            config_keys=cls._config_keys(
                os.path.join(package_root, "config.py")),
            fault_points=cls._fault_points(
                os.path.join(package_root, "faultinject.py")),
            hook_points=cls._hook_points(
                os.path.join(package_root, "broker", "hooks.py")),
            hist_names=cls._named_list(
                os.path.join(package_root, "observe", "hist.py"),
                "HIST_NAMES"),
            dump_reasons=cls._named_list(
                os.path.join(package_root, "observe", "flightrec.py"),
                "DUMP_REASONS"),
            stage_names=cls._named_list(
                os.path.join(package_root, "observe", "flightrec.py"),
                "STAGES"),
        )

    @staticmethod
    def _named_list(path: str, varname: str) -> Set[str]:
        """String elements of a top-level ``varname = [...]`` (or
        tuple) assignment — the HIST_NAMES / DUMP_REASONS / STAGES
        shape."""
        for node in _parse(path).body:
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                if any(isinstance(t, ast.Name) and t.id == varname
                       for t in targets) and node.value is not None:
                    names = _str_elements(node.value)
                    if names:
                        return names
        raise RuntimeError(f"no {varname} found in {path}")

    @staticmethod
    def _metric_names(path: str) -> Set[str]:
        names: Set[str] = set()
        for node in _parse(path).body:
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Name) \
                            and t.id.endswith("METRIC_NAMES") \
                            and node.value is not None:
                        names |= _str_elements(node.value)
        if not names:
            raise RuntimeError(f"no *_METRIC_NAMES lists found in {path}")
        return names

    @staticmethod
    def _config_keys(path: str) -> Set[str]:
        for node in _parse(path).body:
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                if any(isinstance(t, ast.Name) and t.id == "SCHEMA"
                       for t in targets) and node.value is not None:
                    keys = {
                        k.value for k in node.value.keys  # type: ignore
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)
                    }
                    if keys:
                        return keys
        raise RuntimeError(f"no SCHEMA dict found in {path}")

    @staticmethod
    def _hook_points(path: str) -> Set[str]:
        for node in _parse(path).body:
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                if any(isinstance(t, ast.Name) and t.id == "HOOK_POINTS"
                       for t in targets) and node.value is not None:
                    points = _str_elements(node.value)
                    if points:
                        return points
        raise RuntimeError(f"no HOOK_POINTS list found in {path}")

    @staticmethod
    def _fault_points(path: str) -> Set[str]:
        for node in _parse(path).body:
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                if any(isinstance(t, ast.Name) and t.id == "POINTS"
                       for t in targets) and node.value is not None:
                    points = _str_elements(node.value)
                    if points:
                        return points
        raise RuntimeError(f"no POINTS tuple found in {path}")
