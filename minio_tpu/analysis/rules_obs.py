"""Tracing-discipline rule: ``span``.

Trace spans (minio_tpu/obs) set a contextvar on entry and publish +
reset it on exit; a ``span_start``-style call with no guaranteed
``finally`` would leak the context token and corrupt every tree that
request touches. The only supported way to open a span is therefore the
context-manager API::

    with obs.span(obs.TYPE_STORAGE, "readfile", drive=ep) as sp:
        ...

``obs.phase(...)`` (the always-on phase clock) opens the same span when
someone subscribes, and enters a profiler annotation besides: it is held
to the same discipline.

This rule flags, everywhere outside ``obs/`` itself:

- any ``obs.span(...)`` / ``trace.span(...)`` / imported ``span(...)``
  call — and the same for ``phase`` — that is not the context expression
  of a ``with`` (or ``async with``) item — including
  ``span(...).__enter__()`` trickery;
- direct ``Span(...)`` / ``Phase(...)`` construction and any ``span_start``/``start_span``
  call (no such API exists; if one appears, it is a bug by definition).
"""

from __future__ import annotations

import ast
from typing import Iterator

from .core import Finding, dotted_name, rule

_ORPHAN_NAMES = {"span_start", "start_span"}
_OPENERS = {"span", "phase"}  # context-manager-only factories of obs
_CLASSES = {"Span", "Phase"}


def _is_span_call(node: ast.Call, imported: set[str]) -> bool:
    name = dotted_name(node.func)
    if name is None:
        return False
    if name in _OPENERS:
        return name in imported
    parts = name.split(".")
    return parts[-1] in _OPENERS and parts[-2] in ("obs", "trace")


def _openers_imported_from_obs(tree: ast.AST) -> set[str]:
    found: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
            node.module == "obs" or node.module.endswith(".obs")
            or node.module.endswith("obs.trace")
        ):
            found |= {a.name for a in node.names} & _OPENERS
    return found


@rule("span")
def check_span_discipline(tree: ast.AST, ctx) -> Iterator[Finding]:
    if ctx.relpath.startswith("obs/"):
        return  # the span implementation itself
    imported = _openers_imported_from_obs(tree)
    with_exprs: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                with_exprs.add(id(item.context_expr))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func) or ""
        short = name.split(".")[-1]
        if short in _ORPHAN_NAMES:
            yield Finding(
                ctx.path, node.lineno, "span",
                f"{name}(): open spans via the context-manager API "
                "(`with obs.span(...)`) — a start without a guaranteed "
                "finally leaks the trace context token",
            )
            continue
        if short in _CLASSES and (
            name == short or name.endswith(f"obs.{short}")
            or name.endswith(f"trace.{short}")
        ):
            yield Finding(
                ctx.path, node.lineno, "span",
                f"direct {short} construction: use obs.{short.lower()}(...)"
                + (", which is zero-cost when tracing is idle"
                   if short == "Span" else ""),
            )
            continue
        if _is_span_call(node, imported) and id(node) not in with_exprs:
            yield Finding(
                ctx.path, node.lineno, "span",
                f"{name}(...) outside a `with` statement: spans must be "
                "opened via the context-manager API so the exit (publish "
                "+ contextvar reset) always runs",
            )
