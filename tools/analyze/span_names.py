"""Span/hop-name cross-check pass (ISSUE 16 satellite).

Every LITERAL span/hop name opened at a tracer call site — the stage
and request tracers' ``<tracer>.span("name", ...)`` and
``<tracer>.span_in(ctx, "name", ...)`` (runtime/tracing.py), the job
tracer's ``JOB_TRACER.hop/note("name",
...)`` (runtime/job_trace.py), and the offload service's job-span
recorder ``self._trace(job, "name", ...)`` — must be DOCUMENTED in
README.md's '### Span-name table', and every table row must still have
a matching call site (both directions — the same discipline the event
and metric tables get). Unlike the events pass, DYNAMIC names are
legitimate here (``f"client.{op}"``, ``f"rpc.{code}"``, the job
tracer's ``f"{kind}.nested"`` degradation hop): the span vocabulary is
intentionally parameterized by op/code, so non-literal call sites are
simply exempt from the table check, never flagged.
"""

import re

from . import Finding, Repo, register

# literal-name span/hop call sites; group(1) = the name. Four shapes:
#   <tracer>.span("name"          stage + request tracers
#   <tracer>.hop("name" / .note("name"    the job tracer
#   <tracer>.span_in(ctx, "name"  a request span under a given context
#   self._trace(job, "name"       the offload service's job recorder
_SPAN_RE = re.compile(r"\.(?:span|hop|note)\(\s*\"([^\"]+)\"")
_SECOND_ARG_RE = re.compile(
    r"(?:\b_trace|\.span_in)\(\s*\w+\s*,\s*\"([^\"]+)\"")


def source_span_names(repo: Repo) -> set:
    names = set()
    for sf in repo.package_files():
        names.update(_SPAN_RE.findall(sf.text))
        names.update(_SECOND_ARG_RE.findall(sf.text))
    return names


def readme_span_rows(repo: Repo) -> list:
    """Span names from README's '### Span-name table': every backticked
    token in each row's first cell, '/'-alternations split (rows group
    related names, e.g. the learn hops)."""
    rows = []
    for cells in repo.readme_table_rows("Span-name table"):
        for span in re.findall(r"`([^`]+)`", cells[0]):
            for variant in span.split("/"):
                variant = variant.strip()
                if variant:
                    rows.append(variant)
    return rows


@register("span_names")
def run(repo: Repo = None) -> list:
    repo = repo or Repo()
    src = source_span_names(repo)
    rows = readme_span_rows(repo)
    out = []
    if src and not rows:
        return [Finding(
            "span_names", "", 0,
            "README.md has no '### Span-name table' section (or it is "
            "empty) — every literal tracer span/hop name must be "
            "documented there", key="no-table")]
    documented = set(rows)
    for name in sorted(src):
        if name not in documented:
            out.append(Finding(
                "span_names", "", 0,
                f"span/hop {name!r} is opened in source but missing "
                f"from README.md's Span-name table", key=f"undoc:{name}"))
    for name in sorted(documented):
        if name not in src:
            out.append(Finding(
                "span_names", "", 0,
                f"README Span-name table row {name!r} has no matching "
                f"tracer call site in source — delete the row or "
                f"restore the span", key=f"stale-row:{name}"))
    return out
