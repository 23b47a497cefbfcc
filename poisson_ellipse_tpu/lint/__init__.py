"""tpulint — a JAX/Pallas-aware static-analysis pass for the kernel zoo.

The repo carries seven PCG engine variants whose failure modes (silent
dtype drift, traced-value branches, host syncs in hot loops, per-call
recompilation, VMEM-overflowing Pallas tiles) the reference project
caught by hand across five rewrites. tpulint catches them mechanically:

    python -m poisson_ellipse_tpu.lint              # paths from pyproject
    python -m poisson_ellipse_tpu.lint poisson_ellipse_tpu/ops --statistics

Rules are TPU001–TPU020 (see :mod:`.rules`); any finding can be waived
in place with a trailing or preceding-line comment::

    x = jnp.zeros(n, jnp.float64)  # tpulint: disable=TPU001

Configuration lives in ``pyproject.toml`` under ``[tool.tpulint]`` and
is shared by this CLI and the pytest gate (``tests/test_lint_clean.py``),
so "lints clean" means the same thing on a laptop and in CI.

Public API: :func:`load_config`, :func:`lint_paths`, :func:`lint_file`,
:func:`lint_source` (the test harness entry), :data:`RULES`, plus the
hygiene surfaces: :func:`audit_suppressions`/:func:`audit_paths` (stale
``disable`` annotations) and :func:`apply_baseline` (accept-then-ratchet
``--baseline`` files).
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import os
import tomllib
from typing import Iterable, Optional

from poisson_ellipse_tpu.lint.report import Finding, ParseError
from poisson_ellipse_tpu.lint.rules import RULES, LintConfig
from poisson_ellipse_tpu.lint.visitor import (
    Module,
    _iter_suppression_comments,
)

__all__ = [
    "AUDIT_CODE",
    "Finding",
    "LintConfig",
    "ParseError",
    "RULES",
    "apply_baseline",
    "audit_paths",
    "audit_suppressions",
    "finding_key",
    "lint_file",
    "lint_paths",
    "lint_source",
    "load_config",
]


# -- configuration ----------------------------------------------------------


def _read_pyproject(path: str) -> dict:
    with open(path, "rb") as f:
        return tomllib.load(f)


def load_config(root: Optional[str] = None) -> LintConfig:
    """The shared CLI/pytest-gate configuration.

    ``root`` is the directory holding ``pyproject.toml``; defaults to the
    repo root two levels above this package. A missing file or table
    yields the built-in defaults, so the linter works on any checkout.
    """
    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    pyproject = os.path.join(root, "pyproject.toml")
    table: dict = {}
    if os.path.exists(pyproject):
        table = _read_pyproject(pyproject).get("tool", {}).get("tpulint", {})
    cfg = LintConfig()
    select = table.get("select")
    ignore = table.get("ignore", [])
    unknown = (
        frozenset(c.upper() for c in (select or []))
        | frozenset(c.upper() for c in ignore)
    ) - RULES.keys()
    if unknown:
        # mirror the CLI's check: a typo'd code in pyproject must not
        # silently weaken (select) or widen (ignore) the gate
        raise SystemExit(
            f"[tool.tpulint] names unknown rule code(s): "
            f"{', '.join(sorted(unknown))} (known: {', '.join(sorted(RULES))})"
        )
    return dataclasses.replace(
        cfg,
        paths=tuple(table.get("paths", cfg.paths)),
        exclude=tuple(table.get("exclude", cfg.exclude)),
        select=frozenset(select) if select else None,
        ignore=frozenset(ignore),
        per_path_ignores={
            pat: tuple(codes)
            for pat, codes in table.get("per-path-ignores", {}).items()
        },
        min_donate_params=table.get(
            "min-donate-params", cfg.min_donate_params
        ),
        jit_factory_patterns=tuple(
            table.get("jit-factory-patterns", cfg.jit_factory_patterns)
        ),
        assumed_itemsize=table.get("assumed-itemsize", cfg.assumed_itemsize),
        reduction_roots=tuple(
            table.get("reduction-roots", cfg.reduction_roots)
        ),
        host_sync_fns=tuple(
            table.get("host-sync-fns", cfg.host_sync_fns)
        ),
        reraise_fns=tuple(
            table.get("reraise-fns", cfg.reraise_fns)
        ),
        aot_warmup_fns=tuple(
            table.get("aot-warmup-fns", cfg.aot_warmup_fns)
        ),
        retry_backoff_fns=tuple(
            table.get("retry-backoff-fns", cfg.retry_backoff_fns)
        ),
        loop_solver_fns=tuple(
            table.get("loop-solver-fns", cfg.loop_solver_fns)
        ),
        implicit_solver_fns=tuple(
            table.get("implicit-solver-fns", cfg.implicit_solver_fns)
        ),
        mixed_accum_fns=tuple(
            table.get("mixed-accum-fns", cfg.mixed_accum_fns)
        ),
        tunable_fns=tuple(
            table.get("tunable-fns", cfg.tunable_fns)
        ),
        collective_modules=tuple(
            table.get("collective-modules", cfg.collective_modules)
        ),
    )


# -- running ----------------------------------------------------------------


def _norm(path: str) -> str:
    return path.replace(os.sep, "/")


def _path_ignored_codes(path: str, config: LintConfig) -> frozenset[str]:
    codes: set[str] = set()
    norm = _norm(path)
    for pattern, pat_codes in config.per_path_ignores.items():
        # patterns are repo-relative; the leading-`*/` retry makes them
        # match when the runner was handed absolute paths (pytest gate)
        if (
            fnmatch.fnmatch(norm, pattern)
            or fnmatch.fnmatch(norm, f"*/{pattern}")
            or fnmatch.fnmatch(os.path.basename(norm), pattern)
        ):
            codes.update(c.upper() for c in pat_codes)
    return frozenset(codes)


def _active_rules(config: LintConfig, extra_ignore: frozenset[str] = frozenset()):
    for code, rule in sorted(RULES.items()):
        if config.select is not None and code not in config.select:
            continue
        if code in config.ignore or code in extra_ignore:
            continue
        yield rule


def lint_source(
    source: str,
    path: str = "<snippet>",
    config: Optional[LintConfig] = None,
) -> list[Finding]:
    """Lint a source string — the fixture-snippet entry the tests use."""
    config = config or LintConfig()
    module = Module(path, source)
    findings: list[Finding] = []
    for rule in _active_rules(config, _path_ignored_codes(path, config)):
        for f in rule.check(module, config):
            if not module.suppressed(f.line, f.code):
                findings.append(f)
    return sorted(findings)


def lint_file(path: str, config: Optional[LintConfig] = None) -> list[Finding]:
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path=path, config=config)


def _iter_py_files(paths: Iterable[str], config: LintConfig):
    for path in paths:
        if os.path.isfile(path):
            yield path
            continue
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = sorted(
                d for d in dirnames if d not in ("__pycache__", ".git")
            )
            for name in sorted(filenames):
                if not name.endswith(".py"):
                    continue
                full = os.path.join(dirpath, name)
                if any(
                    fnmatch.fnmatch(_norm(full), pat)
                    for pat in config.exclude
                ):
                    continue
                yield full


def lint_paths(
    paths: Iterable[str],
    config: Optional[LintConfig] = None,
) -> tuple[list[Finding], list[ParseError]]:
    """Lint files/trees; returns (findings, parse errors), both sorted."""
    config = config or LintConfig()
    paths = list(paths)
    findings: list[Finding] = []
    errors: list[ParseError] = []
    for path in paths:
        if not os.path.exists(path):
            # a typo'd path must not read as "lints clean"
            errors.append(ParseError(path=path, message="no such file or directory"))
    for path in _iter_py_files(paths, config):
        try:
            findings.extend(lint_file(path, config))
        except (SyntaxError, ValueError, UnicodeDecodeError) as e:
            errors.append(ParseError(path=path, message=str(e)))
        except OSError as e:
            errors.append(ParseError(path=path, message=str(e)))
    return sorted(findings), sorted(errors, key=lambda e: e.path)


# -- suppression audit -------------------------------------------------------

# The audit's pseudo-code: findings about the *annotations*, not the
# linted source, so it lives outside RULES (select/ignore never touch
# it) and stays hyphen-free so the suppression-comment grammar could
# address it.
AUDIT_CODE = "TPU000"


def audit_suppressions(
    source: str,
    path: str = "<snippet>",
    config: Optional[LintConfig] = None,
) -> list[Finding]:
    """Report ``# tpulint: disable=...`` annotations that suppress
    nothing — the annotation ratchet.

    Every active rule is re-run WITHOUT suppression filtering; a
    disable code (or ``all``) whose covered line carries no matching
    raw finding is stale: it reads as a waiver for a hazard that no
    longer exists, and it would silently swallow the next genuine
    finding that lands on that line. Codes whose rules are not active
    under ``config`` (select/ignore/per-path) are skipped — the audit
    cannot judge them; codes unknown to the registry are always flagged
    (they never suppressed anything).
    """
    config = config or LintConfig()
    module = Module(path, source)
    active = list(_active_rules(config, _path_ignored_codes(path, config)))
    active_codes = {r.code for r in active}
    fired_by_line: dict[int, set[str]] = {}
    for rule in active:
        for f in rule.check(module, config):
            fired_by_line.setdefault(f.line, set()).add(f.code.upper())
    out: list[Finding] = []
    for lineno, standalone, codes in _iter_suppression_comments(source):
        covered = {lineno}
        if standalone:  # standalone: covers the line below too
            covered.add(lineno + 1)
        fired: set[str] = set()
        for n in covered:
            fired |= fired_by_line.get(n, set())
        for code in sorted(codes):
            if code == "ALL":
                if not fired:
                    out.append(Finding(
                        path=path, line=lineno, col=1, code=AUDIT_CODE,
                        message="unused suppression: `disable=all` "
                        "covers no finding — remove the annotation",
                    ))
                continue
            if code not in RULES:
                out.append(Finding(
                    path=path, line=lineno, col=1, code=AUDIT_CODE,
                    message=f"unused suppression: `disable={code}` names "
                    "no registered rule — it has never suppressed "
                    "anything (typo?)",
                ))
                continue
            if code not in active_codes:
                continue  # rule not running here: nothing to judge
            if code not in fired:
                out.append(Finding(
                    path=path, line=lineno, col=1, code=AUDIT_CODE,
                    message=f"unused suppression: `disable={code}` "
                    "matches no finding on the line it covers — the "
                    "hazard is gone; remove the annotation",
                ))
    return sorted(out)


def audit_paths(
    paths: Iterable[str],
    config: Optional[LintConfig] = None,
) -> tuple[list[Finding], list[ParseError]]:
    """:func:`audit_suppressions` over files/trees — same walking,
    exclusion and error contract as :func:`lint_paths`."""
    config = config or LintConfig()
    paths = list(paths)
    findings: list[Finding] = []
    errors: list[ParseError] = []
    for path in paths:
        if not os.path.exists(path):
            errors.append(
                ParseError(path=path, message="no such file or directory")
            )
    for path in _iter_py_files(paths, config):
        try:
            with open(path, encoding="utf-8") as f:
                findings.extend(
                    audit_suppressions(f.read(), path=path, config=config)
                )
        except (SyntaxError, ValueError, UnicodeDecodeError, OSError) as e:
            errors.append(ParseError(path=path, message=str(e)))
    return sorted(findings), sorted(errors, key=lambda e: e.path)


# -- baseline (accept-then-ratchet) ------------------------------------------


def finding_key(f: Finding) -> str:
    """The baseline identity of a finding — deliberately message-free,
    so rewording a rule does not re-open accepted debt."""
    return f"{f.path}:{f.line}:{f.code}"


def apply_baseline(
    baseline_path: str,
    findings: list[Finding],
    errors: list[ParseError],
) -> tuple[list[Finding], Optional[str]]:
    """Accept-then-ratchet: filter ``findings`` through a baseline file.

    Missing file: every current finding is accepted into a fresh
    baseline and the run reads clean — the adoption step. Existing
    file: accepted keys stay silent, anything new fails; and once a run
    is otherwise clean, accepted keys that no longer match a finding
    are ratcheted OUT of the file, so the debt can only shrink. Returns
    ``(new_findings, note)`` — the note narrates what the baseline did.
    """
    keys = sorted({finding_key(f) for f in findings})
    if not os.path.exists(baseline_path):
        with open(baseline_path, "w", encoding="utf-8") as fh:
            json.dump(
                {"tool": "tpulint", "version": 1, "accepted": keys},
                fh, indent=2,
            )
            fh.write("\n")
        return [], (
            f"baseline: accepted {len(keys)} finding(s) into "
            f"{baseline_path}"
        )
    with open(baseline_path, encoding="utf-8") as fh:
        accepted = set(json.load(fh).get("accepted", []))
    new = [f for f in findings if finding_key(f) not in accepted]
    stale = sorted(accepted - set(keys))
    if not stale:
        return new, None
    if new or errors:
        return new, (
            f"baseline: {len(stale)} stale entr"
            f"{'y' if len(stale) == 1 else 'ies'} (ratchet deferred "
            "until the run is clean)"
        )
    kept = sorted(accepted & set(keys))
    with open(baseline_path, "w", encoding="utf-8") as fh:
        json.dump(
            {"tool": "tpulint", "version": 1, "accepted": kept},
            fh, indent=2,
        )
        fh.write("\n")
    return new, (
        f"baseline: ratcheted {len(stale)} fixed entr"
        f"{'y' if len(stale) == 1 else 'ies'} out of {baseline_path} "
        f"({len(kept)} remain)"
    )
