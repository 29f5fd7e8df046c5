"""Lint stat-name registrations across the package.

Walks api_ratelimit_tpu/ for literal stat registrations —
scope.counter("..."), .gauge("..."), .timer("..."), .histogram("...") —
and fails on:

  * names violating the dotted-lowercase convention
    (``segment.segment`` where a segment is ``[a-z0-9_]+``); and
  * the same literal name registered under CONFLICTING stat kinds
    (e.g. a counter somewhere and a gauge elsewhere): the Prometheus
    renderer would emit two # TYPE declarations for one family, which
    scrapers reject.

Names are literals as written at the call site (scope-relative); the
convention check is what keeps the composed dotted paths well-formed.
Dynamically composed names (f-strings, variables) are out of scope.

It also drift-checks the README: every backticked ``ratelimit.*`` metric
name mentioned in README.md (brace alternations like ``{steals,drops}``
expanded; ``<placeholder>`` tokens skipped) must resolve to a literal
registration in the source, or be the literal name of a profiler span
(``host_span("ratelimit....")``, tracing/host.py) — a renamed or deleted
stat or span must not leave a stale name in the operator docs.

Run standalone (``python tools/metrics_lint.py``; exit 1 on findings) or
via the fast pytest wrapper in tests/test_metrics_lint.py, which is part
of the tier-1 run. No jax import — this must stay cheap.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "api_ratelimit_tpu")
README = os.path.join(REPO, "README.md")

_REGISTRATION = re.compile(
    r"\.(?P<kind>counter|gauge|timer|histogram)\(\s*(?P<q>['\"])(?P<name>[^'\"]+)(?P=q)"
)
_NAME_OK = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)*$")
# profiler span names are full dotted names, written whole at the site
_SPAN = re.compile(r"host_span\(\s*(?P<q>['\"])(?P<name>ratelimit\.[^'\"]+)(?P=q)")

# freecache parity names (limiter/local_cache.py): the reference exports
# the Go library's camelCase counters verbatim so existing dashboards and
# the prom-statsd-exporter mapping carry over (README "Switching from
# kentik/api-ratelimit"); exempt from the convention, not from the
# conflicting-kind check.
NAME_ALLOWLIST = frozenset(
    {
        "hitCount",
        "missCount",
        "lookupCount",
        "entryCount",
        "expiredCount",
        "evacuateCount",
        "overwriteCount",
    }
)


def _package_sources(package_dir: str):
    """Yield (path, text) of every .py file under the package."""
    for dirpath, dirnames, filenames in os.walk(package_dir):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for filename in sorted(filenames):
            if filename.endswith(".py"):
                path = os.path.join(dirpath, filename)
                with open(path, encoding="utf-8") as f:
                    yield path, f.read()


def iter_registrations(package_dir: str = PACKAGE):
    """Yield (name, kind, file, line) for every literal registration."""
    for path, text in _package_sources(package_dir):
        # whole-file scan: \s* spans newlines, so a registration whose
        # string literal sits on a continuation line still counts
        for m in _REGISTRATION.finditer(text):
            yield (
                m.group("name"),
                m.group("kind"),
                os.path.relpath(path, REPO),
                text.count("\n", 0, m.start()) + 1,
            )


def span_names(package_dir: str = PACKAGE) -> set[str]:
    """The literal names of the package's profiler spans."""
    return {
        m.group("name")
        for _path, text in _package_sources(package_dir)
        for m in _SPAN.finditer(text)
    }


def lint(package_dir: str = PACKAGE) -> list[str]:
    """Returns a list of human-readable findings (empty = clean)."""
    findings: list[str] = []
    kinds_by_name: dict[str, dict[str, list[str]]] = {}
    for name, kind, path, lineno in iter_registrations(package_dir):
        site = f"{path}:{lineno}"
        if name not in NAME_ALLOWLIST and not _NAME_OK.match(name):
            findings.append(
                f"{site}: stat name {name!r} violates the dotted-lowercase "
                f"convention ([a-z0-9_] segments joined by '.')"
            )
        kinds_by_name.setdefault(name, {}).setdefault(kind, []).append(site)
    for name, kinds in sorted(kinds_by_name.items()):
        if len(kinds) > 1:
            detail = "; ".join(
                f"{kind} at {', '.join(sites)}" for kind, sites in sorted(kinds.items())
            )
            findings.append(
                f"stat name {name!r} registered with conflicting types: {detail}"
            )
    return findings


# backticked dotted stat paths in the README, e.g. `ratelimit.slab.loss_ppm`
# or `ratelimit.sidecar.{retry,redial}`; `<domain>`-style placeholders make
# a token unverifiable and are skipped
_README_METRIC = re.compile(r"`(ratelimit\.[A-Za-z0-9_.{},<>]+)`")
_BRACE = re.compile(r"\{([^{}]*)\}")


def readme_metric_names(readme_path: str = README) -> list[str]:
    """Concrete dotted stat names mentioned in the README (one level of
    {a,b,c} alternation expanded; placeholder tokens skipped)."""
    try:
        with open(readme_path, encoding="utf-8") as f:
            text = f.read()
    except FileNotFoundError:
        return []
    names: set[str] = set()
    for m in _README_METRIC.finditer(text):
        token = m.group(1)
        if "<" in token or ">" in token:
            continue
        expanded = [token]
        while any("{" in t for t in expanded):
            nxt = []
            for t in expanded:
                mm = _BRACE.search(t)
                if mm is None:
                    nxt.append(t)
                    continue
                for alt in mm.group(1).split(","):
                    nxt.append(t[: mm.start()] + alt.strip() + t[mm.end():])
            expanded = nxt
        names.update(expanded)
    return sorted(names)


def lint_readme(
    package_dir: str = PACKAGE, readme_path: str = README
) -> list[str]:
    """README drift check: every documented ratelimit.* metric must end in
    a literal stat name registered somewhere in the package (registrations
    are scope-relative, so the check is a dotted-suffix match), or be a
    profiler span's literal name."""
    findings: list[str] = []
    literals = {name for name, _, _, _ in iter_registrations(package_dir)}
    spans = span_names(package_dir)
    for name in readme_metric_names(readme_path):
        if name not in spans and not any(
            name == lit or name.endswith("." + lit) for lit in literals
        ):
            findings.append(
                f"README.md: metric {name!r} does not match any literal "
                f"stat registration or span in the package (renamed or "
                f"deleted?)"
            )
    return findings


def lint_exposition(text: str) -> list[str]:
    """Validate a Prometheus text exposition — in particular the merged
    fleet output of stats/fleet.py merge_expositions (the master's
    ``GET /metrics?fleet=1`` body): every sample must belong to a
    ``# TYPE``-declared family, no family may be declared twice, no
    sample name may repeat, and histogram bucket series must be
    cumulative (monotone non-decreasing toward ``+Inf``). A merge bug —
    double-declared families from conflicting member types, non-monotone
    buckets from summing absolutes into cumulatives — fails here before
    a scraper ever sees it."""
    findings: list[str] = []
    declared: dict[str, str] = {}
    seen_samples: set[str] = set()
    current: str | None = None
    bucket_last: dict[str, float] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4:
                findings.append(f"line {lineno}: malformed TYPE line {line!r}")
                continue
            name, kind = parts[2], parts[3]
            if name in declared:
                findings.append(
                    f"line {lineno}: family {name!r} declared twice"
                )
            declared[name] = kind
            current = name
            continue
        if line.startswith("#"):
            continue
        try:
            key, raw = line.rsplit(" ", 1)
            value = float(raw)
        except ValueError:
            findings.append(f"line {lineno}: malformed sample {line!r}")
            continue
        base = key.split("{", 1)[0]
        if current is None or not base.startswith(current):
            findings.append(
                f"line {lineno}: sample {key!r} has no owning # TYPE family"
            )
        if key in seen_samples:
            findings.append(f"line {lineno}: duplicate sample {key!r}")
        seen_samples.add(key)
        if base.endswith("_bucket") and "le=" in key:
            prev = bucket_last.get(base)
            if prev is not None and value < prev:
                findings.append(
                    f"line {lineno}: histogram {base!r} buckets are not "
                    f"cumulative ({value} after {prev})"
                )
            bucket_last[base] = value
    return findings


def main() -> int:
    findings = lint() + lint_readme()
    if findings:
        for finding in findings:
            print(f"metrics-lint: {finding}", file=sys.stderr)
        print(f"metrics-lint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    count = sum(1 for _ in iter_registrations())
    print(f"metrics-lint: OK ({count} literal registrations checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
