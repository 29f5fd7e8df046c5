"""Fast tier-1 wrapper around tools/metrics_lint.py: the package's literal
stat-name registrations must keep the dotted-lowercase convention and one
stat kind per name (a counter/gauge clash would make the Prometheus
renderer emit two # TYPE declarations for one family)."""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_linter():
    spec = importlib.util.spec_from_file_location(
        "metrics_lint", os.path.join(REPO, "tools", "metrics_lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_package_stat_names_are_clean():
    lint = _load_linter()
    findings = lint.lint()
    assert findings == [], "\n".join(findings)
    # sanity: the walker actually saw the known registrations — an empty
    # scan passing would make this lint vacuous
    names = {name for name, _, _, _ in lint.iter_registrations()}
    assert "config_load_success" in names
    assert "queue_wait_ms" in names


def test_linter_flags_violations(tmp_path):
    lint = _load_linter()
    bad = tmp_path / "bad_stats.py"
    bad.write_text(
        'a = scope.counter("CamelCase.name")\n'
        'b = scope.counter("dup.name")\n'
        'c = scope.gauge("dup.name")\n'
    )
    findings = lint.lint(str(tmp_path))
    assert any("CamelCase.name" in f and "convention" in f for f in findings)
    assert any("dup.name" in f and "conflicting types" in f for f in findings)


def test_multiline_registrations_are_seen(tmp_path):
    """A registration whose string literal sits on a continuation line
    (black-style wrapping) must still be scanned — README drift checking
    depends on the walker seeing every literal."""
    lint = _load_linter()
    (tmp_path / "wrapped.py").write_text(
        "h = scope.histogram(\n"
        '    "wrapped_name", boundaries=BUCKETS\n'
        ")\n"
    )
    names = {n for n, _, _, _ in lint.iter_registrations(str(tmp_path))}
    assert names == {"wrapped_name"}


def test_readme_metric_names_exist_in_source():
    """Drift check: every ratelimit.* metric documented in README.md must
    still be registered somewhere in the package."""
    lint = _load_linter()
    names = lint.readme_metric_names()
    # sanity: the extractor actually parses the README tables (an empty
    # list would make the drift check vacuous)
    assert "ratelimit.batcher.queue_wait_ms" in names
    assert "ratelimit.fallback.degraded" in names  # PR-2 ladder gauge
    findings = lint.lint_readme()
    assert findings == [], "\n".join(findings)


def test_readme_drift_is_flagged(tmp_path):
    lint = _load_linter()
    (tmp_path / "stats.py").write_text('a = scope.counter("real_name")\n')
    readme = tmp_path / "README.md"
    readme.write_text(
        "| `ratelimit.x.real_name` | fine |\n"
        "| `ratelimit.x.ghost_name` | gone |\n"
        "| `ratelimit.y.{real_name,ghost_name}` | brace expansion |\n"
        "| `ratelimit.z.<domain>.anything` | placeholder skipped |\n"
    )
    findings = lint.lint_readme(str(tmp_path), str(readme))
    assert len(findings) == 2
    assert all("ghost_name" in f for f in findings)


def test_readme_span_names_resolve(tmp_path):
    """A documented profiler span resolves to a literal host_span name;
    a renamed span is flagged like a renamed stat."""
    lint = _load_linter()
    (tmp_path / "spans.py").write_text(
        'with host_span("ratelimit.owner.take"):\n    pass\n'
        "with host_span(\n    'ratelimit.owner.redeem'\n):\n    pass\n"
    )
    assert lint.span_names(str(tmp_path)) == {
        "ratelimit.owner.take", "ratelimit.owner.redeem"}
    readme = tmp_path / "README.md"
    readme.write_text(
        "| `ratelimit.owner.{take,redeem}` | spans |\n"
        "| `ratelimit.owner.linger` | gone |\n"
    )
    findings = lint.lint_readme(str(tmp_path), str(readme))
    assert len(findings) == 1 and "ratelimit.owner.linger" in findings[0]
    # the package's own spans are found
    assert "ratelimit.dispatch.submit_wait" in lint.span_names()
