"""Metric-name drift gate: every literal metrics emission in the
package must follow the naming convention and appear in the
docs/OBSERVABILITY.md catalog (tools/check_metrics.py). Runs in the
fast tier so drift fails tier-1 before it ships."""

from tools.check_metrics import (
    _name_matches,
    check,
    extract_sites,
    load_catalog,
    load_catalog_types,
)


def test_package_metric_names_clean():
    assert check() == []


def test_catalog_is_nonempty():
    catalog = load_catalog()
    assert len(catalog) > 40          # the full serving surface
    assert "http.init" in catalog
    assert "circuit.<name>.opened" in catalog


def test_extractor_reads_fstrings_as_wildcards():
    sites = extract_sites(
        "metrics.inc(f'{self.name}.batches')\n"
        "metrics.observe('a.b_s', 1.0)\n"
        "metrics.timer(name)\n",            # dynamic: skipped
        "<test>")
    assert ("*.batches", "inc", 1) in sites
    assert ("a.b_s", "observe", 2) in sites
    assert len(sites) == 2


def test_extractor_covers_block_timer_stage_names():
    """block_timer emits a metric + stage span; its literal names must
    lint like any metrics.observe (the device-stage names this layer
    leans on — scorer.encode_s, pipeline.t2i_s — would otherwise drift
    off the catalog unchecked)."""
    sites = extract_sites(
        "with block_timer('scorer.encode_s') as sink:\n    pass\n",
        "<test>")
    assert ("scorer.encode_s", "observe", 1) in sites
    # the package-wide scan actually sees the real stage sites
    import pathlib

    from tools.check_metrics import PACKAGE

    all_names = set()
    for p in sorted(pathlib.Path(PACKAGE).rglob("*.py")):
        for name, _, _ in extract_sites(p.read_text(), str(p)):
            all_names.add(name)
    assert {"scorer.encode_s", "pipeline.t2i_s",
            "pipeline.sdxl_s", "pipeline.prompt_s"} <= all_names


def test_extractor_covers_timed_host_regions():
    """host_span and a lock's wait_span name the SPAN; the histogram
    they observe, ``<span>_s``, lints like any other."""
    sites = extract_sites(
        "with host_span('pipeline.image_host'):\n    pass\n"
        "lock = OrderedLock('pipeline.t2i_dispatch', rank=10,\n"
        "                   wait_span='pipeline.image_lock_wait')\n",
        "<test>")
    assert ("pipeline.image_host_s", "observe", 1) in sites
    assert ("pipeline.image_lock_wait_s", "observe", 3) in sites


def test_wildcard_matching_rules():
    assert _name_matches("circuit.*.*", "circuit.<name>.opened")
    assert _name_matches("score.batches", "<queue>.batches")
    assert _name_matches("store.lock_*", "store.lock_<kind>")
    assert not _name_matches("score.batches", "<queue>.items")
    assert not _name_matches("a.b.c", "a.b")


def test_violations_are_detected():
    bad = extract_sites("metrics.inc('UPPER.case')\n"
                        "metrics.inc('nosegments')\n"
                        "metrics.observe('a.no_unit', 1.0)\n", "<t>")
    # extraction itself keeps them; check() logic is exercised via the
    # package scan above — here pin the convention primitives
    assert ("UPPER.case", "inc", 1) in bad
    from tools.check_metrics import _SEGMENT

    assert not _SEGMENT.match("UPPER")
    assert _SEGMENT.match("lower_case_1")


def test_extractor_covers_injected_registry_receivers():
    """Modules taking the registry by injection (obs/slo.py,
    obs/process.py use ``self._registry``) must lint like direct
    ``metrics.`` emitters — the receiver rule is name-shaped, not
    import-shaped."""
    sites = extract_sites(
        "self._registry.gauge('slo.burning', 1.0)\n"
        "registry.inc('a.b')\n"
        "cluster_metrics.gauge('federation.peer_up', 1.0)\n"
        "unrelated.gauge('not.linted', 1.0)\n",
        "<t>")
    assert ("slo.burning", "gauge", 1) in sites
    assert ("a.b", "inc", 2) in sites
    assert ("federation.peer_up", "gauge", 3) in sites
    assert not any(name == "not.linted" for name, _, _ in sites)


def test_catalog_types_parsed_from_tables():
    types = load_catalog_types()
    assert types["http.init"] == "counter"
    assert types["round.remaining_s"] == "gauge"
    assert types["http.compute_score_s"] == "histogram"
    assert types["slo.burning"] == "gauge"
    # prose mentions outside typed table rows carry no type
    assert "slo.burn" not in types


def test_type_drift_is_a_lint_error():
    """An emission site whose call kind contradicts the catalog row's
    declared type (a counter quietly emitted as a gauge) fails the
    lint instead of shipping a broken exposition shape."""
    from cassmantle_tpu.analysis.core import parse_source, run_passes
    from cassmantle_tpu.analysis.metric_names import MetricNamePass

    drift = parse_source("metrics.inc('http.compute_score_s')\n", "<t>")
    findings = run_passes([drift], [MetricNamePass()])
    assert len(findings) == 1 and "type drift" in findings[0].message
    drift2 = parse_source("metrics.gauge('http.init', 1.0)\n", "<t>")
    assert any("type drift" in f.message
               for f in run_passes([drift2], [MetricNamePass()]))
    # the matching kind is clean; wildcard sites need only ONE matching
    # typed row of the right kind
    ok = parse_source(
        "metrics.observe('http.compute_score_s', 1.0)\n"
        "metrics.inc(f'{self.name}.batches')\n", "<t>")
    assert run_passes([ok], [MetricNamePass()]) == []
