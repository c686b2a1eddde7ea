"""Metrics-exposition conformance (fast lane): parse the ``/metrics``
document against the Prometheus text-format grammar.

The registry is hand-rolled (no prometheus_client in the container), so
nothing but this test stands between a formatting bug and a scrape that
silently drops samples. Checks, per the exposition format spec
(``text/plain; version=0.0.4``):

- every line is a valid comment/HELP/TYPE/sample line;
- metric and label names match the allowed charsets; label values are
  properly escaped (no raw newline/quote inside the quotes);
- at most one TYPE per metric family, declared before its samples, and
  each family's samples form one contiguous group;
- histogram families carry ``_bucket``/``_sum``/``_count`` series with
  cumulative non-decreasing ``le`` buckets ending at ``+Inf`` == count;
- the document ends with a newline.

Traffic includes label values that exercise the escaper (quotes,
backslashes, newlines) and every instrument family (counter, gauge,
histogram, the PerfStats bridge, the SLO collector).
"""

import math
import re

from opsagent_tpu import obs
from opsagent_tpu.utils.perf import get_perf_stats

METRIC_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
LABEL_NAME = r"[a-zA-Z_][a-zA-Z0-9_]*"
# Escaped label value: backslash, double quote, and newline must appear
# only in their escaped forms.
LABEL_VALUE = r'"(?:[^"\\\n]|\\\\|\\"|\\n)*"'
LABELS = rf"\{{{LABEL_NAME}={LABEL_VALUE}(?:,{LABEL_NAME}={LABEL_VALUE})*,?\}}"
VALUE = r"(?:[+-]?(?:\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|Inf)|NaN)"
SAMPLE_RE = re.compile(
    rf"^({METRIC_NAME})({LABELS})? ({VALUE})(?: [+-]?\d+)?$"
)
HELP_RE = re.compile(rf"^# HELP ({METRIC_NAME}) .*$")
TYPE_RE = re.compile(
    rf"^# TYPE ({METRIC_NAME}) (counter|gauge|histogram|summary|untyped)$"
)
HISTO_SUFFIX = re.compile(r"_(bucket|sum|count)$")


def _family(sample_name: str, types: dict[str, str]) -> str:
    """The metric family a sample belongs to: histogram samples use the
    suffixed names of their declared family."""
    m = HISTO_SUFFIX.search(sample_name)
    if m:
        base = sample_name[: m.start()]
        if types.get(base) == "histogram":
            return base
    return sample_name


def _generate_traffic():
    obs.TTFT_SECONDS.observe(0.012)
    obs.TTFT_SECONDS.observe(0.7)
    obs.TTFT_SECONDS.observe(3.0)
    obs.ITL_SECONDS.observe(0.004)
    obs.DECODE_TOKENS.inc(42)
    obs.ENGINE_REQUESTS.inc(outcome="completed")
    obs.ENGINE_REQUESTS.inc(outcome="error")
    # Label values that must round-trip through the escaper.
    obs.HTTP_REQUESTS.inc(
        method="GET", path='/weird"path\\with\nnewline', status="200"
    )
    obs.TOOL_CALLS.inc(tool="kubectl", outcome="ok")
    obs.KV_PAGE_UTILIZATION.set(0.375)
    obs.COMPILES.inc(phase="startup")
    # Goodput-ledger families: one priced dispatch (with a synchronous
    # measurement, so the drift gauge + measured histogram render) and
    # the goodput phase counters.
    attr = obs.attribution.Attribution(
        num_params=10_000, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, vocab_size=512, dtype_bytes=4,
        device_kind="TPU v5 lite",
    )
    attr.dispatch(
        "single", q_tokens=2, kv_read_tokens=8, kv_write_tokens=2,
        attn_q_ctx=8, measured_s=0.004,
    )
    obs.attribution.record_goodput(0.2, "decode_active")
    obs.attribution.record_goodput(0.1, "tool_blocked")
    obs.attribution.record_goodput(0.05, "queued")
    # PerfStats bridge lines.
    get_perf_stats().record_metric("engine.ttft", 12.5, "ms")
    get_perf_stats().record_metric('series"quote', 1.0, "ms")


def test_metrics_exposition_conforms():
    _generate_traffic()
    text = obs.metrics_text()
    assert text.endswith("\n"), "document must end with a newline"
    lines = text.split("\n")[:-1]
    assert lines, "empty exposition"

    types: dict[str, str] = {}
    sample_values: dict[tuple, float] = {}
    family_order: list[str] = []   # first-seen order of sample families

    for ln in lines:
        if ln.startswith("# HELP "):
            assert HELP_RE.match(ln), f"bad HELP line: {ln!r}"
            continue
        if ln.startswith("# TYPE "):
            m = TYPE_RE.match(ln)
            assert m, f"bad TYPE line: {ln!r}"
            name, kind = m.group(1), m.group(2)
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        if ln.startswith("#"):
            continue  # plain comment
        m = SAMPLE_RE.match(ln)
        assert m, f"bad sample line: {ln!r}"
        name = m.group(1)
        fam = _family(name, types)
        family_order.append(fam)
        key = (name, m.group(2) or "")
        assert key not in sample_values, f"duplicate sample: {ln!r}"
        sample_values[key] = float(m.group(3).replace("Inf", "inf"))

    # Contiguity: samples of one family must form one group.
    seen_done: set[str] = set()
    prev = None
    for fam in family_order:
        if fam != prev:
            assert fam not in seen_done, (
                f"family {fam} interleaved with other families"
            )
            if prev is not None:
                seen_done.add(prev)
            prev = fam

    # Histogram semantics.
    for fam, kind in types.items():
        if kind != "histogram":
            continue
        buckets = [
            (k, v) for k, v in sample_values.items()
            if k[0] == f"{fam}_bucket"
        ]
        if not buckets:
            continue  # registered but never observed: no samples at all
        # Group buckets by their non-le labels (histogram children).
        by_child: dict[str, list[tuple[float, float]]] = {}
        for (name, labels), v in buckets:
            le = re.search(rf'le="({VALUE})"', labels)
            assert le, f"bucket without le label: {name}{labels}"
            rest = re.sub(rf',?le="{re.escape(le.group(1))}"', "", labels)
            if rest == "{}":
                rest = ""  # le was the only label
            by_child.setdefault(rest, []).append(
                (float(le.group(1).replace("Inf", "inf")), v)
            )
        for child, series in by_child.items():
            series.sort(key=lambda t: t[0])
            les = [le for le, _ in series]
            counts = [c for _, c in series]
            assert les[-1] == math.inf, f"{fam}{child}: no +Inf bucket"
            assert counts == sorted(counts), (
                f"{fam}{child}: buckets not cumulative: {counts}"
            )
            # +Inf bucket equals the child's _count sample, and _sum
            # exists for it.
            assert sample_values[(f"{fam}_count", child)] == counts[-1], (
                f"{fam}{child}: +Inf bucket != _count"
            )
            assert (f"{fam}_sum", child) in sample_values


def test_goodput_ledger_families_on_the_scrape():
    """The opsagent_attr_* / opsagent_goodput_* families (the goodput
    ledger's contract with dashboards) are present, typed, and conform —
    the main grammar test above already walked them; this pins the names
    so a rename is a visible contract break."""
    _generate_traffic()
    text = obs.metrics_text()
    for family, kind in (
        ("opsagent_attr_bytes_total", "counter"),
        ("opsagent_attr_step_bytes", "gauge"),
        ("opsagent_attr_flops_total", "counter"),
        ("opsagent_attr_dispatches_total", "counter"),
        ("opsagent_attr_modeled_step_seconds", "gauge"),
        ("opsagent_attr_measured_step_seconds", "histogram"),
        ("opsagent_attr_model_drift_ratio", "gauge"),
        ("opsagent_attr_mfu", "gauge"),
        ("opsagent_attr_hbm_utilization", "gauge"),
        ("opsagent_goodput_seconds_total", "counter"),
    ):
        assert f"# TYPE {family} {kind}" in text, family
    # The split's label values are the documented four kinds.
    for k in ("weights", "kv_read", "kv_write", "other"):
        assert f'opsagent_attr_step_bytes{{kind="{k}"}}' in text


def test_step_tokens_family_on_the_scrape():
    """The mixed step's fill share (ISSUE 32): one counter, two kinds,
    real / computed; pinned so a rename is a visible contract break."""
    obs.STEP_TOKENS.inc(160, kind="real")
    obs.STEP_TOKENS.inc(256, kind="computed")
    text = obs.metrics_text()
    assert "# TYPE opsagent_step_tokens_total counter" in text
    for kind in ("real", "computed"):
        assert f'opsagent_step_tokens_total{{kind="{kind}"}}' in text


def test_mixed_dispatch_width_family_on_the_scrape():
    """How often the narrow branch of a packed mixed step ran (ISSUE 34):
    one counter labelled by the rows the dense segments ran over; pinned
    so a rename is a visible contract break."""
    obs.MIXED_DISPATCH_WIDTH.inc(width="128")
    obs.MIXED_DISPATCH_WIDTH.inc(3, width="256")
    text = obs.metrics_text()
    assert "# TYPE opsagent_mixed_dispatch_width_total counter" in text
    assert 'opsagent_mixed_dispatch_width_total{width="128"} 1' in text
    assert 'opsagent_mixed_dispatch_width_total{width="256"} 3' in text


def test_fleet_journey_families_on_the_scrape():
    """The fleet-journey families (ISSUE 16's contract with dashboards):
    hop latency histogram, journey shape counter, per-replica clock-skew
    gauge — present and typed once traffic touches them."""
    obs.FLEET_HOP_SECONDS.observe(0.012, hop="route")
    obs.FLEET_HOP_SECONDS.observe(0.034, hop="failover")
    obs.FLEET_JOURNEYS.inc(**{"shape": "direct", "class": "interactive"})
    obs.FLEET_JOURNEYS.inc(**{"shape": "failover", "class": "batch"})
    obs.FLEET_CLOCK_SKEW.set(0.004, replica="r1")
    text = obs.metrics_text()
    for family, kind in (
        ("opsagent_fleet_hop_seconds", "histogram"),
        ("opsagent_fleet_journeys_total", "counter"),
        ("opsagent_fleet_clock_skew_seconds", "gauge"),
    ):
        assert f"# TYPE {family} {kind}" in text, family
    assert 'opsagent_fleet_hop_seconds_count{hop="route"}' in text
    assert ('opsagent_fleet_journeys_total{shape="failover",'
            'class="batch"}') in text
    assert 'opsagent_fleet_clock_skew_seconds{replica="r1"}' in text


def test_class_and_history_families_on_the_scrape():
    """The ISSUE 18 families (SLO classes, tail-based trace retention,
    telemetry history) are present and typed once traffic touches them —
    a rename is a visible contract break."""
    obs.CLASS_REQUESTS.inc(**{"class": "interactive", "outcome": "completed"})
    obs.CLASS_REQUESTS.inc(**{"class": "batch", "outcome": "shed"})
    obs.CLASS_TTFT_SECONDS.observe(0.05, **{"class": "interactive"})
    obs.CLASS_ITL_SECONDS.observe(0.004, **{"class": "interactive"})
    obs.CLASS_GOODPUT_SECONDS.inc(
        0.2, **{"class": "interactive", "phase": "decode_active"}
    )
    obs.TRACE_RETENTION.inc(decision="kept_anomalous")
    obs.TRACE_RETENTION.inc(decision="dropped")
    obs.HISTORY_SAMPLES.inc()
    obs.HISTORY_POINTS.set(12, tier="1s")
    obs.HISTORY_BYTES.set(1440)
    text = obs.metrics_text()
    for family, kind in (
        ("opsagent_class_requests_total", "counter"),
        ("opsagent_class_ttft_seconds", "histogram"),
        ("opsagent_class_itl_seconds", "histogram"),
        ("opsagent_class_goodput_seconds_total", "counter"),
        ("opsagent_trace_retention_total", "counter"),
        ("opsagent_history_samples_total", "counter"),
        ("opsagent_history_points", "gauge"),
        ("opsagent_history_bytes", "gauge"),
    ):
        assert f"# TYPE {family} {kind}" in text, family
    assert ('opsagent_class_requests_total{class="interactive",'
            'outcome="completed"}') in text
    assert 'opsagent_trace_retention_total{decision="dropped"}' in text
    assert 'opsagent_history_points{tier="1s"}' in text


def test_class_labels_are_enum_only():
    """Cardinality guard for the new ``class`` label: every class-labeled
    sample on the scrape must carry one of the three declared SLO
    classes — a scenario name, model name, or request id leaking into
    the class label would be unbounded cardinality."""
    _generate_traffic()
    obs.CLASS_REQUESTS.inc(**{"class": "interactive", "outcome": "completed"})
    obs.FLEET_SHED.inc(**{"class": "batch"})
    obs.FLEET_HEDGES.inc(**{"class": "background"})
    obs.FLEET_JOURNEYS.inc(**{"shape": "direct", "class": "interactive"})
    text = obs.metrics_text()
    cls_re = re.compile(r'class="([^"]*)"')
    found = 0
    for ln in text.splitlines():
        if ln.startswith("#"):
            continue
        for m in cls_re.finditer(ln):
            found += 1
            assert m.group(1) in obs.SLO_CLASSES, (
                f"non-enum class label on the scrape: {ln!r}"
            )
    assert found > 0, "no class-labeled samples rendered"


def test_classify_rejects_unknown_values_to_default():
    """obs.slo.classify is the only writer of the class label: bogus
    explicit values and unknown scenarios must clamp to the enum (the
    upstream half of the cardinality guard above)."""
    from opsagent_tpu.obs import slo as obs_slo

    assert obs_slo.classify({"slo_class": "batch"}) == "batch"
    assert obs_slo.classify({"slo_class": "vip-customer-42"}) \
        == "interactive"
    assert obs_slo.classify(scenario="audit") == "batch"
    assert obs_slo.classify(scenario="diagnose") == "interactive"
    assert obs_slo.classify(scenario="no-such-scenario",
                            default="background") == "background"


def test_no_metric_family_is_keyed_by_raw_request_id():
    """Cardinality guard: request/journey IDs are unbounded, so they may
    appear in flight events and timelines but NEVER as a label value on
    the metrics surface — one leaked id-per-request label melts every
    scrape. Journey traffic runs first so a regression would be ON the
    exposition when we scan it."""
    obs.FLEET_HOP_SECONDS.observe(0.01, hop="route")
    obs.FLEET_JOURNEYS.inc(**{"shape": "direct", "class": "interactive"})
    _generate_traffic()
    text = obs.metrics_text()
    id_like = re.compile(
        r'="(?:chatcmpl|req|cli|tl|e2e)-[0-9a-fA-F]{8,}"'
    )
    for ln in text.splitlines():
        if ln.startswith("#"):
            continue
        assert not id_like.search(ln), (
            f"request-id-shaped label value on the scrape: {ln!r}"
        )


def test_escaped_label_values_roundtrip():
    """The escaper's output must re-parse to the original value."""
    from opsagent_tpu.obs.metrics import escape_label_value

    for raw in ['plain', 'with"quote', "back\\slash", "new\nline",
                'all\\"\nthree']:
        esc = escape_label_value(raw)
        assert "\n" not in esc
        unescaped = (
            esc.replace("\\\\", "\x00")
            .replace('\\"', '"')
            .replace("\\n", "\n")
            .replace("\x00", "\\")
        )
        assert unescaped == raw


def test_engine_servers_expose_same_document_shape():
    """Both servers' /metrics handlers serve the identical registry
    render (one process-wide registry — co-hosted deployments scrape
    either port)."""
    _generate_traffic()
    a = obs.metrics_text()
    b = obs.metrics_text()
    # Modulo the SLO collector's evaluated_at drift, consecutive renders
    # of an idle registry agree line-for-line.
    strip = lambda t: [  # noqa: E731
        ln for ln in t.splitlines() if not ln.startswith("opsagent_slo_")
    ]
    assert strip(a) == strip(b)
