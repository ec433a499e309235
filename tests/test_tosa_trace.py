"""Fixture tests for the trace-discipline rule."""

import textwrap

from tosa_testutil import run_project_rule, run_rule_multi


def _src(s):
    return textwrap.dedent(s).lstrip()


TRACING_PATH = "tensorflowonspark_tpu/obs/tracing.py"

#: a minimal tracing module: a one-row span-site table
TRACING_MODULE = _src('''
    """Cluster-wide trace context.

    Span sites
    ----------

    ``feed_wave``      one executor feed wave
    """


    def record_span(name, ts, dur_s, **attrs):
        pass
''')

FIRING_MODULE = _src("""
    from tensorflowonspark_tpu import obs


    def feed(q, item):
        with obs.span("feed_wave"):
            q.put(item)
""")


class TestTraceDiscipline:
    def test_documented_and_fired_is_clean(self):
        findings = run_rule_multi("trace-discipline", {
            TRACING_PATH: TRACING_MODULE,
            "tensorflowonspark_tpu/feeder.py": FIRING_MODULE,
        })
        assert findings == []

    def test_non_literal_span_name_fires(self):
        findings = run_rule_multi("trace-discipline", {
            TRACING_PATH: TRACING_MODULE,
            "tensorflowonspark_tpu/feeder.py": _src("""
                from tensorflowonspark_tpu import obs

                NAME = "feed_wave"


                def feed(q, item):
                    with obs.span(NAME):
                        with obs.span("feed_wave"):
                            q.put(item)
            """),
        })
        assert len(findings) == 1
        assert "non-literal" in findings[0].message

    def test_span_outside_with_fires(self):
        findings = run_rule_multi("trace-discipline", {
            TRACING_PATH: TRACING_MODULE,
            "tensorflowonspark_tpu/feeder.py": _src("""
                from tensorflowonspark_tpu import obs


                def feed(q, item):
                    sp = obs.span("feed_wave")
                    sp.__enter__()
                    q.put(item)
                    sp.__exit__(None, None, None)
            """),
        })
        assert len(findings) == 1
        assert "context manager" in findings[0].message

    def test_record_span_is_with_exempt(self):
        """The form ``util``'s compile listener uses for its ``compile_*``
        spans: a literal name, explicit times, no ``with``."""
        findings = run_rule_multi("trace-discipline", {
            TRACING_PATH: TRACING_MODULE,
            "tensorflowonspark_tpu/feeder.py": _src("""
                from tensorflowonspark_tpu.obs import tracing


                def publish(spans):
                    for s, e in spans:
                        tracing.record_span("feed_wave", ts=s, dur_s=e - s)
            """),
        })
        assert findings == []

    def test_undocumented_span_fires(self):
        findings = run_rule_multi("trace-discipline", {
            TRACING_PATH: TRACING_MODULE,
            "tensorflowonspark_tpu/feeder.py": _src("""
                from tensorflowonspark_tpu import obs


                def feed(q, item):
                    with obs.span("feed_wave"):
                        with obs.span("mystery_phase"):
                            q.put(item)
            """),
        })
        assert len(findings) == 1
        assert "mystery_phase" in findings[0].message
        assert "missing from the span-site table" in findings[0].message

    def test_stale_table_row_fires(self):
        stale = TRACING_MODULE.replace(
            "``feed_wave``      one executor feed wave",
            "``feed_wave``      one executor feed wave\n"
            "    ``ghost_phase``    documented but never opened",
        )
        findings = run_rule_multi("trace-discipline", {
            TRACING_PATH: stale,
            "tensorflowonspark_tpu/feeder.py": FIRING_MODULE,
        })
        assert len(findings) == 1
        assert "ghost_phase" in findings[0].message
        assert "never opened" in findings[0].message

    def test_no_tracing_module_in_scan_skips_table_checks(self):
        findings = run_rule_multi("trace-discipline", {
            "tensorflowonspark_tpu/feeder.py": FIRING_MODULE,
        })
        assert findings == []

    def test_obs_package_internals_are_exempt(self):
        findings = run_rule_multi("trace-discipline", {
            TRACING_PATH: TRACING_MODULE,
            "tensorflowonspark_tpu/feeder.py": FIRING_MODULE,
            "tensorflowonspark_tpu/obs/trace.py": _src("""
                def span(name, **attrs):
                    return Span(name, attrs)


                class Span:
                    def __init__(self, name, attrs):
                        self._handle = trace.span(name)
            """),
        })
        assert findings == []

    def test_check_project_path_detects_drift(self):
        # The index-driven variant (cache-hit path) sees the same drift.
        findings = run_project_rule("trace-discipline", {
            TRACING_PATH: TRACING_MODULE,
            "tensorflowonspark_tpu/feeder.py": _src("""
                from tensorflowonspark_tpu import obs


                def feed(q, item):
                    with obs.span("mystery_phase"):
                        q.put(item)
            """),
        })
        messages = "\n".join(f.message for f in findings)
        assert "mystery_phase" in messages
        assert "feed_wave" in messages  # documented but never opened
