"""Step-time attribution (obs/attribution.py): the per-op trace folded into
roofline buckets.

- `classify_op` markers;
- goldens against the checked-in trace fixture
  (tests/fixtures/attribution_trace) and the degraded record when a trace
  is missing;
- the ``step_attribution`` journal schema;
- the summarize section (present + omitted-when-absent) and LiveAggregator's
  ``attr_*`` gauges.
"""

import os

import pytest

from distribuuuu_tpu.obs import attribution
from distribuuuu_tpu.obs.journal import read_journal, validate_record
from distribuuuu_tpu.obs.summarize import render

FIXTURE_TRACE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures", "attribution_trace"
)


def test_classify_op():
    assert attribution.classify_op("convolution.42") == "matmul"
    assert attribution.classify_op("dot_general") == "matmul"
    assert attribution.classify_op("all-reduce.1") == "collective"
    assert attribution.classify_op("infeed") == "infeed"
    assert attribution.classify_op("fusion.7") == "vector"


def test_attribution_goldens_from_fixture_trace():
    """Hand-computed goldens for the checked-in 2-step trace: device ops are
    8000µs convolution + 3000 fusion + 1000 all-reduce + 500 infeed (the
    jit_ envelope and step-marker tracks excluded), host transfer 800µs."""
    rec = attribution.attribute_logdir(FIXTURE_TRACE, steps=2)
    assert rec["device_ms_per_step"] == pytest.approx(6.25)
    assert rec["buckets"] == {
        "matmul": 4.0, "vector": 1.5, "collective": 0.5,
        "infeed": 0.25, "host": 0.4,
    }
    assert rec["matmul_pct"] == pytest.approx(64.0)
    assert rec["host_ms"] == pytest.approx(0.4)


def test_attribution_missing_trace_degrades():
    rec = attribution.attribute_logdir("/nonexistent/logdir", steps=5)
    assert rec["device_ms_per_step"] is None
    assert rec["matmul_pct"] is None
    assert set(rec["buckets"]) == set(attribution.BUCKETS)


def test_step_attribution_journal_schema(tmp_path):
    from distribuuuu_tpu.obs.journal import ValidatedJournal

    rec = attribution.attribution_record(FIXTURE_TRACE, 2, gstep=30,
                                         trigger="at_steps")
    path = str(tmp_path / "run.jsonl")
    j = ValidatedJournal(path, label="test")
    j.event("step_attribution", **rec)
    j.close()
    recs = list(read_journal(path))
    assert [e for r in recs for e in validate_record(r)] == []
    assert recs[0]["buckets"]["matmul"] == 4.0


def test_summarize_and_aggregator():
    from distribuuuu_tpu.obs.stream import LiveAggregator

    rec = attribution.attribution_record(FIXTURE_TRACE, 2, gstep=30)
    text = render([{"ts": 1.0, "kind": "step_attribution", **rec}])
    assert "step attribution (roofline) @ gstep 30" in text
    assert "outside-the-matmuls: 36.0%" in text
    # omitted-when-absent
    clean = render([{"ts": 1.0, "kind": "run_start", "argv": [], "devices": 1,
                     "device_kind": "cpu", "gstep": 0}])
    assert "attribution" not in clean

    agg = LiveAggregator()
    agg.ingest({"ts": 1.0, "kind": "step_attribution", **rec})
    assert agg.gauges["attr_matmul_ms"] == 4.0
    assert agg.gauges["attr_matmul_pct"] == pytest.approx(64.0)
