"""obs/audit: dispatch predicted-vs-measured accounting + drift flag."""
import math
import threading

import pytest

from repro.obs.audit import DispatchAudit


class StubCostModel:
    """Cost model with a fixed prediction per mode in µs."""

    source = "stub"

    def __init__(self, predictions):
        self.predictions = dict(predictions)

    def estimate_us(self, mode, batch, dims):
        return self.predictions[mode]


DIMS = [17, 400, 300, 6]


def test_empty_audit_reports_no_drift():
    audit = DispatchAudit(StubCostModel({}), DIMS)
    d = audit.drift()
    assert d == {"drift_factor": None, "stale": False, "threshold": 3.0,
                 "batches": 0}
    snap = audit.snapshot()
    assert snap["table"] == {} and snap["drift_factor"] is None


def test_calibrated_model_not_flagged():
    cm = StubCostModel({"fused": 100.0, "layer": 50.0})
    audit = DispatchAudit(cm, DIMS)
    for _ in range(10):
        audit.record("act", "fused", 128, 100e-6)   # measured == predicted
        audit.record("act", "layer", 8, 55e-6)      # off by 1.1x only
    d = audit.drift()
    assert d["batches"] == 20
    assert d["drift_factor"] == pytest.approx(math.sqrt(1.1), rel=1e-6)
    assert not d["stale"]
    tbl = audit.table()
    cell = tbl["act"]["fused"]["128"]
    assert cell["n"] == 10
    assert cell["predicted_us"] == 100.0
    assert cell["measured_us"] == pytest.approx(100.0)
    assert cell["ratio"] == pytest.approx(1.0)


def test_stale_cost_model_flags_drift():
    """The satellite's drift-flag unit test: a model whose predictions are
    5x off on every batch must cross the default threshold (3.0)."""
    cm = StubCostModel({"fused": 10.0})
    audit = DispatchAudit(cm, DIMS)
    for _ in range(5):
        audit.record("train", "fused", 32, 50e-6)   # 5x the prediction
    d = audit.drift()
    assert d["drift_factor"] == pytest.approx(5.0, rel=1e-6)
    assert d["stale"] is True
    # underprediction and overprediction both count (|log ratio|)
    audit2 = DispatchAudit(cm, DIMS)
    audit2.record("train", "fused", 32, 2e-6)       # 5x UNDER
    assert audit2.drift()["drift_factor"] == pytest.approx(5.0, rel=1e-6)
    assert audit2.drift()["stale"]


def test_threshold_configurable():
    cm = StubCostModel({"jnp": 10.0})
    audit = DispatchAudit(cm, DIMS, threshold=10.0)
    audit.record("act", "jnp", 1, 50e-6)            # 5x off
    d = audit.drift()
    assert d["threshold"] == 10.0 and not d["stale"]


def test_cell_mean_weighting_not_dominated_by_noise():
    """Per-cell mean first: one cell with symmetric noise around a perfect
    prediction must not read as drift."""
    cm = StubCostModel({"fused": 100.0})
    audit = DispatchAudit(cm, DIMS)
    for _ in range(50):
        audit.record("act", "fused", 128, 200e-6)   # 2x over
        audit.record("act", "fused", 128, 50e-6)    # 2x under
    d = audit.drift()
    # log ratios cancel inside the cell: factor ~= 1.0 despite 2x noise
    assert d["drift_factor"] == pytest.approx(1.0, rel=1e-6)
    assert not d["stale"]


def test_snapshot_is_json_shaped_and_reset_clears():
    import json
    cm = StubCostModel({"fused": 100.0, "jnp": 20.0})
    audit = DispatchAudit(cm, DIMS)
    audit.record("act", "fused", 128, 120e-6)
    audit.record("train", "jnp", 8, 20e-6)
    snap = audit.snapshot()
    json.dumps(snap)                                # serializable
    assert set(snap["table"]) == {"act", "train"}
    audit.reset()
    assert audit.drift()["batches"] == 0
    assert audit.snapshot()["table"] == {}


def test_audit_thread_safe_counts():
    cm = StubCostModel({"fused": 100.0})
    audit = DispatchAudit(cm, DIMS)
    n, per = 8, 500

    def worker():
        for _ in range(per):
            audit.record("act", "fused", 128, 100e-6)

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert audit.drift()["batches"] == n * per
    assert audit.table()["act"]["fused"]["128"]["n"] == n * per
