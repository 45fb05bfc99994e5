"""bench/drive.py: the counters a step reads are the registry's."""
from bench import drive


def test_counter_values_are_the_snapshots_counters():
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("requests_total").inc(3)
    reg.counter("phase_seconds_total", phase="serving.step").inc(0.25)
    reg.counter("blocked_total", reason="queue_full", tier="a").inc()
    reg.gauge("live").set(4)
    reg.histogram("tick_ms").observe(2.0)
    got = drive.counter_values(reg)
    assert {drive.flat_name(k): v for k, v in got.items()} == \
        reg.snapshot()["counters"]
    reg.counter("requests_total").inc(2)
    assert drive._moved(drive.counter_values(reg), got) == \
        {("requests_total", ()): 2.0}
