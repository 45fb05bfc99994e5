"""bench/phases.py: the phase means per step, and its report on a CPU
run of the test cells."""
import jax
import pytest

from bench import phases, spec
from bench.drive import Tick

DATA = spec.BENCH / "tests" / "data"
PEAK = spec.load_json(spec.BENCH / "peaks.json")["TPU v5 lite"]


def test_phase_ms_is_the_mean_per_step():
    ticks = [Tick(0.0, 1.0, phases={"serving.step": 0.10,
                                    "serving.stage": 0.04}),
             Tick(0.0, 1.0, phases={"serving.step": 0.10,
                                    "serving.stage": 0.02})]
    ms = phases.phase_ms(ticks)
    assert ms["serving.stage"] == pytest.approx(30.0)
    assert ms["serving.step"] == pytest.approx(100.0)
    assert phases.phase_ms([]) == {}


@pytest.mark.parametrize("name,share", [("lm.backlog", "sample.decode"),
                                        ("cnn.backlog", "stage.cnn")])
def test_report_of_a_traced_run(name, share, tmp_path):
    """The report comes from the run's own ticks and trace: phases per
    step before, in and after the trace, the program's spans per step,
    and the cell's phase share; with no TPU in the trace, nothing is
    read from device ops."""
    cell = spec.load_cell(name, DATA / "BENCHMARK.json", DATA)
    r = phases.run_phases(cell, 2 ** 32 + 5, 1.0, jax.devices(), tmp_path,
                          peak=PEAK)
    assert r["correct"]
    assert r["traced_steps"] > 0 and r["steps"] > r["traced_steps"]
    assert r["phase_ms.before_trace"]["serving.step"] > 0
    assert r["phase_ms.traced"]["serving.step"] > 0
    assert r["spans_per_step"] >= 2
    assert 0 < r[share] < 100
    assert r["kv_copy.decode"] is None and r["scoped_share"] is None
    assert (tmp_path / f"{name}.ticks.json").exists()
