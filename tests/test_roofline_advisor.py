"""Roofline hardware model and collective-schedule advisor."""
import pytest

from repro.roofline import V5E, advise_allreduce, analytic_time


def test_analytic_ring_times():
    # 100 MB over 16 chips at 50 GB/s
    t = analytic_time("ring", 16, 100e6)
    assert t == pytest.approx(2 * 15 / 16 * 100e6 / 50e9, rel=1e-9)
    assert analytic_time("ring-bidir", 16, 100e6) == pytest.approx(t / 2)


def test_advisor_des_matches_analytic():
    for a in advise_allreduce(10e6, (2, 2)):
        an = analytic_time(a.schedule, 4, 10e6, V5E, (2, 2))
        assert a.predicted_s == pytest.approx(an, rel=1e-3), a.schedule
        assert a.source == "des"
