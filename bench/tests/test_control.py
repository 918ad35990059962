"""The control of ``correct`` (the reference without EASY's reservation)
must fail the check of every cell, and the reference itself must pass it."""

import pytest

from conftest import CELLS, KEPT, small
from lib import control


@pytest.mark.parametrize("cell_name", CELLS + tuple(KEPT))
def test_control_fails_the_check(cell_name):
    _, cell, cfg, trf = small(cell_name)
    readings = control.control_readings(cell, seed=11, questions=4,
                                        config=cfg, traffic=trf)
    compared = dict((n, v) for n, v, _ in readings)["compared"]
    assert compared > 0
    assert any(lim is not None and v > lim for _, v, lim in readings), \
        readings


@pytest.mark.parametrize("cell_name", CELLS + tuple(KEPT))
def test_reference_in_the_programs_place_passes(cell_name, monkeypatch):
    """The same answers with the reservation kept read 0 everywhere."""
    from lib import refsim

    _, cell, cfg, trf = small(cell_name)
    orig = refsim.simulate
    monkeypatch.setattr(refsim, "simulate",
                        lambda *a, reserve=True, **k: orig(*a, **k))
    readings = control.control_readings(cell, seed=11, questions=4,
                                        config=cfg, traffic=trf)
    assert all(lim is None or v <= lim for _, v, lim in readings), readings
