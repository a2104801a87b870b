"""The control: the float8 reference put in the program's place fails the
check that sound runs pass, here at the reduced size on the CPU
(``bench/control.py`` takes the same readings on the chip)."""
import jax
import pytest

from bench import control
from bench.tests import tiny


@pytest.fixture(autouse=True)
def _reduced(monkeypatch):
    tiny.use_reduced_program(monkeypatch)


def test_float8_control_fails_where_the_program_passes():
    """Both sides judged by ``run.passed`` against the family's limit for
    the reduced size, which is no looser than the committed one."""
    cell = tiny.cell("starcoder2-3b.code_decode")
    assert cell.config["check"]["max_logit_gap"] < \
        tiny.full("starcoder2-3b.code_decode").config["check"]["max_logit_gap"]
    for r in control.readings(cell, [21, 2**33 + 22], 2.0, jax.devices()):
        assert r["tokens"] >= 150
        assert r["program_passes"] and not r["control_passes"], r
