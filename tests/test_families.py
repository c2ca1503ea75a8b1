"""Every driver family registered in ``decode/family.py`` has a row in
``tests/families.py`` whose engine file exists and runs the shared engine
tests: a tenth family registered without its row fails here."""

import importlib

import pytest

from progen_tpu.decode.family import _DRIVER_FAMILIES
from tests import families


@pytest.mark.parametrize("module,config_name,family_name", _DRIVER_FAMILIES,
                         ids=[m.rsplit(".", 1)[1]
                              for m, _, _ in _DRIVER_FAMILIES])
def test_a_driver_family_has_a_row_and_its_engine_file_runs_the_shared_tests(
        module, config_name, family_name):
    rows = [c for c in families.CASES.values()
            if c.models.__name__ == module and c.family == family_name]
    assert len(rows) == 1, f"no row of tests/families.py:CASES for {module}"
    (case,) = rows
    assert isinstance(case.config, getattr(case.models, config_name))
    assert hasattr(case.reference, "forward_row")
    tests = importlib.import_module(f"tests.{case.engine_file}")
    shared = [v for k, v in vars(tests).items()
              if k.startswith("Test") and getattr(v, "case", None) is case]
    assert len(shared) == 1, f"{case.engine_file} runs no engine_tests(CASE)"
