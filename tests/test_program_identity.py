"""The engine's programs, held to their recorded text: the admission program
at the smallest bucket and the chunk program of each of the thirteen families at
its tiny configuration hash to the heads in ``tests/golden/programs.json``
(``tools/program_hash.py`` makes both).  A PR that leaves a family's serving
path alone leaves its two heads alone, and this is where it shows."""

import functools
import importlib.util
from pathlib import Path

import pytest

_TOOL = Path(__file__).resolve().parents[1] / "tools" / "program_hash.py"
_spec = importlib.util.spec_from_file_location("program_hash", _TOOL)
program_hash = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(program_hash)

pytestmark = pytest.mark.serving


@functools.cache
def _programs(family):
    """One engine a family for its two cases."""
    return program_hash.program_shapes(program_hash.build_engine(family))


@pytest.mark.parametrize("program", program_hash.PROGRAMS)
@pytest.mark.parametrize("family", program_hash.FAMILIES)
def test_program_text_is_the_recorded_one(family, program):
    body, shapes = _programs(family)[program]
    head = program_hash.program_head(body, shapes)
    assert head == program_hash.read_golden()[f"{family}.{program}"], (
        f"the program changed ({family}.{program}): if that is what the PR "
        "is for, run `python tools/program_hash.py --write` and say so in "
        "CHANGES.md")
