"""The table of served blocks and its contract, held together: the
clauses themselves run in each block's ``test_<block>_serving.py``
(``served_block_contract.py`` says how and why)."""

from pathlib import Path

import pytest

from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb

FILES = {path.name: path.read_text()
         for path in Path(__file__).parent.glob("test_*_serving.py")}


@pytest.mark.parametrize("name", sb.BLOCKS)
def test_a_row_is_served_by_one_file_that_takes_the_contract(name):
    """A row nobody names runs no clause, and says nothing."""
    takers = [file for file, text in FILES.items()
              if f'BLOCK = sb.BLOCKS["{name}"]' in text
              and "globals().update(contract.clauses(BLOCK))" in text]
    assert len(takers) == 1, takers


@pytest.mark.parametrize("name", contract.CLAUSES)
def test_a_clause_is_given_an_entry_by_some_row(name):
    test, entries = contract.CLAUSES[name]
    assert name in test.__code__.co_varnames[:test.__code__.co_argcount]
    assert any(entries(row) for row in sb.BLOCKS.values())
