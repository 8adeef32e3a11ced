"""A served block's engines, lent to the cases of its module
(``served_blocks.py``): ``served`` is the ``Lender`` of the row the
module names as ``BLOCK``, shared by the contract's cases laid into the
module and the block's own; ``lend`` hands a case an engine and checks
that it comes back empty."""

import pytest

from tests.unit.inference import served_blocks as sb


@pytest.fixture(scope="module")
def served(request):
    lender = sb.Lender(request.module.BLOCK)
    yield lender
    lender.drop()


@pytest.fixture
def lend(served):
    yield served.lend
    served.take_back()
