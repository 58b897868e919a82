"""``tests/test_chip_compile.py``'s cases of the grouped matmuls and the row
kernels (``moe-*``, ``embed-*``), compiled for the described chip in a file of
their own: the cases, the described chip and the check are that file's."""

import pytest

from test_chip_compile import CASES, ROWS_AND_GROUPS, check_case, chip  # noqa: F401 - the fixture


@pytest.mark.parametrize("case", [c for c in CASES if c.startswith(ROWS_AND_GROUPS)])
def test_kernel_compiles_for_the_chip(chip, case):  # noqa: F811
    check_case(chip, case)
