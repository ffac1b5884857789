"""Isolation shared by the tests in this directory."""

import pytest


@pytest.fixture(autouse=True)
def _interpreted_kernels_stay_with_their_test():
    """The kernels a test traces in interpret mode are recorded for the
    whole process (``kernels.common.interpreted_kernels``), and a pytest
    worker runs many files in one process: restore the record after each
    test, so a later run that checks it counts only its own kernels."""
    from repro.kernels import common
    before = set(common._INTERPRETED)
    yield
    common._INTERPRETED.clear()
    common._INTERPRETED.update(before)
