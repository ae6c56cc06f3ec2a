import pytest

import selfcheck


@pytest.mark.parametrize("check", selfcheck.CHECKS,
                         ids=lambda f: f.__name__)
def test_selfcheck(check):
    check()
