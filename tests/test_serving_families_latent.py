"""`tests/test_serving_families.py::test_a_family_is_served_by_its_hooks`
for the families whose cache is a latent a token (Kimi, Ling,
LongCat, Xing4): that file's body and its cases, run from a file of
their own because a file is what one worker of the tier-1 run takes
whole."""
import pytest

import test_serving_families as golden


@pytest.mark.parametrize("family,route", golden.LATENT_CASES)
def test_a_family_is_served_by_its_hooks(family, route, monkeypatch):
    golden.test_a_family_is_served_by_its_hooks(family, route, monkeypatch)
