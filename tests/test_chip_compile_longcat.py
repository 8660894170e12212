"""`tests/test_chip_compile.py[longcat]`: the serving programs of the
WHOLE LongCat-Flash cut (4 published layers = 8 latent cache layers at
published widths, the cell's slots, page, chunk and max_len) compiled for
a described TPU v5e.  The case and its assertions are that file's
(`SERVING_FAMILIES["longcat"]`, `test_serving_programs_compile_for_one_v5e`);
it runs from a file of its own because a file is what one worker of the
tier-1 run takes whole."""
import test_chip_compile as described
from test_chip_compile import (decode_text,  # noqa: F401  (the fixtures)
                               described_chip)


def test_serving_programs_compile_for_one_v5e_longcat(decode_text):  # noqa: F811
    assert "longcat" in described.ELSEWHERE
    described.test_serving_programs_compile_for_one_v5e("longcat",
                                                        decode_text)
