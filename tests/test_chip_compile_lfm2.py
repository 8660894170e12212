"""`tests/test_chip_compile.py[lfm2]`: the serving programs of the WHOLE
LFM2-8B-A1B cut (layers 0-11 at published widths, every expert and the
whole vocabulary, the cell's slots, page, chunk and max_len) compiled for
a described TPU v5e.  The case and its assertions are that file's
(`SERVING_FAMILIES["lfm2"]`, `test_serving_programs_compile_for_one_v5e`);
it runs from a file of its own because a file is what one worker of the
tier-1 run takes whole."""
import test_chip_compile as described
from test_chip_compile import (decode_text,  # noqa: F401  (the fixtures)
                               described_chip)


def test_serving_programs_compile_for_one_v5e_lfm2(decode_text):  # noqa: F811
    assert "lfm2" in described.ELSEWHERE
    described.test_serving_programs_compile_for_one_v5e("lfm2", decode_text)
