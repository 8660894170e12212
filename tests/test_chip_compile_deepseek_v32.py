"""`tests/test_chip_compile.py[deepseek_v32]`: the serving programs of
the WHOLE DeepSeek-V3.2 cut (1 dense + 4 expert layers at published
widths, 8 of 256 experts a layer, the 64 x 128 indexer and its selection
of 2,048, the cell's slots, page, chunk and max_len) compiled for a
described TPU v5e.  The case and its assertions are that file's
(`SERVING_FAMILIES["deepseek_v32"]`,
`test_serving_programs_compile_for_one_v5e`); it runs from a file of its
own because a file is what one worker of the tier-1 run takes whole."""
import test_chip_compile as described
from test_chip_compile import (decode_text,  # noqa: F401  (the fixtures)
                               described_chip)


def test_serving_programs_compile_for_one_v5e_deepseek_v32(decode_text):  # noqa: F811
    assert "deepseek_v32" in described.ELSEWHERE
    described.test_serving_programs_compile_for_one_v5e("deepseek_v32",
                                                        decode_text)
