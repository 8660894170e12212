"""What importing the engine or the serving stack may NOT load (PR 56).

`orbax.checkpoint` brings ~230 packages (google.cloud.logging, grpc,
tensorstore, aiohttp): 12-14 s of every serving run's set-up and 35-39 s of
every train run's on the chip's host while `utils/checkpoint.py` imported it
at the top.  It loads at the first save or restore now.  A subprocess a
case: this suite's own imports would hide what one import brings.
"""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEPT_OUT = ("orbax", "google.cloud", "tensorstore", "grpc")

# (`google.cloud` alone is a namespace a .pth file makes as the interpreter
# starts: what counts is what the statement adds)
ABSENT = f"""
import sys
at_start = set(sys.modules)
{{statement}}
loaded = sorted(m for m in set(sys.modules) - at_start
                if m.startswith({KEPT_OUT!r}))
assert not loaded, loaded[:8]
"""

ROUND_TRIP = """
import sys
import numpy as np
import jax.numpy as jnp
from hetu_tpu.utils.checkpoint import load_checkpoint, save_checkpoint
assert "orbax.checkpoint" not in sys.modules
tree = {"w": jnp.arange(12.0).reshape(3, 4), "n": {"b": jnp.ones(5)}}
path = sys.argv[1] + "/ckpt"
save_checkpoint(path, tree)
assert "orbax.checkpoint" in sys.modules
back = load_checkpoint(path, tree)
assert sorted(back) == ["n", "w"] and list(back["n"]) == ["b"]
np.testing.assert_array_equal(back["w"], tree["w"])
np.testing.assert_array_equal(back["n"]["b"], tree["n"]["b"])
"""

CASES = {
    "serving_engine": ABSENT.format(
        statement="from hetu_tpu.serving.engine import ServingEngine"),
    "trainer": ABSENT.format(
        statement="from hetu_tpu.engine.trainer import Trainer"),
    "first_save_loads_orbax": ROUND_TRIP,
}


@pytest.mark.parametrize("case", list(CASES))
def test_import_cost(case, tmp_path):
    done = subprocess.run([sys.executable, "-c", CASES[case], str(tmp_path)],
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
