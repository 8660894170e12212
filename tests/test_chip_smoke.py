"""`chip_smoke.py` rehearsed off the chip, and the process rules it leans on.

`chip_smoke.py`'s own `main()` has no CPU mode.  Its phase functions take
their sizes as arguments, so they run here at a tiny size under
JAX_PLATFORMS=cpu — only to check control flow: every kernel routes to
XLA on a CPU backend and nothing here says anything about the chip.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from hetu_tpu.core.mesh import MeshConfig
from hetu_tpu.models.llama import LlamaConfig
from hetu_tpu.serving.engine import ServeConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(**kw):
    return LlamaConfig.tiny(param_dtype=jnp.bfloat16, vocab_size=512,
                            num_key_value_heads=4, **kw)


def _records(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


def test_train_phase_control_flow(capsys):
    rec = chip_smoke.train_phase(_tiny(remat_policy="dots_attn"), batch=2,
                                 seq=128, steps=3, seed=0)
    assert rec["losses"][-1] < rec["losses"][0]
    assert not any(r["pallas"] for r in rec["routes"].values())
    assert _records(capsys)[-1]["phase"] == "train"


def test_serve_phase_control_flow(capsys):
    rec = chip_smoke.serve_phase(
        _tiny(max_position_embeddings=256),
        ServeConfig(num_slots=4, page_size=8, max_len=128, prefill_chunk=16),
        prompt_lens=(5, 40, 70, 40), max_new=6, seed=0)
    assert rec["streams_matching_generate"] == 4
    # a prompt's launches of the chunk program: one chunk each where it
    # prefilled alone, up to four where a step's rows came to it first
    for s in rec["streams"]:
        chunks = -(-s["prompt_len"] // 16)
        assert -(-chunks // 4) <= s["prefill_chunks"] <= chunks, s
    assert max(s["prefill_chunks"] for s in rec["streams"]) >= 2
    assert _records(capsys)[-1]["phase"] == "serve"


def test_sharded_phase_control_flow(capsys, devices):
    """`--chips 4` on four of the virtual CPU devices: parameters split
    two ways (tp), optimizer state four ways (tp x ZeRO over dp)."""
    chip_smoke.sharded_phase(_tiny(remat_policy="dots_attn"), batch=2,
                             seq=128, steps=2, seed=0,
                             mesh_config=MeshConfig(dp=2, tp=2))
    verdict = _records(capsys)[-1]
    assert verdict["phase"] == "sharded_vs_single"
    assert verdict["first_step_loss"]["rel_err"] < chip_smoke.SHARDED_LOSS_RTOL
    assert len(verdict["per_device_bytes"]["params"]) == 4


def test_a_failed_check_is_a_failed_phase():
    with pytest.raises(chip_smoke.SmokeFailure, match="loss did not fall"):
        chip_smoke.check(False, "loss did not fall")
    # a route table that disagrees with the compiled text fails the phase
    hlo = ('%c = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call"'
           ', metadata={op_name="jit(step)/pallas_swiglu/pallas_call"}')
    assert chip_smoke.kernels_in(hlo)["swiglu"] == 1
    on, off = {"pallas": True, "why": ""}, {"pallas": False, "why": ""}
    chip_smoke.check_routes({"swiglu": on}, hlo, "p")
    with pytest.raises(chip_smoke.SmokeFailure, match="flash"):
        chip_smoke.check_routes({"flash": on}, hlo, "p")
    with pytest.raises(chip_smoke.SmokeFailure, match="swiglu"):
        chip_smoke.check_routes({"swiglu": off}, hlo, "p")


def test_main_refuses_to_run_without_a_tpu(capsys):
    """No CPU fallback: non-zero exit and no result line."""
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_serve_sizing_fits_the_pool_beside_the_layers():
    fit = chip_smoke.serve_layers(
        LlamaConfig.llama2_7b(param_dtype=jnp.bfloat16),
        ServeConfig(num_slots=8, page_size=16, max_len=2048,
                    prefill_chunk=128), 6, 16 * 2 ** 30)
    assert 1 <= fit["layers"] < 32
    assert (fit["layers"] * fit["per_layer_bytes"] + fit["fixed_bytes"]
            <= 0.85 * fit["bytes_limit"])


# ---------------------------------------------------------------------------
# one process per chip: a parent that imports the package holds no backend
# ---------------------------------------------------------------------------

def _run_python(code: str, **env):
    full = {k: v for k, v in {**os.environ, **env}.items() if v is not None}
    return subprocess.run([sys.executable, "-c", code], cwd=_REPO, env=full,
                          capture_output=True, text=True, timeout=300)


def test_importing_the_package_and_a_launcher_initialises_no_backend():
    """A launcher parent that touched JAX would hold the chip its worker
    needs.  Importing `hetu_tpu` and `hetu_tpu.rpc.launcher` (which pulls
    in jax through rpc/server.py) and constructing a launcher must leave
    JAX's backend table empty."""
    proc = _run_python(
        "import hetu_tpu, hetu_tpu.rpc.launcher as L\n"
        "launcher = L.ElasticLauncher(['true'], num_workers=1)\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, xb._backends\n"
        "print('no backend')\n")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("no backend")


# ---------------------------------------------------------------------------
# built from what git would commit
# ---------------------------------------------------------------------------

def test_a_foreign_native_library_is_rebuilt_from_source(tmp_path,
                                                         monkeypatch):
    """The chip machine gets the tree as it stands on disk, .so files and
    all.  A library that did not come from this tree's source — here
    garbage, newer than its .cpp, which `make` alone would keep — is
    rebuilt before it is loaded; one whose recorded source digest matches
    is loaded as it is."""
    import shutil
    from hetu_tpu.utils import native
    root = tmp_path / "csrc"
    root.mkdir()
    for name in ("Makefile", "dp_core.cpp"):
        shutil.copy(os.path.join(native.csrc_dir(), name), root / name)
    so = root / "libdp_core.so"
    so.write_bytes(b"not a library")
    monkeypatch.setattr(native, "csrc_dir", lambda: str(root))
    monkeypatch.setattr(native, "_CACHE", {})
    assert native.load_native_lib("libdp_core.so") is not None
    built = so.stat().st_mtime_ns
    assert (root / "libdp_core.so.src").read_text() == native._source_digest(
        str(root), "libdp_core.so")
    monkeypatch.setattr(native, "_CACHE", {})
    native.load_native_lib("libdp_core.so")
    assert so.stat().st_mtime_ns == built          # digest matched: kept


# ---------------------------------------------------------------------------
# the compile cache is placed from outside, or at one fixed path
# ---------------------------------------------------------------------------

_CACHE_PROBE = (
    "import jax\n"
    "from hetu_tpu.utils.device import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout():
    proc = _run_python(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=None)
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured, min_secs = proc.stdout.split()
    assert returned == configured == os.path.join(_REPO, ".jax_cache")
    assert float(min_secs) <= 1.0   # the kernels' 1-4 s compiles are kept


def test_compile_cache_set_from_outside_is_left_alone(tmp_path):
    outside = str(tmp_path / "cache")
    proc = _run_python(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=outside)
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured, _ = proc.stdout.split()
    assert returned == configured == outside
