"""Test harness: simulate an 8-device TPU pod slice on CPU.

The reference cannot test distributed behavior without >=4 real GPUs + NCCL
(SURVEY.md §4) — we fix that here: every sharding/collective path is exercised
on a virtual 8-device CPU mesh via XLA host-platform device multiplexing.
Must set flags BEFORE jax initializes.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


#: The files that take 40 s or more of one worker, longest first (test-
#: seconds summed a file from the junit the tier-1 command writes,
#: /tmp/_t1.xml; read the ORDER, the seconds are a machine's).  Under
#: `-n 6 --dist loadfile` a file is what one worker takes whole, and xdist
#: hands the files out by their NUMBER of tests, most first, so a file of
#: four tests and 140 s began among the last and the run ended on one
#: worker while five idled.  Handed out longest first, the workers end
#: within seconds of each other.  A file not named here follows in
#: alphabetical order; a new long file belongs in the list.
LONGEST_FIRST = (
    "test_chip_compile", "test_benchmark_registry", "test_pallas_kernels",
    "test_phi4_flash", "test_chunk_read_row", "test_bailing_hybrid",
    "test_serving_view", "test_serving_decode", "test_comm", "test_lint",
    "test_mimo_v2", "test_kimi_k2", "test_xing4", "test_deepseek_v32",
    "test_serving_families",
    "test_serving_pipeline", "test_jamba", "test_kda_scan_kernel",
    "test_trinity", "test_latent_chunk_attention", "test_step_spans",
    "test_pipeline_1f1b", "test_disagg", "test_serving_families_window",
    "test_serving_families_latent", "test_moe_sort",
    "test_chunk_attention", "test_generation", "test_serving",
    "test_hlo_profile", "test_prefill_budget", "test_serving_trace",
    "test_hetero_pp", "test_numerics", "test_trainer", "test_moe_dispatch",
    "test_hetero_dp", "test_hetero_ring_tp", "test_serving_chaos",
    "test_longcat", "test_benchmark_longcat", "test_benchmark_xing4",
    "test_pipeline",
    "test_llama", "test_chip_smoke", "test_flash_attention", "test_gpt",
    "test_chip_compile_longcat", "test_chip_compile_xing4",
    "test_benchmark_deepseek_v32", "test_chip_compile_deepseek_v32")


def pytest_configure(config):
    # the scheduler keeps the order of collection (below) and does not
    # sort the files by their number of tests (xdist's default)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.stem, len(rank)))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)
