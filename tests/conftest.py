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


#: Test-seconds a file (stem -> seconds summed over its cases), written
#: whole from the junit of the tier-1 command by `write_durations` below
#: (`python tests/conftest.py /tmp/_t1.xml`): data, never edited by
#: hand.  Read the ORDER, the seconds are a machine's.
DURATIONS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "durations.json")


def write_durations(junit_xml: str, out: str = DURATIONS) -> dict:
    """Sum a junit's test-seconds a file and write them, longest first."""
    import collections
    import json
    import xml.etree.ElementTree as ET
    seconds = collections.Counter()
    for case in ET.parse(junit_xml).iter("testcase"):
        stem = case.get("classname", "").split(".")[-1]
        seconds[stem] += float(case.get("time", 0.0))
    record = {stem: round(s, 1) for stem, s in seconds.most_common()}
    with open(out, "w") as fh:
        json.dump(record, fh, indent=0)
        fh.write("\n")
    return record


def longest_first(stems, seconds):
    """The order the files are handed out in.  Under `-n 6 --dist
    loadfile` a file is what one worker takes whole, and xdist hands the
    files out by their NUMBER of tests, most first, so a file of four
    tests and 140 s began among the last and the run ended on one worker
    while five idled.  Handed out longest first, the workers end within
    seconds of each other.  A file the record has no seconds for goes
    BEFORE every file it has: a new long file then starts early with no
    edit anywhere, and a short one costs its few seconds there."""
    return sorted(stems, key=lambda stem: (
        stem in seconds, -seconds.get(stem, 0.0), stem))


def pytest_configure(config):
    # the scheduler keeps the order of collection (below) and does not
    # sort the files by their number of tests (xdist's default)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    import json
    with open(DURATIONS) as fh:
        seconds = json.load(fh)
    order = longest_first({item.path.stem for item in items}, seconds)
    rank = {stem: i for i, stem in enumerate(order)}
    items.sort(key=lambda item: rank[item.path.stem])


def _share_identical_compiles():
    """One XLA:CPU compile per identical module a worker: a test builds
    an engine or a Trainer of its own, so its programs are lowered anew,
    and a third of a serving file's compile seconds (a fifth of its wall)
    went to modules byte-identical to one the process had compiled
    minutes before (641 compiles, 551 distinct, in test_chunk_read_row:
    PR 60).  Tracing and lowering are the test's still; the executable
    of the same text (debug positions apart) under the same options for
    the same devices is handed out again, the last 512 of them kept.
    Not for a module that calls back into Python (its text holds an
    address), nor for another backend than the CPU (a described chip's
    compiles are few and none repeats).  The hook is jax 0.9's; without
    it the suite runs as it did."""
    import collections
    import hashlib
    from jax._src import compiler
    compile_and_load = getattr(compiler, "backend_compile_and_load", None)
    if compile_and_load is None:
        return
    kept = collections.OrderedDict()

    def shared(backend, module, executable_devices, options,
               host_callbacks):
        text = "" if host_callbacks or backend.platform != "cpu" else \
            module.operation.get_asm(enable_debug_info=False)
        if not text or "callback" in text:
            return compile_and_load(backend, module, executable_devices,
                                    options, host_callbacks)
        key = (hashlib.sha256(text.encode()).digest(),
               options.SerializeAsString(),
               tuple(d.id for d in executable_devices))
        if key in kept:
            kept.move_to_end(key)
        else:
            kept[key] = compile_and_load(
                backend, module, executable_devices, options,
                host_callbacks)
            if len(kept) > 512:
                kept.popitem(last=False)
        return kept[key]
    compiler.backend_compile_and_load = shared


_share_identical_compiles()


def generate(model, params, input_ids, **kw):
    """`models.generation.generate` as ONE program a prompt length: the
    golden of the serving tests (`from conftest import generate`).  Run
    op by op, its prefill and its scan were most of a golden test's
    seconds (PR 46 found it in the family cases)."""
    from hetu_tpu.models import generation
    return jax.jit(lambda p, ids: generation.generate(
        model, p, ids, **kw))(params, input_ids)


@pytest.fixture(scope="session")
def orbax_loaded():
    """`orbax.checkpoint` loaded before a test that times heartbeats.
    The package loads at a process's first save or restore (PR 56):
    seconds, its extension modules' with the GIL held, 6.6 s on this
    machine under load.  A worker whose first save fell inside a chaos
    test sent no heartbeat for the 0.6-1.0 s its server allows and was
    declared dead: one red test in each of two whole runs of PR 60, a
    different test each time."""
    import orbax.checkpoint  # noqa: F401


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)


if __name__ == "__main__":
    import sys
    write_durations(sys.argv[1])
