"""Distributed checkpointing with strategy resharding on load.

Rebuild of the reference's safetensors checkpoint stack
(reference: python/hetu/utils/checkpoint/ht_safetensors.py — temp_save_split
:905 / temp_load_split :1147 re-shard per-rank shards when the parallel
strategy changes; save_file_async :505 background saves;
load_by_training/save_by_training :881/:893 resume with ZeRO states).

On TPU this maps onto orbax: tensors are stored sharded (per-host OCDBT
shards) and `load_checkpoint` restores directly into ANY target sharding —
the strategy-resharding load the reference implements by slice bookkeeping
comes from handing orbax the new NamedShardings.  Async save uses orbax's
AsyncCheckpointer (background thread), the analog of save_file_async.

orbax itself loads at the first save or restore (`_ocp()`), not when this
module is imported: `hetu_tpu.engine` and, through `serving.reshard`,
`hetu_tpu.serving` import this module, and orbax brings ~230 packages
(google.cloud.logging, grpc, tensorstore, aiohttp) that took 12-14 s of
every serving run's set-up and 35-39 s of every train run's on the chip's
host (PERF.md s5, PR 56).  A job that checkpoints pays the load once, in
`CheckpointManager.__init__` (`Trainer.__init__` with a `ckpt_dir`), never
inside a step.

Verified fallback (docs/fault_tolerance.md): every committed save gets a
per-step MANIFEST next to the step directory — the state's pytree
structure hash plus per-file size+crc32 — written atomically AFTER the
(possibly async) save commits.  `restore_latest_valid()` walks steps
newest-first, skips any step whose manifest fails verification (counting
`ckpt.fallbacks` and quarantining the corrupt step so it cannot shadow a
later re-save of the same step number), and restores the newest step that
checks out — a torn or bit-rotted save degrades to "lose one checkpoint
interval", not "crash the surviving cluster".
"""
from __future__ import annotations

import json
import os
import sys
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax

from hetu_tpu.utils.logging import get_logger

logger = get_logger("checkpoint")


def _ocp():
    """`orbax.checkpoint`, imported at the first save or restore."""
    first = "orbax.checkpoint" not in sys.modules
    t0 = time.perf_counter()
    import orbax.checkpoint
    if first:
        logger.info(f"orbax.checkpoint loaded in "
                    f"{time.perf_counter() - t0:.2f} s (this process's "
                    f"first save or restore)")
    return orbax.checkpoint


# remote stores ride orbax's filesystem layer untouched — the TPU-native
# analog of the reference's HDFS branch (model_saver.py:168): on TPU pods
# the durable store is a GCS bucket, and orbax speaks gs:// natively
# (needs the gcsfs/etils deps present in cloud images)
_REMOTE_SCHEMES = ("gs://", "s3://", "hdfs://", "file://")


class CheckpointCorruptError(RuntimeError):
    """Checkpoints exist on disk but NONE of them is restorable (every
    step failed manifest verification or raised during restore).  Distinct
    from FileNotFoundError (no checkpoints at all — a legitimate fresh
    start) so recovery paths can be loud about lost state."""


def resolve_ckpt_path(path: str) -> str:
    """Absolute-ify local paths; pass remote URIs through unmangled."""
    if any(path.startswith(s) for s in _REMOTE_SCHEMES):
        return path
    return os.path.abspath(path)


def _is_remote(path: str) -> bool:
    return any(path.startswith(s) for s in _REMOTE_SCHEMES)


# ---------------------------------------------------------------- manifest
def manifest_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"manifest_{int(step)}.json")


def pytree_structure_hash(state: Any) -> str:
    """Stable hash of the state's (keypath, shape, dtype) skeleton —
    recorded in the manifest so a restore target mismatch is explainable
    even before orbax raises."""
    import hashlib
    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        kp = jax.tree_util.keystr(path)
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        leaves.append((kp, list(shape), dtype))
    blob = json.dumps(sorted(leaves), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def _file_checksums(step_dir: str) -> Dict[str, Dict[str, int]]:
    """relpath -> {size, crc32} for every file under a step directory."""
    out: Dict[str, Dict[str, int]] = {}
    for root, _dirs, files in os.walk(step_dir):
        for fn in sorted(files):
            p = os.path.join(root, fn)
            rel = os.path.relpath(p, step_dir)
            crc, size = 0, 0
            with open(p, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        break
                    crc = zlib.crc32(chunk, crc)
                    size += len(chunk)
            out[rel] = {"size": size, "crc32": crc & 0xFFFFFFFF}
    return out


def write_manifest(directory: str, step: int,
                   structure: Optional[str] = None) -> str:
    """Checksum a committed step directory and write its manifest
    atomically (tmp + rename): a crash mid-write leaves either no
    manifest (step reads as unverified) or a complete one — never a torn
    manifest that poisons verification."""
    step_dir = os.path.join(directory, str(int(step)))
    man = {"schema": 1, "step": int(step), "structure": structure,
           "files": _file_checksums(step_dir), "written_at": time.time()}
    path = manifest_path(directory, step)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(man, f)
        f.flush()
        os.fsync(f.fileno())   # rename durability alone doesn't imply
                               # data durability (delayed allocation)
    os.replace(tmp, path)
    return path


#: verify detail prefix for a torn/unreadable manifest — the DATA may be
#: fine, so restore_latest_valid drops the manifest instead of
#: quarantining the step (the step demotes to 'unverified')
MANIFEST_UNREADABLE = "manifest unreadable"


def verify_manifest(directory: str, step: int) -> Tuple[bool, str]:
    """(ok, detail) for one step.  A MISSING manifest passes as
    'unverified' — pre-manifest checkpoints and in-flight async saves must
    stay restorable — while a present-but-mismatching one fails loudly."""
    path = manifest_path(directory, step)
    if not os.path.exists(path):
        return True, "unverified (no manifest)"
    try:
        with open(path) as f:
            man = json.load(f)
    except (ValueError, OSError) as e:
        return False, f"{MANIFEST_UNREADABLE}: {e!r}"
    step_dir = os.path.join(directory, str(int(step)))
    if not os.path.isdir(step_dir):
        return False, "step directory missing"
    actual = _file_checksums(step_dir)
    expected = man.get("files", {})
    if set(actual) != set(expected):
        missing = sorted(set(expected) - set(actual))
        extra = sorted(set(actual) - set(expected))
        return False, (f"file set mismatch (missing={missing[:3]}, "
                       f"extra={extra[:3]})")
    for rel, meta in expected.items():
        a = actual[rel]
        if a["size"] != meta.get("size") or a["crc32"] != meta.get("crc32"):
            return False, (f"checksum mismatch in {rel} "
                           f"(size {a['size']} vs {meta.get('size')})")
    return True, "verified"


class CheckpointManager:
    """Step-numbered checkpoints with retention + async save + verified
    fallback.

    `directory` may be a local path or a remote URI (gs://bucket/ckpts —
    the TPU-pod durable store; reference: model_saver.py:168 remote saves).
    Manifests are local-filesystem only: remote stores get orbax's own
    atomic-commit semantics and read back as 'unverified'.
    """

    def __init__(self, directory: str, max_to_keep: int = 3,
                 async_save: bool = True):
        self.directory = resolve_ckpt_path(directory)
        self._async = async_save
        self._manifests_enabled = not _is_remote(self.directory)
        self._pending: Optional[Tuple[int, Optional[str]]] = None
        self._manifest_thread = None
        ocp = _ocp()
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep, enable_async_checkpointing=async_save)
        self._mgr = ocp.CheckpointManager(self.directory, options=options)

    # -------------------------------------------------------------- save
    def save(self, step: int, state: Dict[str, Any], wait: bool = False):
        """state: arbitrary pytree (params/opt_state/step/...)."""
        self._finalize_pending()   # manifest for the PREVIOUS async save
        saved = self._mgr.save(step, args=_ocp().args.StandardSave(state))
        if saved is False:
            # orbax declines silently when the step already exists (e.g.
            # re-saving the restore point after a fallback walked past a
            # newer step that was NOT quarantined) — silence here would
            # read as "checkpointed" when nothing hit disk
            from hetu_tpu.obs.metrics import get_registry
            get_registry().inc("ckpt.save_skipped")
            logger.warning(f"orbax declined to save step {step} (already "
                           "on disk?); state NOT re-written")
            return
        if self._manifests_enabled:
            self._pending = (int(step),
                             pytree_structure_hash(state))
            if self._async:
                # the wait-for-commit + full checksum read must not stall
                # the training thread — run it alongside the async save
                # and join at the next save/restore/wait/close boundary
                import threading
                self._manifest_thread = threading.Thread(
                    target=self._write_pending_manifest, daemon=True)
                self._manifest_thread.start()
            else:
                self._write_pending_manifest()
        if wait:
            self.wait()

    def _finalize_pending(self):
        """Ensure the last issued save's manifest is on disk (join the
        background writer; write synchronously if none ran)."""
        t = self._manifest_thread
        if t is not None:
            t.join()
            self._manifest_thread = None
        if self._pending is not None:
            self._write_pending_manifest()

    def _write_pending_manifest(self):
        """Write the manifest for the last issued save once it has
        committed (async saves commit in the background; the manifest must
        describe COMMITTED bytes, so it always waits first)."""
        if self._pending is None:
            return
        self._mgr.wait_until_finished()
        step, structure = self._pending
        self._pending = None
        if step not in (self._mgr.all_steps() or []):
            return   # save failed or was retention-pruned already
        try:
            write_manifest(self.directory, step, structure)
            from hetu_tpu.obs.metrics import get_registry
            get_registry().inc("ckpt.manifests_written")
            self._prune_manifests()
        except OSError as e:
            logger.warning(f"manifest for step {step} not written: {e!r}")

    def _prune_manifests(self):
        """Drop manifests for steps orbax's retention already deleted."""
        keep = set(self._mgr.all_steps() or [])
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        for name in names:
            if not (name.startswith("manifest_") and name.endswith(".json")):
                continue
            stem = name[len("manifest_"):-len(".json")]
            if stem.isdigit() and int(stem) not in keep:
                try:
                    os.remove(os.path.join(self.directory, name))
                except OSError:
                    pass

    # ------------------------------------------------------------ queries
    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> List[int]:
        return sorted(self._mgr.all_steps() or [])

    def verify_step(self, step: int) -> Tuple[bool, str]:
        """(ok, detail): does this step's on-disk bytes match its
        manifest?  Remote stores and manifest-less steps pass as
        'unverified' (restore remains the final arbiter for those)."""
        if not self._manifests_enabled:
            return True, "unverified (remote store)"
        return verify_manifest(self.directory, step)

    # ----------------------------------------------------------- restore
    def restore(self, step: Optional[int] = None,
                target: Optional[Any] = None) -> Any:
        """Restore into `target`'s shapes+shardings (reshard-on-load when the
        target strategy differs from the saved one).  `target` is a pytree of
        arrays or ShapeDtypeStructs with .sharding set."""
        self._finalize_pending()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        if target is None:
            return self._mgr.restore(step)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=getattr(x, "sharding", None))
            if hasattr(x, "shape") else x,
            target)
        return self._mgr.restore(
            step, args=_ocp().args.StandardRestore(abstract))

    def restore_latest_valid(self, target: Optional[Any] = None,
                             restore_fn=None, on_fallback=None
                             ) -> Tuple[int, Any]:
        """(step, restored): the newest checkpoint that verifies AND
        restores, walking back past corrupt/torn saves.  Checksum-failed
        steps are quarantined (deleted — they can never restore, and
        leaving them would shadow a later re-save of the same step
        number).  Raises FileNotFoundError when the directory holds no
        checkpoints, CheckpointCorruptError when none is restorable.

        restore_fn(step) overrides the per-step restore (the Trainer
        routes its scaler-retry/EF-reattach restore through here);
        on_fallback(step, why) observes each skipped step (RunLog fault
        events)."""
        from hetu_tpu.obs.metrics import get_registry
        self._finalize_pending()
        reg = get_registry()
        steps = sorted(self.all_steps(), reverse=True)
        if not steps:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        last_err: Optional[BaseException] = None
        for step in steps:
            ok, why = self.verify_step(step)
            if not ok and why.startswith(MANIFEST_UNREADABLE):
                # a torn manifest (crash between data commit and manifest
                # fsync) must not condemn intact data: drop the manifest
                # only — the step demotes to 'unverified' and restore
                # arbitrates
                reg.inc("ckpt.manifests_torn")
                logger.warning(f"dropping torn manifest for step {step} "
                               f"({why}); step demoted to unverified")
                try:
                    os.remove(manifest_path(self.directory, step))
                except OSError:
                    pass
                ok, why = True, "unverified (torn manifest dropped)"
            if not ok:
                reg.inc("ckpt.fallbacks")
                logger.warning(f"checkpoint step {step} failed "
                               f"verification ({why}); falling back")
                self.quarantine(step, why)
                if on_fallback is not None:
                    on_fallback(step, why)
                continue
            try:
                if restore_fn is not None:
                    return step, restore_fn(step)
                return step, self.restore(step, target=target)
            except Exception as e:
                # verified ('unverified' pass included) but unrestorable —
                # FileNotFoundError included: a vanished data file IS the
                # partial-upload fault.  Count + fall back, do NOT
                # quarantine: the bytes may be fine and merely mismatch
                # the CURRENT target (e.g. a changed model); deleting
                # them would destroy good state
                last_err = e
                reg.inc("ckpt.fallbacks")
                logger.warning(f"restore of step {step} raised {e!r}; "
                               "falling back")
                if on_fallback is not None:
                    on_fallback(step, repr(e))
                continue
        raise CheckpointCorruptError(
            f"no restorable checkpoint among steps {steps} in "
            f"{self.directory}"
            + (f" (last error: {last_err!r})" if last_err else ""))

    def quarantine(self, step: int, why: str = ""):
        """Move a corrupt step aside (+ drop its manifest) so it cannot
        shadow a later save of the same step number (orbax silently
        declines to re-save an existing step).  The bytes are PRESERVED
        in a sibling `<directory>.quarantine/` for forensics/repair — a
        checksum-failed step is never auto-restored (that would load
        silently corrupt weights) but it is not destroyed either.  The
        sibling location matters: a renamed step-like dir INSIDE the root
        breaks orbax's step scan.  Best-effort: a live fallback must not
        die here."""
        from hetu_tpu.obs.metrics import get_registry
        get_registry().inc("ckpt.quarantined")
        logger.warning(f"quarantining corrupt checkpoint step {step}"
                       + (f" ({why})" if why else ""))
        step_dir = os.path.join(self.directory, str(int(step)))
        qdir = self.directory.rstrip("/") + ".quarantine"
        moved = False
        try:
            os.makedirs(qdir, exist_ok=True)
            os.rename(step_dir,
                      os.path.join(qdir, f"{int(step)}_{int(time.time())}"))
            moved = True
        except OSError as e:
            logger.warning(f"quarantine move of step {step} failed "
                           f"({e!r}); deleting instead")
        try:
            # sync orbax's cached step list (deletes the dir too when the
            # move failed — shadowing later re-saves is the worse outcome)
            self._mgr.delete(step)
        except Exception:
            if not moved:
                logger.warning(f"quarantine delete of step {step} failed")
            try:
                self._mgr.reload()
            except Exception:
                pass
        try:
            os.remove(manifest_path(self.directory, step))
        except OSError:
            pass

    # ------------------------------------------------------------- admin
    def wait(self):
        # join the manifest writer FIRST (it owns a wait_until_finished of
        # its own) so two threads never wait on orbax concurrently
        self._finalize_pending()
        self._mgr.wait_until_finished()

    def close(self):
        self._finalize_pending()
        self._mgr.close()


def save_checkpoint(path: str, state: Any):
    """One-shot synchronous save (reference temp_save analog)."""
    ckptr = _ocp().StandardCheckpointer()
    ckptr.save(resolve_ckpt_path(path), state, force=True)
    ckptr.wait_until_finished()
    ckptr.close()


def load_checkpoint(path: str, target: Optional[Any] = None) -> Any:
    """One-shot load, resharding into `target`'s shardings if given."""
    ckptr = _ocp().StandardCheckpointer()
    try:
        if target is None:
            return ckptr.restore(resolve_ckpt_path(path))
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=getattr(x, "sharding", None))
            if hasattr(x, "shape") else x,
            target)
        return ckptr.restore(resolve_ckpt_path(path), abstract)
    finally:
        ckptr.close()
