"""Checkpoint / resume — the recovery state plane.

The reference removed BLCR system-level checkpointing in v5 (only the
component-metadata flag remains, opal/mca/mca.h:350) and points users at
app-level checkpointing composed with ULFM (docs/features/ulfm.rst;
SURVEY.md §5.4 asks this framework for modern hooks instead). Here the
hooks are TPU-native:

  * ``save``/``restore``: orbax-backed pytree checkpointing. Save is
    asynchronous (device→host DMA overlaps the next step — the
    accelerator-framework staging discipline applied to state);
  * restore takes a target ``sharding`` pytree/mesh, so state saved on one
    topology restores onto another — THE property elastic ULFM recovery
    needs: detect → revoke → shrink → rebuild a smaller mesh from the
    survivors → ``restore`` onto it (ft/__init__ recipe);
  * ``CheckpointManager``: step-numbered directory layout with retention,
    latest-step discovery, and an every-N-steps ``should_save`` hook.

Single-controller discipline: the controller process drives save/restore
for the whole mesh (orbax handles per-shard IO). In the rank-per-chip
plane, rank 0 of the job drives and the others fence — composing with the
bootstrap exactly like every other collective bring-up step.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any, Dict, Optional

import jax

CHECKSUM_FILE = "ompi_tpu_checksums.json"
_HASH_CHUNK = 1 << 20

# restore-call odometer: elastic recovery (ft/elastic) asserts its
# peer-shadow path moved state with ZERO filesystem round-trips, which
# is only checkable if every restore entry point ticks one counter
_restore_lock = threading.Lock()
_restore_calls = 0


def restore_count() -> int:
    """How many times :func:`restore` has run in this process."""
    with _restore_lock:
        return _restore_calls


def _ocp():
    import orbax.checkpoint as ocp
    return ocp


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint shard failed its blake2s verification on load.

    Recovery (ft/__init__: detect → revoke → shrink → restore) must not
    restore silently corrupted state — a flipped bit in a shard file
    would re-inject exactly the divergence the numerics plane exists to
    catch, one step after the rebuild."""


class CheckpointShapeError(RuntimeError):
    """restore() asked for a GLOBAL array shape different from the one
    saved.

    Mesh and sharding differences are fine — that's what shrink
    recovery and train→serve conversion are — and restore reshards them
    on device.  A different *global* shape is a different model/step;
    reinterpreting the saved bytes onto it would be corruption with
    extra steps, so it fails loudly naming the leaf and both shapes."""


def _file_digest(path: str) -> str:
    h = hashlib.blake2s(digest_size=16)
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(_HASH_CHUNK), b""):
            h.update(chunk)
    return h.hexdigest()


def _shard_files(path: str) -> Dict[str, str]:
    """Relative path -> digest for every payload file under a finalized
    checkpoint directory (the manifest itself is excluded)."""
    out: Dict[str, str] = {}
    for root, _dirs, files in os.walk(path):
        for name in sorted(files):
            if name == CHECKSUM_FILE:
                continue
            full = os.path.join(root, name)
            out[os.path.relpath(full, path)] = _file_digest(full)
    return out


def write_checksums(path: str) -> Dict[str, str]:
    """Bank a blake2s digest per shard file alongside the checkpoint
    (``ompi_tpu_checksums.json``); called after every finalized save."""
    path = os.path.abspath(path)
    digests = _shard_files(path)
    tmp = os.path.join(path, CHECKSUM_FILE + ".tmp")
    with open(tmp, "w") as fh:
        json.dump({"version": 1, "algo": "blake2s-16", "files": digests},
                  fh, indent=1, sort_keys=True)
    os.replace(tmp, os.path.join(path, CHECKSUM_FILE))
    return digests


def verify_checksums(path: str, rank: int = 0) -> int:
    """Re-hash every banked shard file; raise
    :class:`CheckpointCorruptionError` naming the bad shard(s) and the
    restoring rank.  Checkpoints written before the manifest existed
    (no ``ompi_tpu_checksums.json``) verify trivially (returns 0) —
    refusing to restore them would break every pre-existing checkpoint.
    Returns the number of files verified."""
    path = os.path.abspath(path)
    manifest = os.path.join(path, CHECKSUM_FILE)
    if not os.path.exists(manifest):
        return 0
    with open(manifest) as fh:
        banked = json.load(fh).get("files", {})
    bad, missing = [], []
    for rel, want in sorted(banked.items()):
        full = os.path.join(path, rel)
        if not os.path.exists(full):
            missing.append(rel)
        elif _file_digest(full) != want:
            bad.append(rel)
    if bad or missing:
        parts = []
        if bad:
            parts.append(f"corrupted shard file(s) {bad}")
        if missing:
            parts.append(f"missing shard file(s) {missing}")
        raise CheckpointCorruptionError(
            f"checkpoint {path} failed verification on rank {rank}: "
            + "; ".join(parts)
            + " — refusing to restore corrupted state "
            "(ompi_tpu_checksums.json banks the save-time blake2s "
            "digests; the bytes on disk no longer match them)")
    return len(banked)


def save(path: str, state: Any, force: bool = True) -> None:
    """Blocking save of a pytree of (possibly sharded) jax arrays."""
    ckptr = _ocp().StandardCheckpointer()
    ckptr.save(os.path.abspath(path), state, force=force)
    ckptr.wait_until_finished()
    write_checksums(path)


def save_async(path: str, state: Any) -> "AsyncSave":
    """Start an asynchronous save: device→host transfer happens now, disk
    IO in the background; ``wait()`` (or the next save) joins it."""
    ckptr = _ocp().AsyncCheckpointer(_ocp().StandardCheckpointHandler())
    ckptr.save(os.path.abspath(path), args=_ocp().args.StandardSave(state))
    return AsyncSave(ckptr, os.path.abspath(path))


class AsyncSave:
    def __init__(self, ckptr, path: Optional[str] = None) -> None:
        self._ckptr = ckptr
        self._path = path

    def wait(self) -> None:
        if self._ckptr is not None:
            self._ckptr.wait_until_finished()
            # each async save owns its checkpointer; close it or its
            # background threads outlive the save and accumulate
            self._ckptr.close()
            self._ckptr = None
            if self._path:
                # the manifest can only hash FINALIZED bytes: written at
                # join time, after orbax renames the tmp dir into place
                write_checksums(self._path)


def _check_global_shapes(path: str, like: Any, rank: int = 0) -> None:
    """Best-effort pre-restore check of the saved GLOBAL shapes against
    ``like``'s.  Metadata that cannot be read or matched keeps the old
    behavior (orbax's own restore errors stand); a definite mismatch
    raises :class:`CheckpointShapeError` naming the leaf."""
    tu = jax.tree_util
    mismatched = []
    try:
        # StepMetadata: the saved leaves are under item_metadata.tree
        meta = _ocp().StandardCheckpointer().metadata(path).item_metadata.tree
        want = {tu.keystr(kp): tuple(x.shape)
                for kp, x in tu.tree_leaves_with_path(like)
                if hasattr(x, "shape")}
        for kp, m in tu.tree_leaves_with_path(meta):
            saved = tuple(getattr(m, "shape", ()) or ())
            w = want.get(tu.keystr(kp))
            if w is not None and saved and w != saved:
                mismatched.append((tu.keystr(kp), saved, w))
    except Exception:
        return
    if mismatched:
        detail = "; ".join(f"{k}: saved {s} vs requested {w}"
                           for k, s, w in mismatched[:8])
        raise CheckpointShapeError(
            f"checkpoint {path} global-shape mismatch on rank {rank}: "
            f"{detail} — mesh/sharding changes reshard on device, but a "
            "different global shape is a different model; refusing to "
            "reinterpret the saved bytes")


def restore(path: str, like: Any, rank: int = 0,
            source_sharding: Any = None) -> Any:
    """Restore onto the shardings/dtypes/shapes of ``like`` (an abstract or
    concrete pytree). ``like`` may live on a DIFFERENT mesh than the save —
    restore reshards, which is what shrink-recovery needs.  Shard
    files are verified against the save-time checksum manifest first; a
    mismatch raises :class:`CheckpointCorruptionError` naming the bad
    shard and rank, and a genuine global-shape mismatch raises
    :class:`CheckpointShapeError` before any bytes move.

    With ``source_sharding`` (one ``Sharding``, or a pytree of them
    matching ``like``) the shards are read onto the SAVE-TIME layout
    and then redistributed on device through the compiled
    minimal-collective plan engine (``parallel/reshard``) — no host
    round-trip, every step decision-audited and traffic-attributed.
    Without it, the read itself targets ``like``'s layout (orbax
    reshards on read through host IO)."""
    global _restore_calls
    with _restore_lock:
        _restore_calls += 1
    verify_checksums(path, rank=rank)
    path = os.path.abspath(path)
    _check_global_shapes(path, like, rank=rank)
    ckptr = _ocp().StandardCheckpointer()
    if source_sharding is None:
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=_shard(x))
            if hasattr(x, "shape") else x, like)
        return ckptr.restore(path, abstract)
    if isinstance(source_sharding, jax.sharding.Sharding):
        src_tree = jax.tree.map(lambda x: source_sharding, like)
    else:
        src_tree = source_sharding
    abstract = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
        if hasattr(x, "shape") else x, like, src_tree)
    got = ckptr.restore(path, abstract)
    from .parallel.reshard import reshard as _reshard

    def _relayout(g, ref):
        dst = _shard(ref)
        if dst is None or not hasattr(g, "shape"):
            return g
        return _reshard(g, dst)
    return jax.tree.map(_relayout, got, like)


def _shard(x):
    s = getattr(x, "sharding", None)
    return s


class CheckpointManager:
    """Step-numbered checkpoints with retention (keep the newest K), every-N
    cadence, and latest-step discovery — the app-level loop's whole
    checkpoint surface:

        mgr = CheckpointManager(dir, every=100, keep=3)
        for step in ...:
            if mgr.should_save(step):
                mgr.save(step, state)
        state = mgr.restore_latest(like=state)
    """

    def __init__(self, directory: str, every: int = 1, keep: int = 2) -> None:
        self.directory = os.path.abspath(directory)
        self.every = max(1, int(every))
        self.keep = max(1, int(keep))
        os.makedirs(self.directory, exist_ok=True)
        self._pending: Optional[AsyncSave] = None

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:010d}")

    def steps(self) -> list:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_"):
                try:
                    out.append(int(name[5:]))
                except ValueError:
                    pass
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        self.wait()          # an in-flight save IS the latest once finalized
        s = self.steps()
        return s[-1] if s else None

    def should_save(self, step: int) -> bool:
        return step % self.every == 0

    def save(self, step: int, state: Any, blocking: bool = False) -> None:
        if self._pending is not None:
            self._pending.wait()          # one in flight at a time
            self._pending = None
        path = self._step_dir(step)
        if blocking:
            save(path, state)
        else:
            self._pending = save_async(path, state)
        self._retain()

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.wait()
            self._pending = None

    def _retain(self) -> None:
        import shutil
        steps = self.steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    def restore(self, step: int, like: Any,
                source_sharding: Any = None) -> Any:
        self.wait()
        return restore(self._step_dir(step), like,
                       source_sharding=source_sharding)

    def restore_latest(self, like: Any,
                       source_sharding: Any = None) -> Any:
        """Restore the newest step that VERIFIES.  A corrupt newest step
        (flipped bit, truncated shard, missing file) is logged and
        skipped — retention keeps older steps around precisely so one
        bad write doesn't strand the job — and
        :class:`CheckpointCorruptionError` is raised only when no clean
        step remains."""
        from .core.output import output
        steps = [self.latest_step()]          # waits the pending save
        if steps[0] is None:
            raise FileNotFoundError(
                f"no checkpoints under {self.directory}")
        steps = self.steps()
        last_err: Optional[CheckpointCorruptionError] = None
        for step in reversed(steps):
            try:
                verify_checksums(self._step_dir(step))
            except CheckpointCorruptionError as err:
                output.verbose(
                    1, "ckpt",
                    f"step {step} failed verification, falling back to "
                    f"the next-newest clean step: {err}")
                last_err = err
                continue
            return self.restore(step, like,
                                source_sharding=source_sharding)
        raise CheckpointCorruptionError(
            f"all {len(steps)} checkpoint step(s) under {self.directory} "
            "failed verification — no clean step to fall back to"
        ) from last_err
