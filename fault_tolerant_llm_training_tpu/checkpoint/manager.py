"""Orbax checkpoint manager (ref: utils.py:74-81 save; train.py:20-24 load).

The reference writes one monolithic ``torch.save`` dict named
``checkpoint_{JOBID}.ckpt`` (45 GB, 33.6 s, single writer — BASELINE.md) and
reconstructs the data position by replaying N batches (train.py:36-39). The
TPU-native design:

- **sharded, async** Orbax writes: every host writes its own param shards in
  parallel; training can continue while the write drains (periodic saves),
  and fault-path saves block only until commit;
- **atomic commit**: Orbax finalizes a step directory only after all shards
  land, fixing the reference's truncation race (a SIGTERM during the 33 s
  torch.save leaves a corrupt file — SURVEY.md §5.3);
- **data-iterator state saved in-band** (JSON), so resume is O(1) instead of
  O(steps) replay;
- directory layout keeps the reference's job-id naming contract:
  ``{checkpoint_path}/checkpoint_{JOBID}/{step}/...`` — the chained job passes
  the previous job's id exactly like ``sbatch train.sh $JOBID``
  (ref: train.sh:24-27, utils.py:84);
- **write-path tuning for the USR1 deadline**: Orbax's default zstd
  compression saves ~8% disk on weight tensors but costs 3x wall on one
  core (2.15 GB probe state: 22.1 s compressed vs 7.7 s raw, and 6.4 s
  with zarr3's larger chunk pipeline — measured on this harness,
  BASELINE.md round 3). The save must fit the 120 s USR1 lead (ref
  train.sh:12) at flagship scale, so compression is off and zarr3 on;
  restore auto-detects the format, so pre-tuning checkpoints (zarr2 +
  compressed) remain loadable — both verified bit-exact;
- **budget math** (:func:`measure_write_throughput`,
  :func:`estimate_save_seconds`): the Trainer probes the checkpoint
  filesystem once at construction and logs whether the estimated save
  fits the signal lead, instead of discovering a blown deadline at the
  first preemption.
"""

import json
import os
import time
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from ..obs import events
from ..obs.registry import REGISTRY
from ..utils.logging import (
    AUDIT_CKPT_FALLBACK_FMT,
    AUDIT_CKPT_PARTIAL_SKIPPED_FMT,
    AUDIT_CKPT_VERIFY_FAILED_FMT,
    logger,
)
from ..utils.sync import hard_sync

# Fraction of raw filesystem write throughput the tuned Orbax pipeline
# achieves end-to-end (serialization + chunking + commit). Measured on the
# build harness: 0.33 GB/s orbax vs 0.70 GB/s raw dd on the same disk with
# the same 2.15 GB state (BASELINE.md round 3). Deliberately conservative —
# the estimate guards a hard deadline.
ORBAX_WRITE_EFFICIENCY = 0.45


def state_bytes(tree) -> int:
    """Total bytes of a (possibly abstract) state pytree — the one
    definition shared by the budget estimate and the observed-save log
    (training/loop.py), so they can never diverge."""
    import jax

    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def measure_write_throughput(directory: str,
                             probe_bytes: int = 128 * 2**20) -> float:
    """One-shot raw write throughput of ``directory``'s filesystem, in
    bytes/s (fsync'd, incompressible-ish payload so smart filesystems
    cannot fake it). ~0.2 s at the default size on local SSD. The probe
    file is per-process: on a pod every host probes the shared filesystem
    concurrently, and a shared name would make them contend on one file
    (and race each other's os.remove), measuring noise."""
    import jax

    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f".write_probe.{jax.process_index()}")
    # Genuinely random payload: a counter pattern compresses several-fold
    # on filesystems with transparent compression (ZFS lz4 etc.), which
    # would inflate the measured throughput and silently suppress the
    # budget warning for the incompressible real weights.
    payload = np.random.default_rng(0).integers(
        0, np.iinfo(np.uint64).max, probe_bytes // 8, dtype=np.uint64)
    try:
        t0 = time.monotonic()
        with open(path, "wb") as f:
            f.write(memoryview(payload))
            f.flush()
            os.fsync(f.fileno())
        dt = time.monotonic() - t0
    finally:
        try:
            os.remove(path)
        except OSError:
            pass
    return probe_bytes / max(dt, 1e-6)


def estimate_save_seconds(state_bytes_per_host: int,
                          raw_throughput: float) -> float:
    """Expected blocking-save wall time for this host's shard of the
    state, from the measured raw throughput derated by the Orbax
    pipeline's measured efficiency."""
    return state_bytes_per_host / max(raw_throughput
                                      * ORBAX_WRITE_EFFICIENCY, 1e-6)


# ------------------------------------------------------ integrity manifests
# Every finalized step directory gets an ``integrity.json`` mapping each
# checkpoint file (relative path) to its size and CRC32. Orbax's zarr/ocdbt
# layout stores each array's payload in its own file set under ``state/``,
# so file-level checksums ARE per-array checksums keyed by the array's path.
# The manifest is written AFTER Orbax's atomic commit (a finalized,
# digit-named directory is complete by the rename contract), verified at
# restore, and a failure falls back — audited — to the newest earlier step
# that passes. A step without a manifest (written by an older build, or by
# a job killed before its sweep) is accepted as legacy.

MANIFEST_NAME = "integrity.json"

_M_VERIFY_FAILURES = REGISTRY.counter(
    "checkpoint_verify_failures_total",
    "Checkpoint step directories that failed integrity verification at "
    "restore")
_M_LAST_SUCCESS_AGE = REGISTRY.gauge(
    "checkpoint_last_success_age_seconds",
    "Seconds since this process last finalized a checkpoint save or "
    "completed a verified restore (staleness input for SLO alerts)")
_last_success_t: Optional[float] = None


def _mark_checkpoint_success() -> None:
    global _last_success_t
    _last_success_t = time.monotonic()
    _M_LAST_SUCCESS_AGE.set(0.0)


def update_checkpoint_age_gauge() -> None:
    """Refresh ``checkpoint_last_success_age_seconds`` — called on the
    training loop's logging cadence and per serve-loop iteration, so the
    gauge ages between checkpoint events instead of freezing at 0."""
    if _last_success_t is not None:
        _M_LAST_SUCCESS_AGE.set(time.monotonic() - _last_success_t)


class CheckpointIntegrityError(RuntimeError):
    """No checkpoint step passed integrity verification."""


def _crc32_file(path: str, chunk_bytes: int = 1 << 20) -> int:
    crc = 0
    with open(path, "rb") as fh:
        while True:
            chunk = fh.read(chunk_bytes)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def _fsync_dir(path: str) -> None:
    """fsync a directory fd so a just-renamed/just-written entry is durable
    (a kill after rename but before the metadata flush could otherwise
    resurface as a half-visible step on the next mount)."""
    fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    try:
        os.fsync(fd)
    except OSError:
        pass  # some filesystems refuse directory fsync; rename is still atomic
    finally:
        os.close(fd)


def _manifest_files(step_dir: str) -> Dict[str, Dict[str, int]]:
    files: Dict[str, Dict[str, int]] = {}
    for root, _dirs, names in os.walk(step_dir):
        for name in names:
            if name.startswith(MANIFEST_NAME):  # the manifest and its tmps
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, step_dir)
            files[rel] = {"size": os.path.getsize(path),
                          "crc32": _crc32_file(path)}
    return files


def write_manifest(step_dir: str, step: int) -> int:
    """Checksum every file of a FINALIZED step dir into integrity.json
    (atomic tmp-rename write, fsync'd file and directory). On a pod every
    host sweeps the shared checkpoint root after a commit, so the tmp name
    is per process: two hosts writing the same (identical) manifest must
    not rename each other's tmp file away. Returns the bytes checksummed."""
    manifest = {"version": 1, "step": int(step),
                "files": _manifest_files(step_dir)}
    tmp = os.path.join(step_dir, f"{MANIFEST_NAME}.tmp-{os.getpid()}")
    with open(tmp, "w") as fh:
        json.dump(manifest, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, os.path.join(step_dir, MANIFEST_NAME))
    _fsync_dir(step_dir)
    return sum(meta["size"] for meta in manifest["files"].values())


def verify_step_dir(step_dir: str) -> Tuple[bool, str]:
    """Check a step dir against its manifest. Returns ``(ok, detail)``.
    Missing manifest = legacy checkpoint, accepted. Extra files (e.g.
    later-version metadata) are ignored — only manifest-listed files are
    load-bearing for the restore."""
    return _verify_step_dir(step_dir)[:2]


def _verify_step_dir(step_dir: str) -> Tuple[bool, str, int]:
    """:func:`verify_step_dir` plus the bytes it checksummed."""
    manifest_path = os.path.join(step_dir, MANIFEST_NAME)
    if not os.path.isdir(step_dir):
        return False, "step directory missing", 0
    if not os.path.isfile(manifest_path):
        return True, "no manifest (legacy checkpoint)", 0
    try:
        with open(manifest_path) as fh:
            manifest = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        return False, f"unreadable manifest ({e})", 0
    checked = 0
    for rel, meta in sorted(manifest.get("files", {}).items()):
        path = os.path.join(step_dir, rel)
        if not os.path.isfile(path):
            return False, f"missing file {rel}", checked
        size = os.path.getsize(path)
        if size != meta["size"]:
            return False, (f"size mismatch {rel} "
                           f"({size} != {meta['size']})"), checked
        checked += size
        if _crc32_file(path) != meta["crc32"]:
            return False, f"crc mismatch {rel}", checked
    return True, "ok", checked


def _pytree_handler_kwargs() -> dict:
    """zarr3 without compression (module docstring: 3x faster saves for ~8%
    more disk). ``use_compression`` only exists on newer orbax; older ones
    (0.7.x) write zarr3 uncompressed by default, so just drop the kwarg."""
    import inspect

    kwargs = {"use_zarr3": True}
    params = inspect.signature(ocp.PyTreeCheckpointHandler.__init__).parameters
    if "use_compression" in params:
        kwargs["use_compression"] = False
    return kwargs


class CheckpointManager:
    def __init__(self, checkpoint_path: str, job_id: str,
                 enable_async: bool = True, max_to_keep: int = 2):
        self.directory = os.path.join(
            os.path.abspath(checkpoint_path), f"checkpoint_{job_id}")
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            enable_async_checkpointing=enable_async,
            create=True,
        )
        self._mngr = ocp.CheckpointManager(
            self.directory, options=options,
            # see module docstring: 3x faster saves for ~8% more disk;
            # the deadline is the product, the disk is not. (An explicit
            # item_handlers dict disables per-item auto-resolution, so
            # the JSON data item must be registered alongside.)
            item_handlers={
                "state": ocp.PyTreeCheckpointHandler(**_pytree_handler_kwargs()),
                "data": ocp.JsonCheckpointHandler(),
            })
        self.last_save_seconds: Optional[float] = None
        self._partial_audited: set = set()

    def _finalize_integrity(self) -> None:
        """Post-commit sweep of the job's checkpoint root: write integrity
        manifests for finalized step dirs that lack one, audit (once per
        name) any leftover non-finalized temp dir, and fsync the root so
        the just-renamed entries are durable. Orbax's commit protocol makes
        a digit-named directory complete by construction — anything else
        (``<step>.orbax-checkpoint-tmp-*`` style) is an interrupted write
        the restore scan must never pick up."""
        if not os.path.isdir(self.directory):
            return
        t0, written, checked = time.monotonic(), [], 0
        for name in sorted(os.listdir(self.directory)):
            path = os.path.join(self.directory, name)
            if not os.path.isdir(path):
                continue
            if name.isdigit():
                if not os.path.isfile(os.path.join(path, MANIFEST_NAME)):
                    checked += write_manifest(path, int(name))
                    written.append(int(name))
            elif "tmp" in name and name not in self._partial_audited:
                self._partial_audited.add(name)
                events.emit_audit(
                    logger, AUDIT_CKPT_PARTIAL_SKIPPED_FMT.format(name=name),
                    "ckpt_partial_skipped", name=name)
        _fsync_dir(self.directory)
        if written:
            events.emit("ckpt_manifest", step=written[-1],
                        dur=time.monotonic() - t0, bytes=checked,
                        steps=written)

    def save(self, step: int, state: Any, data_state: dict,
             wait: bool = False) -> int:
        """Async sharded save of the TrainState + data-iterator position.
        ``wait=True`` blocks until the atomic commit (fault path) and
        records the wall time in ``last_save_seconds`` — the observed
        number the budget estimate exists to predict."""
        hard_sync(state)  # value-dependent barrier; see utils/sync.py
        if not wait:
            # The train step donates its state buffers (loop.py
            # donate_argnums): once the loop dispatches the next step, the
            # arrays this save captured are backed by buffers XLA is free
            # to reuse. Orbax's async device-to-host copy can then read
            # LATER steps' values — a torn checkpoint whose step dir name,
            # data position, and per-array contents disagree (observed:
            # dir 10 containing step-12 params beside step-10 loader
            # state; found by scripts/chaos_campaign.py). Snapshot into
            # fresh buffers (same sharding) so the async write has sole
            # ownership. Fault-path saves block, so they skip the copy.
            state = jax.tree_util.tree_map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                state)
        t0 = time.monotonic()
        self._mngr.save(
            step,
            args=ocp.args.Composite(
                state=ocp.args.PyTreeSave(state),
                data=ocp.args.JsonSave(data_state),
            ),
        )
        if wait:
            self._mngr.wait_until_finished()
            self.last_save_seconds = time.monotonic() - t0
            # The atomic-rename contract: a blocking save that returned
            # must have produced the finalized digit-named directory. If
            # Orbax's commit protocol ever regresses (or a filesystem
            # lies), fail HERE, not at the eventual restore.
            step_dir = os.path.join(self.directory, str(step))
            assert os.path.isdir(step_dir), (
                f"checkpoint step {step} reported saved but {step_dir} "
                f"does not exist — atomic rename contract violated")
            self._finalize_integrity()
            _mark_checkpoint_success()
        return step

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def _verified_step(self, step: Optional[int]) -> int:
        """Integrity gate for restore: scan candidate steps newest-first,
        return the newest one whose directory passes its manifest. Every
        rejected candidate is audited (``[CKPT VERIFY] ... failed``) and
        counted; taking anything but the newest candidate is itself
        audited (``[CKPT VERIFY] Falling back ...``) so the automatic
        recovery is visible in the .out file and the flight recorder, not
        silent. Raises :class:`CheckpointIntegrityError` if nothing
        passes."""
        steps = sorted(self._mngr.all_steps(), reverse=True)
        if not steps:
            raise FileNotFoundError(f"no checkpoint steps in {self.directory}")
        if step is None:
            candidates = steps
        else:
            # An explicitly requested step still gets verified, and still
            # falls back to older steps if corrupt — recovery beats
            # precision when the alternative is a crash loop.
            candidates = [s for s in steps if s <= step] or steps
        chosen = None
        t0, checked = time.monotonic(), 0
        for cand in candidates:
            ok, detail, n = _verify_step_dir(
                os.path.join(self.directory, str(cand)))
            checked += n
            if ok:
                chosen = cand
                break
            _M_VERIFY_FAILURES.inc()
            events.emit_audit(
                logger,
                AUDIT_CKPT_VERIFY_FAILED_FMT.format(step=cand, detail=detail),
                "ckpt_verify_failed", step=int(cand), detail=detail,
                ok=False)
        events.emit("ckpt_verify", step=chosen, dur=time.monotonic() - t0,
                    bytes=checked, ok=chosen is not None)
        if chosen is None:
            raise CheckpointIntegrityError(
                f"no checkpoint step in {self.directory} passed integrity "
                f"verification (tried {candidates})")
        if chosen != candidates[0]:
            events.emit_audit(
                logger, AUDIT_CKPT_FALLBACK_FMT.format(step=chosen),
                "ckpt_fallback", step=int(chosen),
                rejected=[int(s) for s in candidates
                          if s > chosen])
        return chosen

    def restore(self, abstract_state: Any,
                step: Optional[int] = None) -> Tuple[Any, dict, int]:
        """Restore (state, data_state, step) — the newest step that passes
        integrity verification (see :meth:`_verified_step`; a corrupt
        newest checkpoint falls back, audited, to the previous passing
        one). ``abstract_state`` is a ShapeDtypeStruct pytree (with
        shardings) from ``jax.eval_shape`` — params land directly as
        sharded device arrays on the current mesh, the equivalent of the
        reference's cpu-load + load_state_dict (train.py:22,56-58) without
        the host bounce."""
        step = self._verified_step(step)
        restored = self._mngr.restore(
            step,
            args=ocp.args.Composite(
                # Explicit per-leaf restore args carry the TARGET mesh's
                # shardings: bare PyTreeRestore would fall back to the
                # sharding file — i.e. the SAVING topology — which breaks
                # cross-topology resume (SURVEY §7.3 hard part 3).
                state=ocp.args.PyTreeRestore(
                    abstract_state,
                    restore_args=ocp.checkpoint_utils.construct_restore_args(
                        abstract_state)),
                data=ocp.args.JsonRestore(),
            ),
        )
        _mark_checkpoint_success()
        return restored["state"], restored["data"], step

    def wait_until_finished(self) -> None:
        self._mngr.wait_until_finished()
        self._finalize_integrity()
        _mark_checkpoint_success()

    def close(self) -> None:
        self._mngr.wait_until_finished()
        self._finalize_integrity()
        self._mngr.close()
