"""Watcher + verified zero-downtime hot weight reload for serving.

:class:`PointerWatcher` polls the trainer's ``published.json``
(deploy/publish.py) and offers each distinct publish exactly once.
:class:`HotReloader` then runs the swap state machine::

    VERIFY  -> pointer digest + per-file CRC check of the published step
               (and its draft sub-pointer) BEFORE anything is loaded; a
               failing publish is rejected + audited, serving continues
               on current weights
    PAUSE   -> admission closes (Scheduler.stop_admission); the reload is
               driven between decode iterations, so the in-flight round
               has already finished — no request is dropped, no slot
               freed
    RESTORE -> params restored through the same cross-topology path as
               startup (engine.restore_params) onto the engine's mesh; a
               restore that silently fell back to an OLDER step
               (checkpoint/manager.py _verified_step) is treated as a
               rejection — the pointer names ONE step, serving never
               downgrades implicitly. When the pointer carries a
               ``weights`` sub-entry (quantize-at-publish,
               deploy/publish.py), the CRC-verified int8 artifact is
               loaded and dequantized instead — serving never reads the
               full-precision checkpoint at all
    SWAP    -> engine.reload_params installs the new arrays into the
               running AOT programs (no re-compile — the programs take
               params per call, only the cache is donated); draft params
               swap in the same pause; the prefix cache is flushed (its
               cached KV was computed with the OLD weights)
    RESUME  -> admission reopens; the swap is audited
               (AUDIT_RELOAD_FMT) with counter + step gauge +
               swap-latency histogram on /metrics

In-flight requests keep their already-computed KV: from the swap on,
their decode runs new weights over old-KV context (the standard
continuous-batching reload semantics — finishing a started stream beats
dropping it). Requests ADMITTED after the swap run prefill + decode
entirely under the new weights, so their streams bit-match a fresh serve
of the published step — the property the chaos campaign pins.
"""

import os
import time
from typing import Optional

from ..ft.retry import RetryDeadlineExceeded, retry_with_backoff
from ..obs import events
from ..obs.registry import REGISTRY
from ..utils.logging import (
    AUDIT_ADAPTER_FMT,
    AUDIT_RELOAD_FMT,
    AUDIT_RELOAD_REJECTED_FMT,
    logger,
)
from .publish import (
    Pointer,
    load_weights_artifact,
    read_pointer_strict,
    verify_pointer,
)

_M_RELOADS = REGISTRY.counter(
    "ftl_weights_reload_total",
    "Hot weight swaps completed by the serving process")
_M_WEIGHTS_BYTES = REGISTRY.gauge(
    "weights_artifact_bytes",
    "Payload bytes of the quantized weights artifact currently serving "
    "(0 when weights came from a full-precision checkpoint restore)")
_M_REJECTED = REGISTRY.counter(
    "ftl_weights_reload_rejected_total",
    "Published checkpoints rejected by verify-before-load")
_M_STEP = REGISTRY.gauge(
    "ftl_weights_step",
    "Checkpoint step of the weights currently being served")
_M_SWAP = REGISTRY.histogram(
    "ftl_weights_swap_seconds",
    "Wall time of one hot weight swap (verify + restore + install)")


class PointerWatcher:
    """Offer each distinct publish of ``published.json`` exactly once.

    Distinctness is the (job_id, step, digest) triple, so a republish of
    the same step with a rewritten manifest is a NEW offer, while a
    rejected publish is not re-verified on every poll — the trainer must
    publish something new to be considered again.

    Transient pointer-read failures (a slow or flapping filesystem, a
    mid-replace window) are retried with a bounded deadline
    (ft/retry.py, the same policy as the fleet lease path): on expiry the
    poll renders a clean "no pointer this poll" verdict — a dead
    coordinator costs at most ``deadline_seconds`` per poll, never a hang
    and never a crashed serving process.
    """

    def __init__(self, root: str, deadline_seconds: float = 1.0,
                 clock=time.monotonic, sleep=time.sleep):
        self.root = os.path.abspath(root)
        self.deadline = float(deadline_seconds)
        self.clock = clock
        self.sleep = sleep
        self._seen = None

    def poll(self) -> Optional[Pointer]:
        try:
            ptr = retry_with_backoff(
                lambda: read_pointer_strict(self.root),
                deadline_seconds=self.deadline,
                retry_on=(OSError, ValueError, KeyError, TypeError),
                clock=self.clock, sleep=self.sleep,
                what="published.json read")
        except RetryDeadlineExceeded as e:
            logger.warning(f"[DEPLOY] pointer poll gave up: {e}")
            return None
        if ptr is None:
            return None
        key = (ptr.job_id, ptr.step, ptr.manifest_digest)
        if key == self._seen:
            return None
        self._seen = key
        return ptr


class HotReloader:
    """Swap serving weights to a verified published checkpoint in a
    prefill-pause (module docstring has the state machine)."""

    def __init__(self, engine, scheduler, cfg, checkpoint_path: str,
                 draft_cfg=None, adaptive_k=None, chaos=None,
                 clock=time.monotonic):
        self.engine = engine
        self.scheduler = scheduler
        self.cfg = cfg
        self.draft_cfg = draft_cfg
        self.root = os.path.abspath(checkpoint_path)
        self.adaptive_k = adaptive_k
        self.chaos = chaos
        self.clock = clock
        self.reloads = 0
        self.rejects = 0
        current = getattr(engine, "restored_step", None)
        if current is not None:
            _M_STEP.set(int(current))

    def _reject(self, ptr: Pointer, detail: str, current) -> None:
        self.rejects += 1
        _M_REJECTED.inc()
        events.emit_audit(
            logger,
            AUDIT_RELOAD_REJECTED_FMT.format(step=ptr.step, detail=detail,
                                             current=current),
            "weights_reload_rejected", step=int(ptr.step), detail=detail,
            current=current)
        events.flush()

    def maybe_reload(self, ptr: Optional[Pointer]) -> bool:
        """Verify + swap to ``ptr``; returns True iff the swap completed.
        Must be called between scheduler.step() iterations (the serve
        loop's cadence) so no decode round is in flight."""
        if ptr is None:
            return False
        from ..inference.engine import restore_params
        from ..models.llama import unstack_layer_params

        current = getattr(self.engine, "restored_step", None)
        ok, detail = verify_pointer(self.root, ptr)
        if not ok:
            self._reject(ptr, detail, current)
            return False
        t0 = self.clock()
        was_open = self.scheduler.admission_open
        self.scheduler.stop_admission()
        art_bytes = 0
        try:
            if ptr.weights is not None:
                # quantize-at-publish path: the verified artifact IS the
                # weights — dequantized back to checkpoint dtype, the
                # full-precision checkpoint is never read by serving
                if int(ptr.weights.get("step", -1)) != ptr.step:
                    self._reject(
                        ptr, "weights sub-pointer names step "
                             f"{ptr.weights.get('step')}, pointer names "
                             f"{ptr.step}", current)
                    return False
                params = load_weights_artifact(self.root, ptr.weights)
                art_bytes = int(ptr.weights.get("nbytes", 0))
            else:
                params, got = restore_params(
                    self.root, ptr.job_id, self.cfg, step=ptr.step,
                    mesh=getattr(self.engine, "mesh", None))
                if got != ptr.step:
                    self._reject(ptr, f"restore fell back to step {got}",
                                 current)
                    return False
            if self.cfg.layer_impl == "scan":
                # the engine converted to loop form at build; mirror it
                params = unstack_layer_params(params, self.cfg.n_layers)
            draft_params = None
            if ptr.draft is not None and getattr(self.engine, "spec_k", 0):
                if self.draft_cfg is None:
                    self._reject(ptr, "pointer carries a draft but serving "
                                      "was built without one", current)
                    return False
                draft_params, dgot = restore_params(
                    self.root, str(ptr.draft["job_id"]), self.draft_cfg,
                    step=int(ptr.draft["step"]),
                    mesh=getattr(self.engine, "mesh", None))
                if dgot != int(ptr.draft["step"]):
                    self._reject(ptr, f"draft restore fell back to step "
                                      f"{dgot}", current)
                    return False
                if self.draft_cfg.layer_impl == "scan":
                    draft_params = unstack_layer_params(
                        draft_params, self.draft_cfg.n_layers)
            if self.chaos is not None:
                # mid-swap fault window: new params restored but not yet
                # installed — a reload_signal lands here
                self.chaos.on_reload(self.reloads + 1)
            self.engine.reload_params(params)
            adapters_swapped = 0
            if ptr.adapters:
                # Tenant adapter hot-swap, in the SAME pause and equally
                # recompile-free (the programs take the adapter pool per
                # call): each verified sub-pointer registers its artifact
                # and, when that adapter is resident, pages the new
                # version in ALONGSIDE the old one — in-flight slots keep
                # decoding the version they pinned until they drain
                # (adapters.py swap/release). A pool too full to hold
                # both versions defers THAT adapter (old keeps serving);
                # it never rejects the weights swap.
                mgr = getattr(self.engine, "adapters", None)
                if mgr is None:
                    logger.warning(
                        "[DEPLOY] pointer carries %d adapter sub-"
                        "pointer(s) but serving was built without "
                        "adapter serving (adapter_rank=0); ignoring",
                        len(ptr.adapters))
                else:
                    for name, sub in sorted(ptr.adapters.items()):
                        art_dir = os.path.join(self.root,
                                               str(sub["path"]))
                        if mgr.swap(name, art_dir):
                            adapters_swapped += 1
                            events.emit_audit(
                                logger, AUDIT_ADAPTER_FMT.format(
                                    action="swap", name=name,
                                    pages=mgr.layout.pages_per_adapter,
                                    detail=f"step {sub.get('step', 0)} "
                                           f"in-flight slots preserved"),
                                "adapter", name=name,
                                step=int(sub.get("step", 0)))
                        else:
                            logger.warning(
                                "[DEPLOY] adapter %s swap deferred: the "
                                "adapter pool cannot hold the new "
                                "version alongside the in-flight one",
                                name)
            if draft_params is not None:
                self.engine.reload_draft_params(draft_params)
                if self.adaptive_k is not None:
                    # a fresh draft resets the acceptance estimate: start
                    # optimistic again instead of dragging the stale
                    # draft's learned-down k into the new regime
                    self.adaptive_k.reset()
            if getattr(self.scheduler, "prefix_cache", None) is not None:
                self.scheduler.prefix_cache.flush()
                # rows of the old weights, like the cached blocks: a free
                # slot's held window rings die with them
                self.scheduler.held_rings.clear()
            self.engine.restored_step = ptr.step
            self.reloads += 1
        except Exception as e:  # a verified step should restore; if the
            # filesystem disagrees mid-read, reject and keep serving
            self._reject(ptr, f"restore failed ({e})", current)
            return False
        finally:
            if was_open:
                self.scheduler.resume_admission()
        dt = self.clock() - t0
        _M_RELOADS.inc()
        _M_STEP.set(int(ptr.step))
        _M_SWAP.observe(dt)
        _M_WEIGHTS_BYTES.set(art_bytes)
        events.emit_audit(
            logger,
            AUDIT_RELOAD_FMT.format(old=current, new=ptr.step,
                                    active=len(self.scheduler.active),
                                    ms=dt * 1e3),
            "weights_reload", step=int(ptr.step), old=current, dur=dt,
            active=len(self.scheduler.active), draft=bool(ptr.draft),
            weights=bool(ptr.weights), artifact_bytes=art_bytes,
            adapters=adapters_swapped)
        events.flush()
        return True
