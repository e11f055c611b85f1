"""Host -> device double-buffered prefetch.

The reference has *no* prefetch: a synchronous ``.to(device)`` per step with
``num_workers=0`` tokenization on the critical path (ref: train.py:93-96,
dataset.py:27-35; SURVEY.md §5.8 flags this as the gap). Here a background
thread tokenizes/collates ahead while ``jax.device_put`` (async under the
hood) stages batches into HBM with the batch's NamedSharding, so the TPU never
waits on the host in steady state.

Checkpoint correctness under prefetch: the loader's position runs ``depth``
batches ahead of what the trainer has consumed, so each queued batch carries
the loader-state snapshot taken *right after* it was produced. The trainer
checkpoints the snapshot of the last batch it actually consumed — restoring
that state resumes at exactly the first unconsumed batch, prefetch depth
notwithstanding.
"""

import queue
import threading
from typing import Optional, Tuple

import jax
import numpy as np

from ..obs.trace import span


class DevicePrefetcher:
    """Wraps a DataLoader; yields ``(inputs_dev, labels_dev, data_state)``.

    Single-process: the worker thread both tokenizes and stages to the
    device, so steady state never waits on the host. Multi-process: staging
    moves to the consumer thread — issuing JAX operations from a background
    thread concurrently with the main thread's dispatches is not safe when
    a cross-process runtime (gloo on CPU pods) is underneath (observed as
    collective payload-size mismatches); tokenization, the expensive part,
    still runs ahead in the worker.
    """

    def __init__(self, loader, sharding=None, depth: int = 2,
                 stage_in_worker: Optional[bool] = None,
                 chaos_on_batch=None, start_batch: int = 0):
        self.loader = loader
        self.sharding = sharding
        self.depth = max(1, depth)
        # Chaos hook (chaos/injector.py on_batch): called in the worker
        # with the global step the produced batch will feed, BEFORE it is
        # queued — a loader_stall delays exactly that batch's delivery.
        # start_batch is the resume step so schedule steps stay global.
        self._chaos_on_batch = chaos_on_batch
        self._batch_index = start_batch
        if stage_in_worker is None:
            stage_in_worker = jax.process_count() == 1
        self.stage_in_worker = stage_in_worker
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._exc: Optional[BaseException] = None
        self._started = False

    def _stage(self, arr: np.ndarray):
        if self.sharding is not None:
            return jax.device_put(arr, self.sharding)
        return jax.device_put(np.asarray(arr))

    def _stage_pair(self, inputs: np.ndarray, labels: np.ndarray):
        """Host-sharded loaders carry only this host's rows and assemble
        the global array themselves (loader.stage_global); replicated
        loaders device_put the full batch against the global sharding."""
        if hasattr(self.loader, "stage_global"):
            return self.loader.stage_global(inputs, labels)
        return self._stage(inputs), self._stage(labels)

    def _worker(self):
        try:
            while not self._stop.is_set():
                # one batch's host work; the wait for room in the queue
                # below is not part of it
                with span("ftl:data.prefetch", batch=self._batch_index):
                    try:
                        inputs, labels = next(self.loader)
                    except StopIteration:
                        break
                    state = self.loader.get_state()
                    if self.stage_in_worker:
                        inputs, labels = self._stage_pair(inputs, labels)
                    if self._chaos_on_batch is not None:
                        self._chaos_on_batch(self._batch_index)
                    self._batch_index += 1
                self._q.put((inputs, labels, state))
        except BaseException as e:  # surfaced to the consumer
            self._exc = e
        finally:
            self._q.put(None)

    def __iter__(self):
        if not self._started:
            self._started = True
            self.loader.resume()
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()
        return self

    def __next__(self) -> Tuple[jax.Array, jax.Array, dict]:
        if not self._started:
            iter(self)
        item = self._q.get()
        if item is None:
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        if not self.stage_in_worker:
            inputs, labels, state = item
            inputs, labels = self._stage_pair(inputs, labels)
            return inputs, labels, state
        return item

    def _drain(self):
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def stop(self):
        """Tell the background thread to stop and drain the queue (used on
        fault exits so the checkpoint write is not racing tokenization).
        Does not wait for the thread: the fault-path save must not queue
        behind a batch still being tokenized."""
        self._stop.set()
        self._drain()

    def close(self):
        """:meth:`stop`, then join the worker. Must run before the
        interpreter finalizes: the worker is a daemon thread that holds
        device arrays, and a daemon thread that drops a ``jax.Array``
        (or sits in ``device_put``) once finalization has begun is
        ``pthread_exit``-ed inside jaxlib's noexcept destructor — glibc
        aborts the process (``FATAL: exception not rethrown``, rc 134),
        which the scheduler reads as a failed job."""
        self.stop()
        t = self._thread
        while t is not None and t.is_alive():
            t.join(timeout=0.05)
            self._drain()  # a worker blocked in put() needs room to leave
