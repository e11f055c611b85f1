"""DevicePrefetcher unit tests (data/prefetch.py).

The multihost end-to-end tests exercise the consumer-thread staging mode
through the full trainer; these pin the contract down directly: staging
mode selection, state threading, exception surfacing, and stop().
"""

import numpy as np
import pytest

import jax

from fault_tolerant_llm_training_tpu.data.prefetch import DevicePrefetcher


class _StubLoader:
    def __init__(self, n=4, fail_at=None):
        self.n = n
        self.i = 0
        self.fail_at = fail_at
        self.resumed = False

    def resume(self):
        self.resumed = True

    def __next__(self):
        if self.fail_at is not None and self.i == self.fail_at:
            raise ValueError("boom")
        if self.i >= self.n:
            raise StopIteration
        i = self.i
        self.i += 1
        return (np.full((2, 4), i, np.int32), np.full((2, 4), -i, np.int32))

    def get_state(self):
        return {"index": self.i}


@pytest.mark.parametrize("stage_in_worker", [True, False])
def test_prefetcher_stages_and_threads_state(stage_in_worker):
    pf = DevicePrefetcher(_StubLoader(n=3), depth=2,
                          stage_in_worker=stage_in_worker)
    items = list(iter(pf))
    assert pf.loader.resumed
    assert len(items) == 3
    for i, (inputs, labels, state) in enumerate(items):
        # device arrays out in both modes; the staging just happens on a
        # different thread (stage_in_worker=False is the multi-process mode)
        assert isinstance(inputs, jax.Array) and isinstance(labels, jax.Array)
        assert int(inputs[0, 0]) == i and int(labels[0, 0]) == -i
        # the state snapshot matches the batch it was produced after
        assert state == {"index": i + 1}


def test_prefetcher_surfaces_worker_exception():
    pf = DevicePrefetcher(_StubLoader(n=5, fail_at=2), depth=2)
    it = iter(pf)
    next(it)
    next(it)
    with pytest.raises(ValueError, match="boom"):
        while True:
            next(it)


def test_prefetcher_stop_drains():
    pf = DevicePrefetcher(_StubLoader(n=100), depth=2)
    it = iter(pf)
    next(it)
    pf.stop()  # must not deadlock on a full queue
    assert pf._stop.is_set()


@pytest.mark.parametrize("consumed", [0, 1, 50])
def test_prefetcher_close_joins_the_worker(consumed):
    """close() must leave no worker behind, wherever it was: blocked on a
    full queue, mid-batch, or never started. A daemon thread that still
    holds device arrays when the interpreter finalizes aborts the process
    (rc 134) — the exit-0 contract depends on this join."""
    pf = DevicePrefetcher(_StubLoader(n=100), depth=2)
    if consumed:
        it = iter(pf)
        for _ in range(consumed):
            next(it)
    pf.close()
    assert pf._thread is None or not pf._thread.is_alive()
    assert pf._q.empty()
    pf.close()  # idempotent: the exit handler and close() may both run
