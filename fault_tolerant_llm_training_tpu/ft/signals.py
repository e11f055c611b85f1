"""Signal capture (ref: utils.py:93-97, train.py:89-90).

The reference's handler raises an exception *directly from the signal
handler*, which can fire anywhere in Python — including inside the checkpoint
write (SURVEY.md §5.3 lists this as a known race). Under JAX the situation is
sharper still: a Python exception cannot interrupt XLA execution at all.

So this framework uses the flag pattern (SURVEY.md §7.1): the POSIX handler
only records the signal number (an atomic int store); the host loop calls
``check()`` between step dispatches — and during setup phase boundaries,
closing the reference's unprotected-setup window (train.py:42-84 runs ~35 s
before handlers are registered at :89) — which re-raises it as a
``TrainingSignal`` carrying the same ``("Exception", signum)`` args shape the
reference's classification logic expects (train.py:122-126).

Signal-number contract (Linux): SIGUSR1=10 (Slurm pre-timeout warning, armed
by ``--signal=USR1@120``, ref train.sh:12), SIGTERM=15 (scancel); injected
code errors use -1.
"""

import contextlib
import os
import signal
from typing import Optional

_FAULT_SIGNALS = {signal.SIGUSR1, signal.SIGTERM}


def inject(signum: int) -> None:
    """Deliver a real POSIX signal to this process (the chaos injection
    path, chaos/injector.py). Routing through ``os.kill`` — not a direct
    flag mutation — means the installed handler, the first-signal-wins
    latch, ``deferred()`` masking and the multihost agreement all run
    exactly as they would for a scheduler-sent signal."""
    os.kill(os.getpid(), signum)


class TrainingSignal(Exception):
    """Raised between steps when a POSIX signal was received.

    ``args == ("Exception", signum)`` so ``e.args[1]`` yields the error type,
    exactly like the reference's re-raise (ref: utils.py:97).
    """

    def __init__(self, signum: int):
        super().__init__("Exception", signum)
        self.signum = signum


class SignalFlag:
    """Records the latest fault signal; checked by the host loop."""

    def __init__(self):
        self.signum: Optional[int] = None
        self.received: list = []  # every fault signal, in arrival order

    def _handler(self, signum, frame):
        self.received.append(signum)
        if self.signum is None:
            # First signal wins: a SIGTERM chasing the USR1 pre-warning (the
            # Slurm grace-period pattern) must not flip a pending
            # save-and-requeue into a no-save cancel. The reference has the
            # inverse race — its second signal raises *inside* the save
            # handler and truncates the checkpoint (SURVEY.md §5.3).
            self.signum = signum

    def register(self) -> None:
        """Install for SIGUSR1 and SIGTERM (ref: train.py:89-90) — call as
        early as possible, before model build."""
        signal.signal(signal.SIGUSR1, self._handler)
        signal.signal(signal.SIGTERM, self._handler)

    def check(self, synced: bool = False) -> None:
        """Raise ``TrainingSignal`` if a fault signal is pending.

        ``synced=True`` first agrees on a cluster-wide verdict with the
        other hosts (ft/multihost.py ``agree_on_signal``, a one-shot
        KV-store voting round here — the trainer's loop manages proper
        round ids itself): either every host raises at this boundary or
        none does — a host raising alone would deadlock the rest inside
        the next XLA collective. Single-process: identical to
        ``synced=False``.
        """
        signum = self.signum
        if synced:
            from .multihost import agree_on_signal

            signum = agree_on_signal(signum)
        if signum is not None:
            self.signum = None
            raise TrainingSignal(signum)

    @contextlib.contextmanager
    def deferred(self):
        """Block fault-signal *delivery* (pthread_sigmask) for the scope.

        A signal interrupting native code (XLA compilation, the PJRT
        client's backend init, an Orbax commit) can wedge the process via
        EINTR mishandling deep in C++ — observed hanging backend init. During
        setup and during the exit handler the signals are therefore blocked
        at the OS level; they stay *pending* and are delivered (and recorded
        by the flag) the moment the scope exits, where the next ``check()``
        picks them up at a safe boundary.
        """
        signal.pthread_sigmask(signal.SIG_BLOCK, _FAULT_SIGNALS)
        try:
            yield
        finally:
            signal.pthread_sigmask(signal.SIG_UNBLOCK, _FAULT_SIGNALS)
