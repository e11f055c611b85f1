"""Entry point (ref: train.py:12-134).

Lifecycle: install signal handlers -> build Trainer (setup) -> run the loop ->
route any exception through the exit-policy table -> always exit 0 so Slurm
never marks the job failed (ref: train.py:119,129).
"""

import sys
import time

from fault_tolerant_llm_training_tpu.ft.handler import (
    classify_exception,
    handle_exit,
)
from fault_tolerant_llm_training_tpu.ft.signals import SignalFlag
from fault_tolerant_llm_training_tpu.obs import events
from fault_tolerant_llm_training_tpu.training.loop import Trainer
from fault_tolerant_llm_training_tpu.utils.config import get_args
from fault_tolerant_llm_training_tpu.utils.logging import (
    AUDIT_COMPLETED,
    init_logger,
    logger,
)

_IMPORTS_DONE_T = time.time()  # flight recorder: imports_done


def train(cfg) -> None:
    events.emit_startup(_IMPORTS_DONE_T)
    # Handlers installed before any setup work — a signal during the model
    # build is deferred to a phase boundary instead of being fatal
    # (the reference registers at train.py:89-90, after ~35 s of setup).
    flag = SignalFlag()
    flag.register()
    trainer = None
    try:
        # Signals are deferred (blocked at the OS level) for the whole
        # native-heavy setup: they stay pending and are handled at the first
        # loop boundary with a fully-built trainer — so a preemption during
        # setup still gets a checkpoint+resubmit instead of a dead job.
        with flag.deferred():
            trainer = Trainer(cfg, signal_flag=flag)
        trainer.run()
        # ref: train.py:118 — audit string byte-identical; the paired event
        # closes the flight-recorder chain for goodput stitching.
        events.emit_audit(logger, AUDIT_COMPLETED, "complete",
                          step=trainer.training_step)
        events.flush()
        sys.exit(0)
    except Exception as e:
        error_type = classify_exception(e)  # ref: train.py:122-126
        if error_type == -1:
            # The reference swallows the traceback entirely; log it so code
            # errors are debuggable from the Slurm .out file.
            logger.exception("Unhandled exception (routing to exit handler)")
        # A second signal (Slurm's grace-period SIGTERM chasing the USR1)
        # must not interrupt the checkpoint write — the reference's
        # truncation race (SURVEY.md §5.3).
        try:
            with flag.deferred():
                handle_exit(trainer, error_type, logger)
        except Exception:
            # The exit-0 contract (Slurm must never mark the job failed,
            # ref train.py:119,129) holds even when the handler itself
            # fails — e.g. the checkpoint write dying on a pod whose peers
            # are gone. The traceback is the diagnostic.
            logger.exception("Exit handler failed; exit code preserved")
        sys.exit(0)  # ref: train.py:129 — exit 0 even on error
    finally:
        if trainer is not None:
            try:
                trainer.close()
            except Exception:
                # The exit-0 contract (Slurm must never mark the job failed,
                # ref train.py:119,129) survives a teardown failure.
                logger.exception("close() failed; exit code preserved")


if __name__ == "__main__":
    init_logger()  # ref: train.py:132
    train(get_args())  # ref: train.py:133-134
