"""Restart cost a cycle: (sum over the run's cycles of drain + resume) / cycles.
drain = ``os.kill(SIGUSR1)`` -> the child's exit reaped; resume = the next
child's ``Device |`` line -> ``block_until_ready`` of its first optimizer
step. The hand-over between them is ``proc_start_s``. All the recovery time
over all the recoveries: no median, no best-of. (ISSUE 23 defined it as an
end-to-end metric; it is per-layer until the ledger shows a spread that a
bound can hold.)"""


def read(ctx):
    return ctx["e2e"].get("recover_cycle_s")
