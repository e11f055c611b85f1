"""A traffic file names its kind of cell; the kind is a file of its own
(``kind_<kind>.py`` with ``run(cell, args, t0) -> exit code``)."""

import importlib


def runner_for(kind: str):
    return importlib.import_module(f"perfbench.lib.kind_{kind}").run
