"""Faults planted in the timed path, to show that ``correct`` catches them.

Each app adapter (``bench/apps/<app>.py``) holds the faults its cells can
have: ``FAULTS`` maps a fault's name to a function that returns the
program's class and the methods that replace its own, and
``faults_for(cell)`` names those a cell can have.  The names:

* ``unchanged`` — a round returns its state unchanged;
* ``half_batch`` — half of the batch left out;
* ``altered`` — an answer altered where it is produced;
* ``exchange`` — the exchange between chips left out.

``planted(app, fault)`` patches the class for the duration of a run (the
engine traces the patched methods when it compiles).  The benchmark's own
runs never plant one; ``bench/control.py`` and the tests do.
"""
from __future__ import annotations

import contextlib
import importlib


@contextlib.contextmanager
def planted(app: str, fault: str):
    """Patch the program with ``fault`` of ``bench/apps/<app>.py``."""
    table = importlib.import_module(f"bench.apps.{app}").FAULTS
    if fault not in table:
        raise ValueError(f"the {app} cells cannot have the fault {fault!r}; "
                         f"they can have {sorted(table)}")
    cls, patches = table[fault]()
    saved = {k: cls.__dict__[k] for k in patches}
    try:
        for k, fn in patches.items():
            setattr(cls, k, fn)
        yield
    finally:
        for k, fn in saved.items():
            setattr(cls, k, fn)
