"""The benchmark's own code: nothing here imports the program but
`served.py`, which starts it."""

import ctypes
import os
import signal


def die_with_parent() -> None:
    """What a child of `run.py` calls first: the kernel ends it when the
    run's process ends, however that ends (a time limit's SIGKILL
    too), so that no run leaves a loader or a generator behind."""
    parent = os.getppid()
    ctypes.CDLL(None, use_errno=True).prctl(1, signal.SIGTERM)  # PR_SET_PDEATHSIG
    if os.getppid() != parent:      # it had ended already
        raise SystemExit(143)
