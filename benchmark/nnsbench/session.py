"""What a configuration's program-side file (``configs/<name>.py``) needs
from the run that opens it. The program ``exec``s that file with no
arguments, so the run publishes itself here first: one object, set by
``activate`` and cleared by ``deactivate``."""
from __future__ import annotations

_current = None


class Session:
    def __init__(self, config: dict, sizes: dict, seed: int, traffic: dict):
        self.config = config      # the configuration file, whole
        self.sizes = sizes        # the widths as run (rehearsal: tiny)
        self.seed = seed
        self.traffic = traffic
        self.weights = None       # set by the config's builder: the tree
        self.fault = None         # tests plant a fault on the timed path


def activate(session: Session) -> Session:
    global _current
    _current = session
    return session


def deactivate() -> None:
    global _current
    _current = None


def current() -> Session:
    if _current is None:
        raise RuntimeError("no benchmark session is active: this file is "
                           "opened by benchmark/run.py, not on its own")
    return _current
