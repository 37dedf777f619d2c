"""Count XLA compilations over a region of code.

A copy of the mechanism of the program's ``CompileGuard``: with
``jax_log_compiles`` on, JAX logs one "Compiling <name>" record per
compilation (in-memory cache hits are silent); a handler on JAX's
loggers counts them.
"""
from __future__ import annotations

import logging
import re

_COMPILE_RE = re.compile(r"^Compiling ([^\s]+)")
_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")


class _Recorder(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if m:
            self.names.append(m.group(1))


class CompileCounter:
    """``with CompileCounter() as c: ...`` then ``c.count``."""

    def __init__(self):
        self._rec = _Recorder()
        self._prev = None

    @property
    def count(self) -> int:
        return len(self._rec.names)

    @property
    def names(self) -> list[str]:
        return list(self._rec.names)

    def __enter__(self) -> "CompileCounter":
        import jax
        self._prev = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        for name in _LOGGERS:
            logging.getLogger(name).addHandler(self._rec)
        return self

    def __exit__(self, *exc) -> bool:
        import jax
        for name in _LOGGERS:
            logging.getLogger(name).removeHandler(self._rec)
        jax.config.update("jax_log_compiles", self._prev)
        return False
