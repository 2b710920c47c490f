"""Request tracing: a contextvar trace id propagated over HTTP.

The reference correlates nothing across its client → per-model Flask pod
hop; debugging a slow prediction means grepping two pods' logs by
timestamp. Here one header — ``X-Gordo-Trace-Id`` — rides every client
request, the server adopts (or mints) it per request and echoes it in the
response, and a ``logging`` record factory stamps the current trace id
onto EVERY log record emitted on that request's thread: client retry
warnings, server access lines, and engine dispatch logs all carry the
same id without any call site threading it by hand.

``contextvars`` (not thread-locals) so the ids flow correctly through
both the threaded WSGI server and the client's asyncio task fan-out —
each in-flight chunk request holds its own trace id.
"""

from __future__ import annotations

import contextlib
import logging
import uuid
from contextvars import ContextVar
from typing import Iterator, Optional

TRACE_HEADER = "X-Gordo-Trace-Id"

_trace_id: ContextVar[str] = ContextVar("gordo_trace_id", default="")

logger = logging.getLogger(__name__)


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def get_trace_id() -> str:
    """The current context's trace id ('' when none is active)."""
    return _trace_id.get()


def set_trace_id(trace_id: str):
    """Bind ``trace_id`` to the current context; returns the reset token."""
    return _trace_id.set(trace_id)


def reset_trace_id(token) -> None:
    _trace_id.reset(token)


def current_or_new() -> str:
    """The active trace id, or a fresh one (NOT bound — callers starting a
    new trace should bind via :func:`trace` / :func:`set_trace_id`)."""
    return _trace_id.get() or new_trace_id()


@contextlib.contextmanager
def trace(trace_id: Optional[str] = None) -> Iterator[str]:
    """Bind a trace id (given or fresh) for the duration of the block."""
    tid = trace_id or new_trace_id()
    token = _trace_id.set(tid)
    try:
        yield tid
    finally:
        _trace_id.reset(token)


_factory_installed = False


def install_log_record_factory() -> None:
    """Stamp ``record.trace_id`` onto every log record from the active
    context. Idempotent; wraps (never replaces) whatever
    factory is already installed, so it composes with other libraries'
    factories and with repeated ``configure_logging`` calls."""
    global _factory_installed
    if _factory_installed:
        return
    _factory_installed = True
    previous = logging.getLogRecordFactory()

    def factory(*args, **kwargs):
        record = previous(*args, **kwargs)
        record.trace_id = _trace_id.get()
        return record

    logging.setLogRecordFactory(factory)
