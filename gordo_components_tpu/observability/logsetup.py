"""Process logging setup: text or JSON lines, trace-id-stamped.

The CLI's former ``logging.basicConfig`` call, grown into the one place
log shape is decided. ``--log-format json`` emits one JSON object per
record (machine-parseable by the log pipeline the reference delegated to
Kubernetes), with the active trace/span ids as first-class fields; the
text format keeps the exact pre-existing line shape so operator muscle
memory and log scrapers survive.
"""

from __future__ import annotations

import json
import logging

from .tracing import install_log_record_factory

TEXT_FORMAT = "%(asctime)s %(levelname)s %(name)s: %(message)s"


class JsonFormatter(logging.Formatter):
    """One JSON object per record; the trace id included when active."""

    def format(self, record: logging.LogRecord) -> str:
        payload = {
            "ts": self.formatTime(record),
            "level": record.levelname,
            "logger": record.name,
            "message": record.getMessage(),
        }
        trace_id = getattr(record, "trace_id", "")
        if trace_id:
            payload["trace_id"] = trace_id
        if record.exc_info:
            payload["exc_info"] = self.formatException(record.exc_info)
        return json.dumps(payload, default=str)


def configure_logging(level: str = "INFO", fmt: str = "text") -> None:
    """Install root logging at ``level`` in ``fmt`` ('text' | 'json') and
    the trace-id record factory (every record carries a ``trace_id``
    attribute from then on, whatever the handler).

    ``basicConfig`` WITHOUT ``force``, exactly like the CLI call this
    grew from: a no-op when the root logger already has handlers (a test
    runner's capture, an embedding app's own setup) — clobbering those
    would reroute their records into our stream."""
    if fmt not in ("text", "json"):
        raise ValueError(f"log format must be 'text' or 'json', got {fmt!r}")
    install_log_record_factory()
    handler = logging.StreamHandler()
    handler.setFormatter(
        JsonFormatter() if fmt == "json" else logging.Formatter(TEXT_FORMAT)
    )
    logging.basicConfig(level=level.upper(), handlers=[handler])
