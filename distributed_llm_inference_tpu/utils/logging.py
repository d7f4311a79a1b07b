"""Structured JSON-lines logging.

The reference logs with emoji print() banners throughout
(/root/reference/orchestration.py:74-76, Worker1.py:84-87 — SURVEY.md §5
metrics/logging). Here every log record is one JSON object on stderr
(machine-parseable, greppable), with arbitrary structured fields:

    log = get_logger("engine")
    log.info("request", model="tinyllama-1.1b", tokens=20, ttft_s=0.01)

Stdout stays clean for tool output (the client CLI, chip_smoke.py's
result line).
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import sys
import time
from typing import Any, Optional

_CONFIGURED = False

# Current request id (utils/tracing.Trace): set around a request's
# processing so every record logged inside — engine internals included,
# with no plumbing — carries the id for cross-service correlation.
_REQUEST_ID: contextvars.ContextVar = contextvars.ContextVar(
    "request_id", default=None
)
# Current W3C trace id (utils/tracing.SpanContext): same contract as the
# request id, set by the serving edges (router POST handling, replica
# request handling, fabric code paths) so router- and fabric-side log
# records carry the fleet-wide trace id too — not just the engine side.
_TRACE_ID: contextvars.ContextVar = contextvars.ContextVar(
    "trace_id", default=None
)


def set_request_id(rid: Optional[str]):
    """Set (rid) or clear (None) the context's request id; returns the
    token for contextvars reset."""
    return _REQUEST_ID.set(rid)


def get_request_id() -> Optional[str]:
    return _REQUEST_ID.get()


def get_trace_id() -> Optional[str]:
    return _TRACE_ID.get()


@contextlib.contextmanager
def request_id_context(rid: Optional[str], trace_id: Optional[str] = None):
    token = _REQUEST_ID.set(rid)
    t_token = _TRACE_ID.set(trace_id) if trace_id is not None else None
    try:
        yield
    finally:
        if t_token is not None:
            _TRACE_ID.reset(t_token)
        _REQUEST_ID.reset(token)


class _JsonFormatter(logging.Formatter):
    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": round(record.created, 3),
            "level": record.levelname.lower(),
            "logger": record.name,
            "event": record.getMessage(),
        }
        rid = _REQUEST_ID.get()
        if rid is not None:
            out["request_id"] = rid
        tid = _TRACE_ID.get()
        if tid is not None:
            out["trace_id"] = tid
        fields = getattr(record, "fields", None)
        if fields:
            out.update(fields)  # an explicit request_id field wins
        if record.exc_info and record.exc_info[0] is not None:
            out["exc"] = self.formatException(record.exc_info)
        return json.dumps(out, default=str)


class StructuredLogger:
    """Thin wrapper adding **fields kwargs to the stdlib logger."""

    def __init__(self, logger: logging.Logger):
        self._logger = logger

    def _log(self, level: int, event: str, exc_info=None, **fields: Any):
        if self._logger.isEnabledFor(level):
            self._logger.log(level, event, extra={"fields": fields}, exc_info=exc_info)

    def debug(self, event: str, **fields):
        self._log(logging.DEBUG, event, **fields)

    def info(self, event: str, **fields):
        self._log(logging.INFO, event, **fields)

    def warning(self, event: str, **fields):
        self._log(logging.WARNING, event, **fields)

    def error(self, event: str, exc_info=None, **fields):
        self._log(logging.ERROR, event, exc_info=exc_info, **fields)


def configure(level: int = logging.INFO, stream=None) -> None:
    """Install the JSON handler on the package root logger.

    The handler is installed exactly once, but the LEVEL applies on every
    call: a repeat `configure(logging.DEBUG)` (an operator turning on
    verbosity at runtime) updates the root level instead of being
    silently ignored.
    """
    global _CONFIGURED
    root = logging.getLogger("distributed_llm_inference_tpu")
    root.setLevel(level)
    if _CONFIGURED:
        return
    handler = logging.StreamHandler(stream or sys.stderr)
    handler.setFormatter(_JsonFormatter())
    root.addHandler(handler)
    root.propagate = False
    _CONFIGURED = True


def get_logger(name: str) -> StructuredLogger:
    """Library-safe: does NOT install handlers — records propagate to the
    host application's logging config by default. Entry points (the server
    CLI) call configure() to get the JSON-lines handler."""
    return StructuredLogger(
        logging.getLogger(f"distributed_llm_inference_tpu.{name}")
    )
