"""What the persistent compile cache answered for one compile, heard from
JAX's own monitoring events while the compile runs.

``jax.monitoring`` calls its listeners on the thread that records an event,
and a compile records its cache events on the thread that calls
``.compile()``. One listener is registered a process, on first use; it notes
an event into the block open on its own thread and ignores every other
thread's, so a compile on another thread (the prefetch worker's small
programs) is never counted here.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterator

# the cache was asked (a key computed: the cache is on), and it held the
# program; JAX records ``cache_misses`` only where it then writes the entry,
# which a compile under the cache's minimum time never does
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"

_open = threading.local()
_lock = threading.Lock()
_listening = False


def _heard(event: str, **_: Any) -> None:
    heard = getattr(_open, "heard", None)
    if heard is not None:
        heard.add(event)


def _listen() -> None:
    global _listening
    with _lock:
        if not _listening:
            import jax

            jax.monitoring.register_event_listener(_heard)
            _listening = True


@contextlib.contextmanager
def cache_outcome(attrs: Dict[str, Any]) -> Iterator[None]:
    """Set ``attrs["cache"]`` as the block ends: ``"hit"`` where the
    persistent cache held the program and it was loaded, ``"miss"`` where
    the cache was asked and the program compiled, ``"off"`` where no cache
    was asked (none is configured)."""
    _listen()
    outer = getattr(_open, "heard", None)
    heard = _open.heard = set()
    try:
        yield
    finally:
        _open.heard = outer
        attrs["cache"] = (
            "hit" if _HIT in heard else "miss" if _ASKED in heard else "off"
        )
