"""Persistent compile-cache hits and misses, read from JAX's compiler log.

``CacheLog().install()`` attaches a filter to ``jax._src.compiler``; its
debug records are counted here and go no further. ``take()`` returns and
clears what was seen since the last call.
"""
from __future__ import annotations

import logging


class CacheLog(logging.Filter):
    _EVENTS = (("hit", "Persistent compilation cache hit for '"),
               ("miss", "PERSISTENT COMPILATION CACHE MISS for '"),
               ("unwritten", "Not writing persistent cache entry for '"))

    def __init__(self):
        super().__init__()
        self._seen = []

    def install(self) -> "CacheLog":
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.addFilter(self)
        return self

    def uninstall(self) -> None:
        logging.getLogger("jax._src.compiler").removeFilter(self)

    def filter(self, record: logging.LogRecord) -> bool:
        msg = record.getMessage()
        for kind, prefix in self._EVENTS:
            if msg.startswith(prefix):
                name, _, rest = msg[len(prefix):].partition("'")
                self._seen.append((kind, name, rest.partition("because ")[2]))
        return record.levelno >= logging.WARNING

    def take(self) -> dict:
        """{"hits": [names], "misses": [names], "unwritten": {name: why}};
        compiles under JAX's minimum compile time are left out of
        ``unwritten``."""
        seen, self._seen = self._seen, []
        refused = {name: why for kind, name, why in seen if kind == "unwritten"}
        return {
            "hits": [name for kind, name, _ in seen if kind == "hit"],
            "misses": [name for kind, name, _ in seen if kind == "miss"],
            "unwritten": {name: why for name, why in refused.items()
                          if not why.startswith("it took <")},
        }
