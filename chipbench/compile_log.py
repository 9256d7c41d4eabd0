"""Backend compiles of this process: seconds, persistent-cache hit or miss.

A ``jax.monitoring`` listener records every backend compile with the
jitted function's name; JAX's own DEBUG log lines say whether the
persistent compilation cache supplied the executable. The harness reads
:attr:`CompileLog.count` before and after the timed window: a compile in
between fails the run.
"""

from __future__ import annotations

import logging
import sys


class CompileLog:
    def __init__(self):
        import jax
        self.events: list[dict] = []
        self._lookup: dict = {}
        self._parts: dict[str, str] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        handler = logging.Handler(logging.DEBUG)
        handler.emit = self._on_log
        for name in ("jax._src.compiler", "jax._src.compilation_cache",
                     "jax._src.cache_key"):
            log = logging.getLogger(name)
            log.setLevel(logging.DEBUG)
            log.addHandler(handler)
            log.propagate = False

    @property
    def count(self) -> int:
        return len(self.events)

    def _on_log(self, rec) -> None:
        msg = str(rec.msg)
        if msg.startswith("get_cache_key hash of serialized"):
            self._parts[str(rec.args[0])] = str(rec.args[1])[:8]
        elif msg.startswith("Persistent compilation cache hit"):
            self._lookup = {"cache": "hit", "parts": dict(self._parts)}
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self._lookup = {"cache": "miss", "parts": dict(self._parts)}
        elif rec.levelno >= logging.WARNING:
            print(rec.getMessage(), file=sys.stderr, flush=True)

    def _on_time(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append({"fun": kw.get("fun_name", "?"),
                                "seconds": seconds,
                                "cache": self._lookup.get("cache", "off"),
                                "parts": self._lookup.get("parts", {})})
            self._lookup = {}

    def lines(self, since: int = 0) -> list[str]:
        """One line per compile; a miss names the hash of each part of its
        cache key, so two runs that miss on one program show which part
        differs."""
        return [f"compile {e['fun']}: {e['seconds']:.2f} s, persistent "
                f"cache {e['cache']}"
                + ("" if e["cache"] != "miss" else " [" + " ".join(
                    f"{k.replace(' ', '_')}={v}"
                    for k, v in e["parts"].items()) + "]")
                for e in self.events[since:]]
