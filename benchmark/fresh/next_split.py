"""Freshener ``next_split``: the next request's table is the ring's next
file under a path no request has had.

``tables/lineitem_parquet.py`` wrote the ring in set-up; ``next()`` hard
links its next file under a new name beside it. The server keys a split by
its source (path, size, mtime), so every request is new to the result
cache, decodes and stages in full, and neighbouring requests read different
bytes; nothing is written inside the window. The link before the last is
removed: at most two requests' paths exist at a time.
"""

from __future__ import annotations

import os


class Freshener:
    def __init__(self, made: dict, seed: int):
        self._made = made            # keeps the directory's keeper alive
        self._paths = list(made["paths"])
        self._count = 0
        self._handed: list = []

    def next(self) -> str:
        """The path the next request binds."""
        source = self._paths[self._count % len(self._paths)]
        path = os.path.join(os.path.dirname(source),
                            f"split-{self._count:06d}.snappy.parquet")
        os.link(source, path)
        self._count += 1
        self._handed.append(path)
        if len(self._handed) > 2:
            os.unlink(self._handed.pop(0))
        return path
