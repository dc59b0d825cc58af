"""Registry keys for handles shared per directory."""

import os


class RealPathKeys:
    """``dir_path`` as given -> its real path, remembered while the
    handle shared under that key is live.  Every group of a store opens
    the same string, and a ``realpath`` is one ``lstat`` a path
    component: 4,096 groups were 20,000 lstats of a store's boot (86 us
    each on the chip host; PERF.md section 6, PR 29).  Callers hold the
    lock of the registry the keys are for."""

    def __init__(self) -> None:
        self._real: dict[str, str] = {}

    def key(self, dir_path: str) -> str:
        key = self._real.get(dir_path)
        if key is None:
            key = self._real[dir_path] = os.path.realpath(dir_path)
        return key

    def forget(self, key: str) -> None:
        """The handle under ``key`` is closed: resolve afresh next time."""
        for path in [p for p, k in self._real.items() if k == key]:
            del self._real[path]
