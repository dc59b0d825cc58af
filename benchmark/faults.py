"""The control and the faults: the timed path, broken underneath.

``plant`` puts a client in the harness's hands that breaks one thing the
configuration guarantees.  Nothing of a benchmark run uses it: ``control.py``
runs the control on the chip, and the tests drive each fault through
``driver.run_cell`` on the CPU and see ``correct`` come out false.

- ``stale_reads`` is the control: it breaks "reads are linearizable" the way
  a later PR would be tempted to — a read answered from the state machine of
  whichever store is nearest, with no ReadIndex round.
- ``drop_updates``: a step that returns its state unchanged — every other
  update acknowledged and never sent.
- ``alter_answer``: an answer altered where it is produced — one byte of
  every 64th value read.
- ``skip_replica``: the exchange between replicas left out — one store's
  state machine loses what it applied for a slice of the records.
"""

from __future__ import annotations

import random

FAULTS = ("stale_reads", "drop_updates", "alter_answer", "skip_replica")


class _Faulty:
    def __init__(self, cluster, kind: str, seed: int):
        self.cluster, self.client, self.kind = cluster, cluster.client, kind
        self.rng = random.Random(seed)
        self.n = 0
        self.skipped: list = []

    async def get(self, key: bytes):
        self.n += 1
        n = self.n      # this call's number: others are made while it waits
        if self.kind == "stale_reads":
            store = self.rng.randrange(len(self.cluster.stores))
            return self.cluster.stores[store].raw_store.get(key)
        value = await self.client.get(key)
        if self.kind == "alter_answer" and n % 64 == 0 and value:
            value = value[:-1] + bytes([value[-1] ^ 1])
        return value

    async def put(self, key: bytes, value: bytes):
        self.n += 1
        if self.kind == "drop_updates" and self.n % 2 == 0:
            return True
        if self.kind == "skip_replica" and self.n % 8 == 0:
            self.skipped.append(key)
        return await self.client.put(key, value)

    def after_window(self) -> None:
        """``skip_replica``: the last store's state machine, as if it had
        never been sent the updates of the skipped records."""
        raw = self.cluster.stores[-1].raw_store
        for key in self.skipped:
            raw.delete(key)


def plant(kind: str, cluster, seed: int):
    if kind not in FAULTS:
        raise ValueError(f"unknown fault {kind!r}; there are {FAULTS}")
    return _Faulty(cluster, kind, seed)
