"""The one traffic generator: a mix's data file and a seed in, operations out.

The program sees only the operations.  A mix is a file of parameters
(``benchmark/traffic/<name>.json``).  The kinds of operation no cell uses yet
are validated by ``check_manifest`` and refused here by name: the reference
has to grow with them.  How the operations arrive is the mix's loop, a module
of its own (``benchmark/loops/``), which also says which faults it can run.
"""

from __future__ import annotations

import struct

import numpy as np

READ, UPDATE = 0, 1
HEADER = struct.Struct(">IQI")      # writer, sequence number, record
LOADER = 0xFFFFFFFF                  # the writer id of the load phase
_BLOB_BYTES = 1 << 20


class NotImplementedTraffic(NotImplementedError):
    pass


def refuse_unimplemented(spec: dict) -> None:
    for key in ("insert_share", "scan_share", "rmw_share"):
        if spec.get(key, 0.0):
            raise NotImplementedTraffic(
                f"traffic {spec['name']}: {key} is not implemented "
                f"(benchmark/traffic.py: OpStream)")
    if spec["request_distribution"] == "latest":
        raise NotImplementedTraffic(
            f"traffic {spec['name']}: request_distribution 'latest' is not "
            f"implemented (benchmark/traffic.py: OpStream)")


class OpStream:
    """``n`` operations drawn from ``seed``: ``kinds[i]`` (READ or UPDATE) on
    record ``records[i]``.  Clients take them in order from one cursor, so
    the same seed gives the same operations however the clients interleave.
    """

    def __init__(self, spec: dict, record_count: int, seed: int,
                 n: int = 1 << 20):
        refuse_unimplemented(spec)
        self.seed = seed
        rng = np.random.default_rng([seed, 0x7AF1C])
        self.kinds = (rng.random(n) >= spec["read_share"]).astype(np.uint8)
        if spec["request_distribution"] == "zipfian":
            # YCSB's ZipfianGenerator: P(rank r) ~ r^-constant over all
            # records, by inverse CDF; ScrambledZipfian spreads the ranks
            # over the keyspace so the hot records are not one region's
            ranks = np.arange(1, record_count + 1, dtype=np.float64)
            cdf = np.cumsum(ranks ** -float(spec["zipfian_constant"]))
            cdf /= cdf[-1]
            picks = np.searchsorted(cdf, rng.random(n), side="right")
            picks = np.minimum(picks, record_count - 1)
            if spec.get("scrambled", True):
                picks = rng.permutation(record_count)[picks]
        else:
            picks = rng.integers(0, record_count, n)
        self.records = picks.astype(np.int32)
        self.n = n


class Values:
    """Record values that name their writer: ``field_count x field_bytes``
    bytes, a 16-byte header (writer, sequence number, record) and a body cut
    from a seeded blob at a place the header fixes, so every byte read back
    can be checked and a value says which update wrote it."""

    def __init__(self, seed: int, value_bytes: int):
        if value_bytes <= HEADER.size:
            raise ValueError("a value must be longer than its header")
        self.body = value_bytes - HEADER.size
        self.blob = np.random.default_rng([seed, 0xB10B]).bytes(_BLOB_BYTES)

    def _offset(self, writer: int, seq: int, record: int) -> int:
        return ((writer * 2654435761 + seq * 40503 + record * 97)
                % (_BLOB_BYTES - self.body))

    def make(self, writer: int, seq: int, record: int) -> bytes:
        off = self._offset(writer, seq, record)
        return HEADER.pack(writer, seq, record) + self.blob[off:off + self.body]

    def parse(self, value) -> tuple | None:
        """(writer, seq, record) when every byte of ``value`` is what that
        write wrote, else None."""
        if not isinstance(value, (bytes, bytearray, memoryview)) \
                or len(value) != HEADER.size + self.body:
            return None
        writer, seq, record = HEADER.unpack_from(value)
        off = self._offset(writer, seq, record)
        if bytes(value[HEADER.size:]) != self.blob[off:off + self.body]:
            return None
        return writer, seq, record
