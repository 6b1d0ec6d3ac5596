"""Byte-blob communicators for the host-driven parallel tree learners.

The wide-data learners (``parallel/hostlearner.py``) express every
exchange as an allgather of opaque byte blobs — best-split records,
partition bitmaps, vote ballots, elected-column histograms.  Two
communicators implement that surface:

- :class:`NetComm` rides the hardened multi-process transports in
  ``collect.py`` / ``net.py`` (deadline-bounded, heartbeat liveness,
  chunked KV payloads), so peer-death and timeout semantics are
  identical to every other collective in the repo;
- :class:`LocalComm` simulates R ranks inside one process with a
  barrier-synchronized slot exchange.  It exists for fast determinism
  tests and device-independent comms-volume counts: byte counts are
  exact and identical to what NetComm would send, without subprocesses.

Both keep an always-on ``ledger`` mapping purpose -> bytes sent by this
rank (``hist`` / ``best_split`` / ``vote`` / ``elect``, plus ``hist_q``
for the quantized-training int16 histogram wire and its scale/root-sum
side channels), independent of whether tracing is enabled — the
per-iter ``net_bytes`` report field reads it directly.  Under ``quantized_training`` the per-node histogram payload
moves from f32x3 (``hist``, F*B*12 bytes) to int16x2 (``hist_q``,
F*B*4 bytes — the count plane is derived at the receiver), a fixed 3x
wire reduction; the report CLI surfaces the measured ratio per
iteration.
"""

from __future__ import annotations

import threading
from typing import Dict, List

from ..obs import tracer


class Comm:
    """Allgather-of-bytes surface with a purpose-tagged byte ledger."""

    #: membership epoch this communicator's collectives are scoped to.
    #: Static worlds never bump it; the elastic MembershipComm
    #: (parallel/membership.py) overrides it with the live runtime
    #: epoch, so learners can stamp epoch-sensitive state without
    #: knowing which transport they ride.
    epoch = 0

    def __init__(self, rank: int, nproc: int):
        self.rank = int(rank)
        self.nproc = int(nproc)
        self.ledger: Dict[str, int] = {}

    def _account(self, blob: bytes, purpose: str) -> None:
        self.ledger[purpose] = self.ledger.get(purpose, 0) + len(blob)

    def ledger_total(self) -> int:
        return sum(self.ledger.values())

    def allgather(self, blob: bytes, purpose: str = "misc") -> List[bytes]:
        raise NotImplementedError


class NetComm(Comm):
    """Multi-process communicator over the hardened collect/net stack."""

    def __init__(self):
        import jax

        super().__init__(jax.process_index(), jax.process_count())

    def allgather(self, blob: bytes, purpose: str = "misc") -> List[bytes]:
        from . import collect

        self._account(blob, purpose)
        # collect.allgather_bytes emits the net.bytes tracer counter
        return collect.allgather_bytes(blob, purpose=purpose)


class LocalGroup:
    """Shared state for an in-process group of :class:`LocalComm` ranks.

    Exchange protocol: write own slot -> barrier -> snapshot all slots
    -> barrier.  The trailing barrier keeps a fast rank from starting
    the next round (overwriting its slot) before a slow rank snapshots.
    """

    def __init__(self, nproc: int):
        self.nproc = int(nproc)
        self.slots: List[bytes] = [b""] * self.nproc
        self.barrier = threading.Barrier(self.nproc)

    def comms(self) -> List["LocalComm"]:
        return [LocalComm(r, self) for r in range(self.nproc)]


class LocalComm(Comm):
    """Single-process rank simulation; exact byte accounting, no net."""

    def __init__(self, rank: int, group: LocalGroup):
        super().__init__(rank, group.nproc)
        self.group = group

    def allgather(self, blob: bytes, purpose: str = "misc") -> List[bytes]:
        self._account(blob, purpose)
        tracer.counter("net.bytes", float(len(blob)), purpose=purpose,
                       transport="local")
        if self.nproc == 1:
            return [blob]
        self.group.slots[self.rank] = blob
        self.group.barrier.wait()
        out = list(self.group.slots)
        self.group.barrier.wait()
        return out
