"""HBM arena/offset allocator: deterministic packing under a budget.

The autotuner (``client_tpu.engine.autotune``) must answer "does this
ladder promotion fit in device memory?" *before* compiling the candidate
bucket — XLA will happily OOM the chip at dispatch time otherwise. This
module provides the planning layer: a per-device byte budget carved from
the same source as the ``tpu_hbm_limit_bytes`` gauge
(``device.memory_stats()["bytes_limit"]``), with named offset-based
reservations in the style of the offset-calculation arenas from
"Efficient Memory Management for Deep Neural Net Inference"
(PAPERS.md, arXiv 2001.03288):

- every reservation is a ``[offset, offset + nbytes)`` interval inside a
  single linear arena — co-resident models *pack* instead of fragmenting,
  and non-overlap is guaranteed by construction;
- placement is first-fit at the lowest free offset (gaps left by released
  reservations are reused before the tail grows), so the same reserve
  sequence always produces the same layout — layouts are reproducible
  across restarts and debuggable from the ``/v2/profile`` snapshot;
- a reservation that fits in no gap raises :class:`ArenaExhausted`; the
  tuner turns that into an ``autotune.rejected_budget`` journal event
  instead of a device OOM.

This is a *planner*, not an allocator of real device pointers: JAX owns
the physical HBM. The arena keeps the engine's view of "committed" bytes
(per-bucket executables/activations, generative KV arenas) honest so the
tuner never promotes past the budget.
"""

from __future__ import annotations

from client_tpu.utils import lockdep
from dataclasses import dataclass

from client_tpu.engine.types import EngineError

# Reservations are rounded up to this grain: XLA allocates HBM in large
# pages and sub-KiB precision would be false accuracy in a planner.
ALIGN = 1024


class ArenaExhausted(EngineError):
    """A reservation does not fit in any free gap of the arena."""

    def __init__(self, message: str):
        # 507 Insufficient Storage: the honest HTTP translation should a
        # frontend ever surface this (the tuner normally absorbs it).
        super().__init__(message, 507)


@dataclass(frozen=True)
class Reservation:
    """One named ``[offset, offset + nbytes)`` interval in the arena."""

    name: str
    offset: int
    nbytes: int

    @property
    def end(self) -> int:
        return self.offset + self.nbytes


class ArenaAllocator:
    """First-fit offset allocator over a single linear byte budget."""

    def __init__(self, budget_bytes: int, label: str = "hbm"):
        if budget_bytes <= 0:
            raise EngineError(
                f"arena '{label}': budget must be positive, "
                f"got {budget_bytes}", 500)
        self.budget = int(budget_bytes)
        self.label = label
        self._lock = lockdep.Lock("engine.arena")
        self._res: dict[str, Reservation] = {}

    # -- core ops -------------------------------------------------------------

    @staticmethod
    def _align(nbytes: int) -> int:
        return max(ALIGN, (int(nbytes) + ALIGN - 1) // ALIGN * ALIGN)

    def reserve(self, name: str, nbytes: int) -> Reservation:
        """Place ``name`` at the lowest free offset that fits (first-fit;
        released gaps are reused before the tail grows). Raises
        :class:`ArenaExhausted` when no gap fits, ``EngineError`` when the
        name is already reserved (release first — reservations are not
        resizable in place)."""
        need = self._align(nbytes)
        with self._lock:
            if name in self._res:
                raise EngineError(
                    f"arena '{self.label}': '{name}' already reserved "
                    f"({self._res[name].nbytes} bytes)", 500)
            offset = self._first_fit_locked(need)
            if offset is None:
                raise ArenaExhausted(
                    f"arena '{self.label}': cannot reserve {need} bytes for "
                    f"'{name}' — {self.free_bytes_locked()} of {self.budget} "
                    f"bytes free, largest gap "
                    f"{self.largest_gap_locked()} bytes")
            r = Reservation(name, offset, need)
            self._res[name] = r
            return r

    def _first_fit_locked(self, need: int) -> int | None:
        cursor = 0
        for r in sorted(self._res.values(), key=lambda r: r.offset):
            if r.offset - cursor >= need:
                return cursor
            cursor = max(cursor, r.end)
        if self.budget - cursor >= need:
            return cursor
        return None

    def reserve_sharded(self, name: str, nbytes: int,
                        shards: int = 1) -> Reservation:
        """Reserve the PER-DEVICE share of a globally sharded buffer.

        The planning arena models one device's HBM (its budget comes from
        device 0's ``bytes_limit``), while a ``NamedSharding``-sharded
        buffer — e.g. the cross-chip KV arena (parallel/kv_shard.py) —
        reports its *global* pytree bytes.  Charging the global size
        against one device's budget would spuriously exhaust the planner;
        an N-way shard commits ``ceil(nbytes / N)`` per device."""
        shards = max(1, int(shards))
        return self.reserve(name, (int(nbytes) + shards - 1) // shards)

    def release(self, name: str) -> bool:
        """Free one reservation; returns False when the name is unknown
        (idempotent — unload paths call this unconditionally)."""
        with self._lock:
            return self._res.pop(name, None) is not None

    def release_prefix(self, prefix: str) -> int:
        """Free every reservation whose name starts with ``prefix``
        (e.g. ``bucket:simple:1:``); returns the count released."""
        with self._lock:
            doomed = [n for n in self._res if n.startswith(prefix)]
            for n in doomed:
                del self._res[n]
            return len(doomed)

    # -- introspection --------------------------------------------------------

    def get(self, name: str) -> Reservation | None:
        with self._lock:
            return self._res.get(name)

    def reserved_bytes(self) -> int:
        with self._lock:
            return sum(r.nbytes for r in self._res.values())

    def free_bytes(self) -> int:
        with self._lock:
            return self.free_bytes_locked()

    def free_bytes_locked(self) -> int:
        return self.budget - sum(r.nbytes for r in self._res.values())

    def largest_gap_locked(self) -> int:
        cursor, largest = 0, 0
        for r in sorted(self._res.values(), key=lambda r: r.offset):
            largest = max(largest, r.offset - cursor)
            cursor = max(cursor, r.end)
        return max(largest, self.budget - cursor)

    def snapshot(self) -> dict:
        """JSON view for ``/v2/profile``: budget, usage, and the packed
        layout sorted by offset (offsets make overlap auditable)."""
        with self._lock:
            layout = sorted(self._res.values(), key=lambda r: r.offset)
            reserved = sum(r.nbytes for r in layout)
            return {
                "label": self.label,
                "budget_bytes": self.budget,
                "reserved_bytes": reserved,
                "free_bytes": self.budget - reserved,
                "reservations": [
                    {"name": r.name, "offset": r.offset, "nbytes": r.nbytes}
                    for r in layout
                ],
            }


def device_hbm_budget(fraction: float, fallback_bytes: int = 0) -> int:
    """The arena budget for device 0: ``bytes_limit`` (the
    ``tpu_hbm_limit_bytes`` gauge source) scaled by ``fraction``. CPU
    backends report no limit (``memory_stats`` is None) — fall back to
    ``fallback_bytes`` so the planner still works in tests/CI. A TPU that
    reports none is an error: planning a 16 GB chip against the CPU
    fallback would be wrong by an order of magnitude, silently."""
    from client_tpu.engine.backend_init import ensure_backend

    dev = ensure_backend()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0) or 0)
    if limit > 0:
        return int(limit * fraction)
    if dev.platform == "tpu":
        raise RuntimeError(
            f"TPU device {dev} reports no memory_stats()['bytes_limit']; "
            "refusing to plan HBM against the CPU fallback budget")
    return int(fallback_bytes)
