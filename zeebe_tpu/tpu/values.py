"""Host-held payload values: the exact form of what a device column cannot
hold.

A top-level payload variable whose value is a map, a list, or a number
that f32 does not hold exactly (a price of 19.99, an id above 2^24, an
epoch-millisecond timestamp) is staged under the type tag ``VT_HELD``: its
``v_str`` cell holds a handle into this store, as a string's holds an
intern id, and ``v_num`` is 0. The kernel moves the handle with its column
in every copy and merge it does and never looks inside it; the engine keeps
such a value out of every place the kernel compares (an instance whose
workflow reads it runs on the host engine, ``TpuPartitionEngine``).

Ownership keeps the store bounded by what is live. A handle staged by a
command has no owner until the wave that stepped the command is decoded:
every emitted row that carries it adds it to its owner's set (the row's
instance; a message's own rows, the message). A handle that no row took
(the command was rejected) is freed right there; every other one is freed
when its last owner ends. Values are not deduplicated by content: a value
is held again only where the very object the store holds comes back (a
record the engine built from its handles), which reuses the handle.

Standard library only, like ``intern``.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict, Iterable, List, Optional, Set

from zeebe_tpu.protocol import msgpack


class ValueStore:
    """Handle -> value, with the owner sets that free a handle. Handle 0 is
    never given out (a zero cell is "no value")."""

    def __init__(self):
        self._values: Dict[int, Any] = {}
        self._by_obj: Dict[int, int] = {}       # id(value) -> its handle
        self._owners: Dict[int, Set] = {}       # handle -> owner keys
        self._held_by: Dict[Any, Set[int]] = {}  # owner key -> handles
        self._free: List[int] = []
        self._next = 1
        # totals not yet handed to a wave's clock (``take_counts``)
        self.counts: Dict[str, float] = {}
        # profiler annotation around the store's work, where a tracer is
        # installed (set by the engine per cycle)
        self.annotate = False

    # -- the store's own clock -------------------------------------------
    def timed(self) -> "_Stopwatch":
        """Seconds of holding and resolving values, on a clock of its own
        (``with store.timed():``): no phase is cut, so the phase the work
        runs in keeps the time."""
        return _Stopwatch(self)

    def _count(self, name: str, n: float) -> None:
        self.counts[name] = self.counts.get(name, 0) + n

    def take_counts(self) -> Dict[str, float]:
        counts, self.counts = self.counts, {}
        return counts

    # -- values -------------------------------------------------------------
    def hold(self, value: Any) -> int:
        """The handle of ``value``: the one it has where the store holds
        this very object, else a new one."""
        handle = self._by_obj.get(id(value))
        if handle is not None:
            return handle
        if self._free:
            handle = self._free.pop()
        else:
            handle = self._next
            self._next += 1
            if handle >= 1 << 31:
                raise OverflowError("payload value handles exhausted")
        self._values[handle] = value
        self._by_obj[id(value)] = handle
        self._owners[handle] = set()
        self._count("payload_values_held", 1)
        self._count("payload_value_bytes", len(msgpack.pack(value)))
        return handle

    def handle_of(self, value: Any) -> Optional[int]:
        """The handle the store holds this very object under, or None."""
        return self._by_obj.get(id(value))

    def __getitem__(self, handle: int) -> Any:
        return self._values[handle]

    def resolve(self, handles: Iterable[int]) -> Dict[int, Any]:
        """handle -> value for each of ``handles``: what a decoded batch
        keeps, so that its rows read the same values after a handle was
        freed and given out again."""
        values = self._values
        return {int(h): values[int(h)] for h in handles}

    def __len__(self) -> int:
        return len(self._values)

    # -- owners -------------------------------------------------------------
    def own(self, owner, handle: int) -> None:
        owners = self._owners.get(handle)
        if owners is None or owner in owners:
            return
        owners.add(owner)
        held = self._held_by.get(owner)
        if held is None:
            held = self._held_by[owner] = set()
        held.add(handle)

    def release_unowned(self, handles: Iterable[int]) -> None:
        """Free each of ``handles`` that no owner took."""
        for handle in handles:
            owners = self._owners.get(handle)
            if owners is not None and not owners:
                self._drop(handle)

    def end(self, owner) -> None:
        """``owner`` ended: free every handle it was the last owner of."""
        held = self._held_by.pop(owner, None)
        if not held:
            return
        for handle in held:
            owners = self._owners.get(handle)
            if owners is None:
                continue
            owners.discard(owner)
            if not owners:
                self._drop(handle)

    def _drop(self, handle: int) -> None:
        value = self._values.pop(handle)
        self._by_obj.pop(id(value), None)
        del self._owners[handle]
        self._free.append(handle)
        self._count("payload_values_freed", 1)

    def clear(self) -> None:
        """Free everything (a restore replaces the store's contents)."""
        for handle in list(self._values):
            self._drop(handle)
        self._held_by.clear()

    # -- snapshot form ------------------------------------------------------
    def snapshot(self) -> dict:
        """Plain data for the snapshot's ``meta``: the values by handle and
        the owner sets. An owner key is an instance key (an int) or
        ``("m", message key)``."""
        return {
            "values": [[h, v] for h, v in sorted(self._values.items())],
            "owners": [
                [list(owner) if isinstance(owner, tuple) else owner, sorted(held)]
                for owner, held in self._held_by.items()
            ],
        }

    def restore(self, snap: Optional[dict]) -> None:
        """The store as ``snapshot`` gave it, under the same handles (the
        restored tables hold them). A value no owner holds is dropped."""
        self.clear()
        self._by_obj.clear()
        self._free = []
        self._next = 1
        if not snap:
            return
        for handle, value in snap.get("values", []):
            handle = int(handle)
            self._values[handle] = value
            self._by_obj[id(value)] = handle
            self._owners[handle] = set()
            self._next = max(self._next, handle + 1)
            self._count("payload_values_held", 1)
        for owner, held in snap.get("owners", []):
            if isinstance(owner, list):
                owner = tuple(owner)
            for handle in held:
                self.own(owner, int(handle))
        self.release_unowned(list(self._values))
        taken = self._values.keys()
        self._free = [h for h in range(self._next - 1, 0, -1) if h not in taken]


class _Stopwatch:
    """One ``ValueStore.timed`` interval, with its profiler annotation
    ``zb:payload_values`` where the store is told to annotate."""

    __slots__ = ("store", "t0", "annotation")

    def __init__(self, store: ValueStore):
        self.store = store
        self.annotation = None

    def __enter__(self) -> "_Stopwatch":
        if self.store.annotate:
            self.annotation = sys.modules["jax"].profiler.TraceAnnotation(
                "zb:payload_values"
            )
            self.annotation.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.store._count(
            "payload_value_seconds", (time.perf_counter_ns() - self.t0) / 1e9
        )
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False
