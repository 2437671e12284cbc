"""Server-side shared-memory region managers.

Two data planes, mirroring the reference's register-by-key /
register-by-handle split (SURVEY.md §5.8):

**SystemShmManager** — POSIX system shm, registered by key: the server opens
``/dev/shm/<key>`` and mmaps it (the server side of the reference's
``RegisterSystemSharedMemory``; client-side creation in
``client_tpu.utils.shared_memory``). Tensor reads are zero-copy views into
the mapping (``np.frombuffer``); the single host→HBM DMA happens inside the
engine's ``device_put``.

**TpuShmManager** — the TPU-native replacement for CUDA-IPC regions
(reference ``cudaIpcGetMemHandle``→``raw_handle`` transport,
grpc_client.cc:796-826). CUDA IPC has no public 1:1 TPU analog (libtpu does
not export cross-process HBM handles), so a TPU region is:

- *in-process* (the perf-harness / C-API path): the registry maps the region
  name directly to a device-resident ``jax.Array`` — true zero-copy: the
  engine executes straight from HBM and leaves outputs there;
- *cross-process*: the opaque ``raw_handle`` describes a host-shm staging
  buffer (key + byte_size); the server mmaps it and serves tensor reads as
  zero-copy host views, so the dynamic batcher assembles whole batches on
  host and pays ONE host→HBM DMA per batch (inside the engine's
  device_put) with zero network bytes — the best available contract
  without PjRt cross-process buffer export, and the analog of the
  reference's cudaMemcpy-based ``set``/``get``
  (cuda_shared_memory.cc:63-123).

Handles serialize as JSON (transported as raw bytes over gRPC, base64 over
HTTP, exactly like the reference's cudaIpcMemHandle_t).
"""

from __future__ import annotations

import json
import mmap
import os
from client_tpu.utils import lockdep

import numpy as np

from client_tpu.engine.types import EngineError
from client_tpu.protocol.codec import deserialize_tensor, serialize_tensor
from client_tpu.protocol.dtypes import DataType


class _SysRegion:
    __slots__ = ("name", "key", "offset", "byte_size", "fd", "map")

    def __init__(self, name, key, offset, byte_size):
        self.name = name
        self.key = key
        self.offset = int(offset)
        self.byte_size = int(byte_size)
        path = shm_path(key)
        if not os.path.exists(path):
            raise EngineError(
                f"shared memory key '{key}' does not exist", 400)
        if self.offset < 0 or self.byte_size < 0:
            raise EngineError(
                f"region '{name}': offset/byte_size must be non-negative "
                f"(got {self.offset}/{self.byte_size})", 400)
        self.fd = os.open(path, os.O_RDWR)
        try:
            self.map = mmap.mmap(self.fd, 0)
        except Exception:
            os.close(self.fd)
            raise
        if self.offset + self.byte_size > len(self.map):
            self.close()
            raise EngineError(
                f"region '{name}': offset+byte_size "
                f"({self.offset}+{self.byte_size}) exceeds shm segment size "
                f"({len(self.map)})", 400)

    def close(self):
        # Idempotent: a second close() (e.g. unregister-all racing a single
        # unregister, or re-close after the BufferError path below already
        # dropped the mapping) must be a no-op, not an EBADF/AttributeError.
        if self.map is not None:
            try:
                self.map.close()
                self.map = None
            except BufferError:
                # zero-copy tensor views still reference the mapping; drop
                # our reference and let GC unmap once the last view dies
                self.map = None
        if self.fd >= 0:
            fd, self.fd = self.fd, -1
            os.close(fd)

    def read_view(self, offset: int, byte_size: int) -> memoryview:
        offset = int(offset)
        if offset < 0 or offset > self.byte_size:
            raise EngineError(
                f"offset {offset} outside region '{self.name}' "
                f"({self.byte_size}B)", 400)
        start = self.offset + offset
        if byte_size <= 0:
            byte_size = self.byte_size - offset
        if byte_size == 0:
            # Explicit zero-length read (offset == byte_size with default
            # size): a valid empty window, not an error.
            return memoryview(b"")
        if byte_size < 0 or start + byte_size > self.offset + self.byte_size:
            raise EngineError(
                f"read of {byte_size}B at {offset} exceeds region "
                f"'{self.name}' ({self.byte_size}B)", 400)
        return memoryview(self.map)[start:start + byte_size]

    def read_ndarray(self, offset, byte_size, datatype, shape) -> np.ndarray:
        view = self.read_view(offset, byte_size)
        if datatype == DataType.BYTES:
            return deserialize_tensor(bytes(view), datatype, shape)
        # zero-copy view; the device_put downstream performs the single DMA
        return np.frombuffer(view, dtype=np.uint8).view(
            _np_dtype(datatype)).reshape(tuple(int(d) for d in shape))

    def write_ndarray(self, offset, byte_size, arr: np.ndarray) -> int:
        from client_tpu.protocol.dtypes import np_to_wire_dtype

        offset = int(offset)
        if offset < 0 or offset > self.byte_size:
            raise EngineError(
                f"offset {offset} outside region '{self.name}' "
                f"({self.byte_size}B)", 400)
        raw = serialize_tensor(arr, np_to_wire_dtype(arr.dtype))
        start = self.offset + offset
        # Clamp the client-supplied placement size to the region extent so a
        # write can never spill past the registered region.
        limit = byte_size if byte_size > 0 else self.byte_size
        limit = min(limit, self.byte_size - offset)
        if len(raw) > limit:
            raise EngineError(
                f"output ({len(raw)}B) exceeds shm placement in region "
                f"'{self.name}' ({limit}B)", 400)
        self.map[start:start + len(raw)] = raw
        return len(raw)


def shm_path(key: str) -> str:
    """POSIX shm keys live under /dev/shm; '/key' and 'key' both accepted."""
    return "/dev/shm/" + key.lstrip("/")


class SystemShmManager:
    def __init__(self):
        self._regions: dict[str, _SysRegion] = {}
        self._lock = lockdep.Lock("shm.system")

    def register(self, name, key, offset, byte_size) -> None:
        with self._lock:
            if name in self._regions:
                raise EngineError(
                    f"shared memory region '{name}' already registered", 400)
            self._regions[name] = _SysRegion(name, key, offset, byte_size)

    def register_from_json(self, name, body: dict) -> None:
        self.register(name, body["key"], int(body.get("offset", 0)),
                      int(body["byte_size"]))

    def unregister(self, name: str | None) -> None:
        with self._lock:
            if name is None:
                for r in self._regions.values():
                    r.close()
                self._regions.clear()
                return
            region = self._regions.pop(name, None)
            if region is not None:
                region.close()

    def has_region(self, name) -> bool:
        with self._lock:
            return name in self._regions

    def status(self, name: str | None = None) -> dict:
        with self._lock:
            items = (
                self._regions.items() if name is None
                else [(name, self._regions[name])] if name in self._regions
                else [])
            return {
                n: {"name": n, "key": r.key, "offset": r.offset,
                    "byte_size": r.byte_size}
                for n, r in items
            }

    def _get(self, name) -> _SysRegion:
        with self._lock:
            region = self._regions.get(name)
        if region is None:
            raise EngineError(
                f"shared memory region '{name}' not registered", 400)
        return region

    def read_tensor(self, name, offset, byte_size, datatype, shape) -> np.ndarray:
        return self._get(name).read_ndarray(offset, byte_size, datatype,
                                            shape)

    def write_tensor(self, name, offset, byte_size, arr: np.ndarray) -> int:
        return self._get(name).write_ndarray(offset, byte_size,
                                             np.asarray(arr))


def _np_dtype(datatype: str):
    from client_tpu.protocol.dtypes import wire_to_np_dtype

    dt = wire_to_np_dtype(datatype)
    if dt is None:
        raise EngineError(f"unknown datatype '{datatype}'", 400)
    return dt


# ---------------------------------------------------------------------------
# TPU regions
# ---------------------------------------------------------------------------


class DeviceTensorView:
    """A zero-dispatch window into a device-resident batch output.

    The dynamic batcher's per-request output slices used to be lazy
    ``jax.Array`` slices — each one DISPATCHES a tiny XLA execution, so a
    64-request batch cost ~128 extra device executions just to split its
    outputs (measured as the round-3 device-plane pathology: 379 ips /
    p99 3.3 s on 64 B tensors vs 839 inline). A view carries only
    (parent, start, stop) metadata; the actual gather runs once, on the
    first reader, not per enqueued response."""

    __slots__ = ("parent", "start", "stop", "_materialized")

    def __init__(self, parent, start: int, stop: int):
        self.parent = parent
        self.start = int(start)
        self.stop = int(stop)
        self._materialized = None

    @property
    def shape(self):
        return (self.stop - self.start,) + tuple(self.parent.shape[1:])

    @property
    def ndim(self) -> int:
        return self.parent.ndim

    @property
    def dtype(self):
        return self.parent.dtype

    @property
    def nbytes(self) -> int:
        n = int(np.dtype(self.parent.dtype).itemsize)
        for d in self.shape:
            n *= int(d)
        return n

    def materialize(self):
        """The device slice, dispatched once and cached."""
        if self._materialized is None:
            self._materialized = self.parent[self.start:self.stop]
        return self._materialized

    def __array__(self, dtype=None):
        arr = np.asarray(self.materialize())
        return arr.astype(dtype) if dtype is not None else arr


def make_tpu_handle(key: str, byte_size: int, device_id: int = 0) -> bytes:
    """Serialize a cross-process TPU region handle (host-staged backing)."""
    return json.dumps({
        "kind": "host_staged",
        "key": key,
        "byte_size": int(byte_size),
        "device_id": int(device_id),
    }).encode("utf-8")


class _TpuRegion:
    __slots__ = ("name", "device_id", "byte_size", "kind", "staging",
                 "device_array")

    def __init__(self, name, device_id, byte_size, kind,
                 staging: _SysRegion | None = None,
                 device_array=None):
        self.name = name
        self.device_id = int(device_id)
        self.byte_size = int(byte_size)
        self.kind = kind                  # 'host_staged' | 'device'
        self.staging = staging
        self.device_array = device_array  # persistent HBM residency

    def close(self):
        if self.staging is not None:
            self.staging.close()
        self.device_array = None


class TpuShmManager:
    def __init__(self, devices=None):
        self._regions: dict[str, _TpuRegion] = {}
        self._lock = lockdep.Lock("shm.device")
        self._devices = devices

    def _device(self, device_id: int):
        import jax

        devices = self._devices or jax.devices()
        if device_id >= len(devices):
            raise EngineError(
                f"device_id {device_id} out of range "
                f"({len(devices)} devices)", 400)
        return devices[device_id]

    # -- registration --------------------------------------------------------

    def register_handle(self, name, raw_handle: bytes, device_id,
                        byte_size) -> None:
        """The gRPC/HTTP register path: raw bytes (or base64 over HTTP)."""
        try:
            desc = json.loads(bytes(raw_handle).decode("utf-8"))
        except Exception:
            raise EngineError(
                f"region '{name}': malformed TPU buffer handle", 400) from None
        # Fuzz contract: any malformed/truncated handle is a client error
        # (400), never a 500 — a JSON scalar/list, a missing or non-string
        # key, and a non-numeric byte_size all land here.
        if not isinstance(desc, dict):
            raise EngineError(
                f"region '{name}': malformed TPU buffer handle", 400)
        if desc.get("kind") != "host_staged":
            raise EngineError(
                f"region '{name}': unsupported handle kind "
                f"'{desc.get('kind')}'", 400)
        key = desc.get("key")
        if not isinstance(key, str) or not key:
            raise EngineError(
                f"region '{name}': handle missing shm key", 400)
        try:
            staged_size = int(desc.get("byte_size", byte_size))
        except (TypeError, ValueError):
            raise EngineError(
                f"region '{name}': malformed handle byte_size", 400) \
                from None
        staging = _SysRegion(name, key, 0, staged_size)
        with self._lock:
            if name in self._regions:
                staging.close()
                raise EngineError(
                    f"shared memory region '{name}' already registered", 400)
            self._regions[name] = _TpuRegion(
                name, device_id, byte_size, "host_staged", staging=staging)

    def register_from_json(self, name, body: dict) -> None:
        from client_tpu.protocol.codec import b64_decode_handle

        raw = b64_decode_handle(body["raw_handle"]["b64"])
        self.register_handle(name, raw, int(body.get("device_id", 0)),
                             int(body["byte_size"]))

    def register_device_array(self, name, array, device_id: int = 0) -> None:
        """In-process zero-copy path: the region *is* a device buffer."""
        with self._lock:
            if name in self._regions:
                raise EngineError(
                    f"shared memory region '{name}' already registered", 400)
            self._regions[name] = _TpuRegion(
                name, device_id, array.nbytes, "device", device_array=array)

    def unregister(self, name: str | None) -> None:
        with self._lock:
            if name is None:
                for r in self._regions.values():
                    r.close()
                self._regions.clear()
                return
            region = self._regions.pop(name, None)
            if region is not None:
                region.close()

    def has_region(self, name) -> bool:
        with self._lock:
            return name in self._regions

    def region_kind(self, name) -> str | None:
        """'device' | 'host_staged' | None (not registered here)."""
        with self._lock:
            region = self._regions.get(name)
            return region.kind if region is not None else None

    def status(self, name: str | None = None) -> dict:
        with self._lock:
            items = (
                self._regions.items() if name is None
                else [(name, self._regions[name])] if name in self._regions
                else [])
            return {
                n: {"name": n, "device_id": r.device_id,
                    "byte_size": r.byte_size}
                for n, r in items
            }

    def _get(self, name) -> _TpuRegion:
        with self._lock:
            region = self._regions.get(name)
        if region is None:
            raise EngineError(
                f"shared memory region '{name}' not registered", 400)
        return region

    # -- data plane ----------------------------------------------------------

    def read_tensor(self, name, offset, byte_size, datatype, shape):
        """'device' regions return their HBM-resident array (true zero-copy).

        Host-staged regions return a zero-copy *host* view: the dynamic
        batcher concatenates request tensors on host and issues ONE
        device_put per assembled batch (Model.execute_timed), so staging
        each request's inputs to HBM here would both serialize a device
        round trip per request ahead of the queue and force the batcher to
        fetch the arrays straight back."""
        region = self._get(name)
        shape = tuple(int(d) for d in shape)
        if region.kind == "device":
            arr = self._resolve_device_array(region)
            if int(offset):
                raise EngineError(
                    f"region '{name}': offsets unsupported for device "
                    "regions", 400)
            if tuple(arr.shape) != shape:
                arr = arr.reshape(shape)
            return arr
        # Validate the registered device ordinal even though staging reads
        # stay host-side (placement happens per batch in the engine).
        self._device(region.device_id)
        return region.staging.read_ndarray(offset, byte_size, datatype, shape)

    def write_tensor(self, name, offset, byte_size, arr) -> int:
        region = self._get(name)
        if region.kind == "device":
            # keep outputs HBM-resident; in-process readers fetch directly.
            # A device region holds exactly one buffer: offsets are invalid
            # (same contract as the read path) and size must fit.
            if int(offset):
                raise EngineError(
                    f"region '{name}': offsets unsupported for device "
                    "regions", 400)
            if int(arr.nbytes) > region.byte_size:
                raise EngineError(
                    f"output ({arr.nbytes}B) exceeds device region "
                    f"'{name}' ({region.byte_size}B)", 400)
            if isinstance(arr, DeviceTensorView):
                # Zero-dispatch store: the region holds the view; the
                # gather out of the batch buffer runs on first read. The
                # parent batch buffer stays alive until the next write —
                # bounded by one batch's outputs.
                region.device_array = arr
                return int(arr.nbytes)
            import jax

            region.device_array = (
                arr if isinstance(arr, jax.Array)
                else jax.device_put(arr, self._device(region.device_id)))
            return int(region.device_array.nbytes)
        return region.staging.write_ndarray(offset, byte_size,
                                            np.asarray(arr))

    def _resolve_device_array(self, region: _TpuRegion):
        """Materialize a stored output view (once). The store-back happens
        under the manager lock and only when the region still holds the
        SAME view — a concurrent write_tensor of a newer batch's output
        must not be clobbered by this read's stale materialization."""
        arr = region.device_array
        if not isinstance(arr, DeviceTensorView):
            return arr
        materialized = arr.materialize()
        with self._lock:
            if region.device_array is arr:
                region.device_array = materialized
        return materialized

    def read_back(self, name):
        """In-process reader: current device array of a region."""
        region = self._get(name)
        if region.kind == "device":
            return self._resolve_device_array(region)
        raise EngineError(
            f"region '{name}' is host-staged; read via its shm key", 400)
