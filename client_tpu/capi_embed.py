"""Support module for the in-process C API (`libtpuserver.so`).

The native shim (native/capi/tpu_server_capi.cc) embeds CPython and calls the
functions here — this file is the whole Python-side surface of the embedded
server, so the C code stays a thin marshalling layer. Plays the role the
reference delegates to the dlopen'd libtritonserver.so
(/root/reference/src/c++/perf_analyzer/client_backend/triton_c_api/
triton_loader.cc:251,899): an engine in the benchmark process, no network.

Contract with the C side:
- create_engine(models_csv) -> engine object (opaque PyObject to C)
- *_json helpers return JSON strings
- infer(engine, request_json, buffers) ->
  (response_json, [np.ndarray], [(name, datatype, shape)])
  where `buffers` are zero-copy memoryviews of caller-owned input bytes
  (valid only for the duration of the call), the returned arrays are
  C-contiguous and exposed back to C via the buffer protocol (zero-copy
  out), and the metadata tuples let the C side read names/dtypes/shapes
  without re-parsing the JSON on the hot path.
"""

from __future__ import annotations

import json
from client_tpu import config as envcfg

import numpy as np

from client_tpu.engine import InferRequest, TpuEngine
from client_tpu.engine.types import EngineError, OutputRequest
from client_tpu.models import build_repository
from client_tpu.protocol.codec import (
    deserialize_bytes_tensor,
    serialize_bytes_tensor,
)
from client_tpu.protocol.dtypes import np_to_wire_dtype, wire_to_np_dtype


def create_engine(models_csv: str = "") -> TpuEngine:
    # The device is JAX's choice: JAX_PLATFORMS=cpu in the embedding
    # process's environment runs the engine hermetically.
    names = [n.strip() for n in models_csv.split(",") if n.strip()] or None
    # CLIENT_TPU_WARMUP=1: pre-compile every batch bucket at load so no
    # XLA compile ever lands inside a perf-harness measurement window
    # (pair with tpu_perf_analyzer --warmup-request-count for the
    # request-path caches).
    warmup = envcfg.env_flag("CLIENT_TPU_WARMUP")
    return TpuEngine(build_repository(names), warmup=warmup)


def shutdown_engine(engine: TpuEngine) -> None:
    engine.shutdown()


def model_metadata_json(engine: TpuEngine, name: str, version: str = "") -> str:
    return json.dumps(engine.model_metadata(name, version))


def model_config_json(engine: TpuEngine, name: str, version: str = "") -> str:
    return json.dumps(engine.model_config(name, version))


def model_statistics_json(engine: TpuEngine, name: str = "",
                          version: str = "") -> str:
    return json.dumps(engine.model_statistics(name, version))


def server_metadata_json(engine: TpuEngine) -> str:
    return json.dumps(engine.server_metadata())


def register_system_shm(engine: TpuEngine, name: str, key: str,
                        byte_size: int) -> None:
    engine.system_shm.register(name, key, 0, int(byte_size))


def unregister_system_shm(engine: TpuEngine, name: str = "") -> None:
    engine.system_shm.unregister(name or None)


def register_tpu_shm(engine: TpuEngine, name: str, raw_handle: bytes,
                     device_id: int, byte_size: int) -> None:
    engine.tpu_shm.register_handle(name, raw_handle, int(device_id),
                                   int(byte_size))


def unregister_tpu_shm(engine: TpuEngine, name: str = "") -> None:
    engine.tpu_shm.unregister(name or None)


def _read_shm_input(engine: TpuEngine, meta: dict) -> np.ndarray:
    p = meta.get("parameters") or {}
    if "shared_memory_region" not in p:
        # data=NULL is the C API's shm marker (tpu_server_capi.h); a NULL
        # buffer without the parameters is a caller wiring bug — surface it
        # as a clean 400, not a KeyError traceback.
        raise EngineError(
            f"input '{meta.get('name')}': NULL data pointer but no "
            "shared_memory_region/byte_size parameters", 400)
    return engine.read_shm_tensor(
        p["shared_memory_region"], int(p.get("shared_memory_offset", 0)),
        int(p.get("shared_memory_byte_size", 0)), meta["datatype"],
        meta["shape"])


def _input_array(meta: dict, buf) -> np.ndarray:
    dtype = meta["datatype"]
    shape = meta["shape"]
    if dtype == "BYTES":
        arr = deserialize_bytes_tensor(bytes(buf))
        return arr.reshape(shape)
    # Zero-copy view over caller memory; the engine's batcher copies on
    # concatenation, and the call is synchronous, so the view never outlives
    # the caller's buffer.
    return np.frombuffer(buf, dtype=wire_to_np_dtype(dtype)).reshape(shape)


def infer(engine: TpuEngine, request_json: str, buffers: list):
    req_d = json.loads(request_json)
    inputs_meta = req_d.get("inputs", [])
    if len(inputs_meta) != len(buffers):
        raise ValueError(
            f"{len(inputs_meta)} input descriptors but {len(buffers)} buffers")
    inputs = {}
    for m, b in zip(inputs_meta, buffers):
        if b is None or (m.get("parameters") or {}).get(
                "shared_memory_region"):
            inputs[m["name"]] = _read_shm_input(engine, m)
        else:
            inputs[m["name"]] = _input_array(m, b)
    outputs = []
    for o in req_d.get("outputs", []):
        p = o.get("parameters") or {}
        outputs.append(OutputRequest(
            name=o["name"],
            classification_count=int(o.get("classification", 0)),
            shm_region=p.get("shared_memory_region"),
            shm_offset=int(p.get("shared_memory_offset", 0)),
            shm_byte_size=int(p.get("shared_memory_byte_size", 0)),
        ))
    # True zero-copy output plane: if every requested output lands in a
    # device-resident tpu region, the scheduler skips the D2H fetch and the
    # shm write below stores the HBM-resident slice as-is.
    keep_on_device = bool(outputs) and all(
        o.shm_region and engine.tpu_shm.region_kind(o.shm_region) == "device"
        for o in outputs)
    req = InferRequest(
        model_name=req_d["model_name"],
        model_version=req_d.get("model_version", ""),
        request_id=req_d.get("id", ""),
        inputs=inputs,
        outputs=outputs,
        sequence_id=int(req_d.get("sequence_id", 0)),
        sequence_start=bool(req_d.get("sequence_start", False)),
        sequence_end=bool(req_d.get("sequence_end", False)),
        priority=int(req_d.get("priority", 0)),
        timeout_us=int(req_d.get("timeout_us", 0)),
        keep_outputs_on_device=keep_on_device,
    )
    timeout_s = req.timeout_us / 1e6 if req.timeout_us else None
    resp = engine.infer(req, timeout_s=timeout_s)

    out_meta = []
    out_arrays = []
    out_req = {o.name: o for o in outputs}
    for name, arr in resp.outputs.items():
        o = out_req.get(name)
        if o is not None and o.shm_region:
            # shm-placed output: write into the region, return parameters
            # instead of a data view (the caller owns the mapping).
            written = engine.write_shm_tensor(o.shm_region, o.shm_offset,
                                              o.shm_byte_size, arr)
            out_meta.append({
                "name": name,
                "datatype": np_to_wire_dtype(arr.dtype) or "BYTES",
                "shape": list(arr.shape),
                "parameters": {
                    "shared_memory_region": o.shm_region,
                    "shared_memory_offset": o.shm_offset,
                    "shared_memory_byte_size": written,
                },
            })
            out_arrays.append(None)
            continue
        wire = np_to_wire_dtype(arr.dtype)
        if wire is None or arr.dtype.kind in ("S", "U", "O"):
            data = np.frombuffer(serialize_bytes_tensor(arr), dtype=np.uint8)
            out_meta.append({"name": name, "datatype": "BYTES",
                             "shape": list(arr.shape)})
            out_arrays.append(data)
        else:
            out_meta.append({"name": name, "datatype": wire,
                             "shape": list(arr.shape)})
            out_arrays.append(np.ascontiguousarray(arr))
    response_json = json.dumps({
        "model_name": resp.model_name,
        "model_version": resp.model_version,
        "id": resp.request_id,
        "outputs": out_meta,
    })
    metas = [(m["name"], m["datatype"], m["shape"]) for m in out_meta]
    return response_json, out_arrays, metas
