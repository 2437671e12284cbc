"""Native C++ layer tests: build, unit tests, and example-clients-as-
conformance-tests against a live HTTP server (the example binaries
hard-assert output values, same oracle style as the reference's simple_*
examples, SURVEY.md §4).
"""

import os
import subprocess

import pytest

from client_tpu.engine import TpuEngine
from client_tpu.models import build_repository
from client_tpu.server import HttpInferenceServer
from client_tpu.server.grpc_server import GrpcInferenceServer

NATIVE = os.path.join(os.path.dirname(__file__), "..", "native")
BUILD = os.path.join(NATIVE, "build")

EXAMPLES = [
    "simple_http_infer_client",
    "simple_http_async_infer_client",
    "simple_http_string_infer_client",
    "simple_http_shm_client",
    "simple_http_sequence_client",
    "simple_http_health_metadata",
    "simple_http_model_control",
    "simple_http_tpushm_client",
]

# gRPC conformance clients: the in-tree C++ HTTP/2+HPACK transport driven
# against the framework's grpcio-based server (wire interop both ways).
GRPC_EXAMPLES = [
    "simple_grpc_infer_client",
    "simple_grpc_async_infer_client",
    "simple_grpc_string_infer_client",
    "simple_grpc_shm_client",
    "simple_grpc_tpushm_client",
    "simple_grpc_sequence_sync_client",
    "simple_grpc_sequence_stream_client",
    "simple_grpc_custom_repeat_client",
    "grpc_generate_client",
    "simple_grpc_health_metadata",
]


@pytest.fixture(scope="module")
def native_build():
    """Configure+build the native tree (no-op when up to date)."""
    subprocess.run(
        ["cmake", "-B", "build", "-G", "Ninja", "-DCMAKE_BUILD_TYPE=Release"],
        cwd=NATIVE, check=True, capture_output=True)
    proc = subprocess.run(["ninja", "-C", "build"], cwd=NATIVE,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return BUILD


@pytest.fixture(scope="module")
def server():
    eng = TpuEngine(build_repository(
        ["simple", "simple_string", "simple_sequence"]))
    # A sequence may stand idle for a minute here, not the default second:
    # on a host that other test workers load, a sequence of the load
    # generator's waits longer than that between two of its requests, is
    # collected, and its next request is refused ("request without start
    # flag for an inactive sequence": what
    # test_perf_analyzer_num_of_sequences_rate_mode met).
    eng.repository.get("simple_sequence").config.sequence_batching \
        .max_sequence_idle_microseconds = 60_000_000
    srv = HttpInferenceServer(eng, port=0).start()
    yield srv
    srv.stop()
    eng.shutdown()


def test_unit_tests(native_build):
    proc = subprocess.run([os.path.join(native_build, "tpuclient_unit_tests")],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL UNIT TESTS PASSED" in proc.stdout


@pytest.fixture(scope="module")
def grpc_server():
    eng = TpuEngine(build_repository(
        ["simple", "simple_string", "simple_sequence", "simple_repeat",
         "resnet50", "tiny_gpt"]))
    # Pre-compile the resnet50 bucket the image client hits: on a loaded CI
    # machine an XLA compile inside a client's first request can outlast
    # the client timeout and flake the conformance run.
    import numpy as np

    from client_tpu.engine import InferRequest

    eng.infer(InferRequest(
        model_name="resnet50",
        inputs={"INPUT": np.zeros((2, 224, 224, 3), np.float32)}),
        timeout_s=300)
    srv = GrpcInferenceServer(eng, port=0).start()
    yield srv
    srv.stop()
    eng.shutdown()


@pytest.fixture(scope="module")
def ensemble_server():
    eng = TpuEngine(build_repository(
        ["image_preprocess", "resnet50", "ensemble_image"]))
    srv = HttpInferenceServer(eng, port=0).start()
    yield srv
    srv.stop()
    eng.shutdown()


@pytest.mark.parametrize("example", EXAMPLES)
def test_example_conformance(native_build, server, example):
    binary = os.path.join(native_build, example)
    proc = subprocess.run([binary, "-u", server.url], capture_output=True,
                          text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


@pytest.mark.parametrize("example", GRPC_EXAMPLES)
def test_grpc_example_conformance(native_build, grpc_server, example):
    binary = os.path.join(native_build, example)
    url = f"127.0.0.1:{grpc_server.port}"
    proc = subprocess.run([binary, "-u", url], capture_output=True,
                          text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_ensemble_image_client(native_build, ensemble_server):
    """C++ ensemble client: raw image -> preprocess -> resnet50 in one
    request (reference ensemble_image_client.cc:365)."""
    binary = os.path.join(native_build, "ensemble_image_client")
    proc = subprocess.run([binary, "-u", ensemble_server.url],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_perf_analyzer_smoke(native_build, server, tmp_path):
    """tpu_perf_analyzer end-to-end: short concurrency sweep against the live
    HTTP server, asserting a sane throughput figure and CSV export
    (reference perf_analyzer CLI surface, SURVEY.md §2.2/§3.3)."""
    csv = tmp_path / "perf.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", server.url, "-p", "600", "-r", "6",
         "-s", "70", "--concurrency-range", "2:2", "-f", str(csv)],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Throughput" in proc.stdout
    lines = csv.read_text().strip().splitlines()
    assert len(lines) >= 2, lines
    # header + one row; throughput column must be positive
    header = lines[0].split(",")
    row = lines[1].split(",")
    ips = float(row[header.index("Inferences/Second")])
    assert ips > 0


def test_perf_analyzer_long_flag_aliases(native_build, server, tmp_path):
    """Reference long spellings of the short options (--measurement-interval,
    --stability-percentage, --max-trials, --sync; reference main.cc option
    table): both forms accepted, same semantics."""
    csv = tmp_path / "alias.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", server.url,
         "--measurement-interval", "600", "--max-trials", "6",
         "--stability-percentage", "70", "--sync",
         "--concurrency-range", "2:2", "-f", str(csv)],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


def test_perf_analyzer_grpc_compression_flag(native_build, grpc_server):
    """--grpc-compression-algorithm gzip: every generated request rides the
    native client's per-call message compression (reference flag; the
    grpcio server transparently decompresses)."""
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", f"127.0.0.1:{grpc_server.port}",
         "-i", "grpc", "--grpc-compression-algorithm", "gzip",
         "-p", "600", "-r", "6", "-s", "70",
         "--concurrency-range", "2:2"],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Throughput" in proc.stdout


def test_perf_analyzer_num_of_sequences_rate_mode(native_build, server):
    """--num-of-sequences under request-rate load: the sequence pool is
    bounded to N distinct concurrent sequences (reference semantics; in
    concurrency mode the pool is sized by the concurrency level)."""
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple_sequence", "-u", server.url, "-a",
         "--request-rate-range", "50:50", "--num-of-sequences", "2",
         "--sequence-length", "4",
         "-p", "800", "-r", "6", "-s", "70"],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Throughput" in proc.stdout


def test_client_timeout_binary(native_build, server, grpc_server):
    """Reference test parity: client_timeout_test drives sync/async/stream
    over both protocols with microsecond and generous deadlines
    (reference src/c++/tests/client_timeout_test.cc:391)."""
    proc = subprocess.run(
        [os.path.join(native_build, "client_timeout_test"),
         "-u", server.url, "-g", f"127.0.0.1:{grpc_server.port}"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_memory_leak_binary(native_build, server, grpc_server):
    """Reference test parity: memory_leak_test loops inferences with and
    without object reuse, bounding RSS growth (reference
    memory_leak_test.cc:301)."""
    proc = subprocess.run(
        [os.path.join(native_build, "memory_leak_test"),
         "-u", server.url, "-g", f"127.0.0.1:{grpc_server.port}",
         "-r", "300"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_reuse_infer_objects_binary(native_build, server, grpc_server):
    proc = subprocess.run(
        [os.path.join(native_build, "reuse_infer_objects_client"),
         "-u", server.url, "-g", f"127.0.0.1:{grpc_server.port}", "-n", "8"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_model_control_binary(native_build, grpc_server):
    proc = subprocess.run(
        [os.path.join(native_build, "simple_grpc_model_control"),
         "-u", f"127.0.0.1:{grpc_server.port}"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_image_client_binary(native_build, grpc_server):
    """image_client over gRPC with the classification extension, batch 2."""
    proc = subprocess.run(
        [os.path.join(native_build, "image_client"),
         "-u", f"127.0.0.1:{grpc_server.port}", "-i", "grpc",
         "-m", "resnet50", "-b", "2", "-c", "3"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Image 1:" in proc.stdout


def test_perf_analyzer_grpc_smoke(native_build, grpc_server, tmp_path):
    """tpu_perf_analyzer -i grpc: async concurrency sweep over the native
    gRPC client against the grpcio server (reference protocol-switched
    backend, triton_client_backend.h:61-199)."""
    csv = tmp_path / "perf_grpc.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-i", "grpc", "-u",
         f"127.0.0.1:{grpc_server.port}", "-a",
         "-p", "600", "-r", "6", "-s", "70",
         "--concurrency-range", "4:4", "-f", str(csv)],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Throughput" in proc.stdout
    assert "Inference count" in proc.stdout  # server stats over gRPC too
    header, row = [ln.split(",") for ln in
                   csv.read_text().strip().splitlines()[:2]]
    assert float(row[header.index("Inferences/Second")]) > 0


def test_perf_analyzer_streaming_sequence(native_build, grpc_server):
    """--streaming (reference main.cc:610-748): requests ride the bidi
    gRPC stream, completions multiplex back by request id; sequence steps
    keep per-context order. The report must show real measured load."""
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple_sequence", "-u", f"127.0.0.1:{grpc_server.port}",
         "--service-kind", "tpu_grpc", "--streaming",
         "-p", "600", "-r", "6", "-s", "70", "--sequence-length", "4",
         "--concurrency-range", "4:4"],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Throughput" in proc.stdout
    assert "Inference count" in proc.stdout


def test_perf_analyzer_generative_profile(native_build, grpc_server):
    """--generative: token-streaming measurement through the networked
    gRPC stack — TTFT / inter-token latency percentiles and tok/s for a
    decoupled model (the reference profiler has no token vocabulary)."""
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "tiny_gpt", "-u", f"127.0.0.1:{grpc_server.port}",
         "--service-kind", "tpu_grpc", "--generative",
         "--generative-max-tokens", "6", "--shape", "INPUT_IDS:4",
         "-p", "1500", "--concurrency-range", "4:4"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "tok/s" in proc.stdout and "TTFT" in proc.stdout
    import json as _json

    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("{")][-1]
    rep = _json.loads(line)
    assert rep["tok_s"] > 0
    assert rep["ttft_us_p50"] > 0 and rep["itl_us_p50"] >= 0


def test_perf_analyzer_capi_inprocess(native_build, tmp_path):
    """--service-kind tpu_capi: perf harness dlopens libtpuserver.so, which
    embeds CPython hosting the engine — no server process, no network
    (reference triton_c_api kind, SURVEY.md §2.3/§3.5). CPU platform for
    hermetic runs."""
    csv = tmp_path / "capi.csv"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "--service-kind", "tpu_capi",
         "--capi-library-path", os.path.join(native_build, "libtpuserver.so"),
         "--capi-repo-root", os.path.join(NATIVE, ".."),
         "-p", "600", "-r", "6", "-s", "70",
         "--concurrency-range", "2:2", "-f", str(csv)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Throughput" in proc.stdout
    # Server-side stats must flow through the in-process path too.
    assert "Inference count" in proc.stdout
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


@pytest.mark.parametrize("shm_mode", ["system", "tpu"])
def test_perf_analyzer_shm_modes(native_build, server, tmp_path, shm_mode):
    """--shared-memory system|tpu over HTTP: the north-star data planes
    (BASELINE.json config 2, reference cudashm path load_manager.cc:287-446)
    driven by the native harness against the live server."""
    csv = tmp_path / f"shm_{shm_mode}.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", server.url, "-p", "600", "-r", "6",
         "-s", "70", "--concurrency-range", "2:2",
         "--shared-memory", shm_mode, "-f", str(csv)],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


def test_perf_analyzer_capi_tpushm(native_build, tmp_path):
    """In-process engine + tpu-shm regions: the full north-star config with
    zero network anywhere (reference has no counterpart — its C-API kind
    cannot do shm, main.cc:1227-1248)."""
    csv = tmp_path / "capi_tpushm.csv"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "--service-kind", "tpu_capi",
         "--capi-library-path", os.path.join(native_build, "libtpuserver.so"),
         "--capi-repo-root", os.path.join(NATIVE, ".."),
         "-p", "600", "-r", "6", "-s", "70",
         "--concurrency-range", "2:2", "--shared-memory", "tpu",
         "-f", str(csv)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


def test_libcshm_ctypes(native_build):
    """The C shm extension loads via ctypes and round-trips data
    (reference shared_memory ctypes bindings,
    /root/reference/src/python/library/tritonclient/utils/shared_memory/
    __init__.py:46-73)."""
    import ctypes

    lib = ctypes.CDLL(os.path.join(native_build, "libcshm.so"))
    lib.SharedMemoryRegionCreate.restype = ctypes.c_int
    lib.SharedMemoryRegionCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.POINTER(ctypes.c_void_p)]
    handle = ctypes.c_void_p()
    rc = lib.SharedMemoryRegionCreate(b"/pytest_cshm", 1024,
                                      ctypes.byref(handle))
    assert rc == 0
    data = (ctypes.c_uint8 * 4)(1, 2, 3, 4)
    assert lib.SharedMemoryRegionSet(
        handle, ctypes.c_uint64(0), ctypes.c_uint64(4), data) == 0
    out = (ctypes.c_uint8 * 4)()
    assert lib.SharedMemoryRegionRead(
        handle, ctypes.c_uint64(0), ctypes.c_uint64(4), out) == 0
    assert list(out) == [1, 2, 3, 4]
    # out-of-range rejected
    assert lib.SharedMemoryRegionSet(
        handle, ctypes.c_uint64(1021), ctypes.c_uint64(4), data) != 0
    assert lib.SharedMemoryRegionDestroy(handle) == 0


# ---------------------------------------------------------------------------
# TLS, compression, keepalive (reference SslOptions grpc_client.h:42-58,
# CompressData http_client.cc:122-198, KeepAliveOptions grpc_client.h:61-81)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tls_cert(tmp_path_factory):
    """Self-signed cert with SANs for localhost and 127.0.0.1."""
    d = tmp_path_factory.mktemp("tls")
    cert, key = str(d / "cert.pem"), str(d / "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-keyout", key,
         "-out", cert, "-days", "2", "-nodes", "-subj", "/CN=localhost",
         "-addext", "subjectAltName=DNS:localhost,IP:127.0.0.1"],
        check=True, capture_output=True)
    return cert, key


@pytest.fixture(scope="module")
def tls_server(tls_cert):
    cert, key = tls_cert
    eng = TpuEngine(build_repository(["simple"]))
    srv = HttpInferenceServer(eng, port=0, certfile=cert, keyfile=key).start()
    yield srv
    srv.stop()
    eng.shutdown()


@pytest.fixture(scope="module")
def tls_grpc_server(tls_cert):
    cert, key = tls_cert
    eng = TpuEngine(build_repository(["simple"]))
    srv = GrpcInferenceServer(eng, port=0, certfile=cert, keyfile=key).start()
    yield srv
    srv.stop()
    eng.shutdown()


def test_https_infer(native_build, tls_server, tls_cert):
    """Native HTTP client over https:// with peer+host verification against
    the provided CA (the self-signed cert doubles as its own root)."""
    binary = os.path.join(native_build, "simple_http_infer_client")
    proc = subprocess.run(
        [binary, "-u", f"https://127.0.0.1:{tls_server.port}",
         "-C", tls_cert[0]],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_https_rejects_unknown_ca(native_build, tls_server):
    """Without the CA, verification must fail (no silent insecure fallback)."""
    binary = os.path.join(native_build, "simple_http_infer_client")
    proc = subprocess.run(
        [binary, "-u", f"https://127.0.0.1:{tls_server.port}"],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode != 0
    assert "TLS" in proc.stderr or "certificate" in proc.stderr.lower()


def test_grpcs_infer(native_build, tls_grpc_server, tls_cert):
    """Native gRPC client (h2 over TLS, ALPN h2) against the grpcio server's
    secure port."""
    binary = os.path.join(native_build, "simple_grpc_infer_client")
    proc = subprocess.run(
        [binary, "-u", f"grpcs://127.0.0.1:{tls_grpc_server.port}",
         "-C", tls_cert[0]],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


@pytest.mark.parametrize("algo", ["gzip", "deflate"])
def test_http_compression(native_build, server, algo):
    """Request body compressed (Content-Encoding) and response compression
    negotiated (Accept-Encoding) end to end; values still assert."""
    binary = os.path.join(native_build, "simple_http_infer_client")
    proc = subprocess.run([binary, "-u", server.url, "-z", algo],
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.parametrize("algo", ["gzip", "deflate"])
def test_grpc_message_compression(native_build, grpc_server, algo):
    """Per-call gRPC message compression (reference grpc_client.h:323-382:
    Infer takes grpc_compression_algorithm; here InferOptions carries it):
    the framed request goes out with flag byte 1 + grpc-encoding, the
    grpcio server inflates it natively, and the add/sub values assert."""
    binary = os.path.join(native_build, "simple_grpc_infer_client")
    proc = subprocess.run(
        [binary, "-u", f"127.0.0.1:{grpc_server.port}", "-z", algo],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_grpc_keepalive(native_build, grpc_server):
    """Transport keepalive: aggressive PING cadence across an idle window,
    then a value-asserting inference on the same channel."""
    binary = os.path.join(native_build, "simple_grpc_keepalive_client")
    proc = subprocess.run([binary, "-u", f"127.0.0.1:{grpc_server.port}"],
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


# ---------------------------------------------------------------------------
# TENSORFLOW_SERVING + TORCHSERVE backend kinds (reference
# client_backend.h:101-106, tfserve_grpc_client.{h,cc},
# torchserve_http_client.{h,cc}) against hermetic fake servers.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tfs_pb2(tmp_path_factory):
    """Python message classes generated from the same re-authored TFS protos
    the C++ backend compiles — the test proves both sides share one wire."""
    import sys

    d = tmp_path_factory.mktemp("tfs_pb")
    proto_dir = os.path.join(NATIVE, "..", "client_tpu", "protocol", "protos")
    subprocess.run(
        ["protoc", f"--python_out={d}", "-I", proto_dir,
         os.path.join(proto_dir, "tfs_predict.proto")],
        check=True, capture_output=True)
    sys.path.insert(0, str(d))
    try:
        import tfs_predict_pb2
    finally:
        sys.path.remove(str(d))
    return tfs_predict_pb2


@pytest.fixture(scope="module")
def fake_tfs_server(tfs_pb2):
    """Minimal TFS PredictionService: y = 2x, serving_default signature."""
    from concurrent import futures as cf

    import grpc
    import numpy as np

    pb = tfs_pb2

    def predict(req, ctx):
        resp = pb.PredictResponse()
        resp.model_spec.name = req.model_spec.name
        x = np.frombuffer(req.inputs["x"].tensor_content, np.float32)
        out = resp.outputs["y"]
        out.dtype = pb.DT_FLOAT
        out.tensor_shape.dim.add().size = len(x)
        out.tensor_content = (2 * x).astype(np.float32).tobytes()
        return resp

    def metadata(req, ctx):
        resp = pb.GetModelMetadataResponse()
        resp.model_spec.name = req.model_spec.name
        sigmap = pb.SignatureDefMap()
        sig = sigmap.signature_def["serving_default"]
        ti = sig.inputs["x"]
        ti.name, ti.dtype = "x", pb.DT_FLOAT
        ti.tensor_shape.dim.add().size = 4
        to = sig.outputs["y"]
        to.name, to.dtype = "y", pb.DT_FLOAT
        to.tensor_shape.dim.add().size = 4
        resp.metadata["signature_def"].Pack(sigmap)
        return resp

    handler = grpc.method_handlers_generic_handler(
        "tensorflow.serving.PredictionService", {
            "Predict": grpc.unary_unary_rpc_method_handler(
                predict,
                request_deserializer=pb.PredictRequest.FromString,
                response_serializer=pb.PredictResponse.SerializeToString),
            "GetModelMetadata": grpc.unary_unary_rpc_method_handler(
                metadata,
                request_deserializer=pb.GetModelMetadataRequest.FromString,
                response_serializer=(
                    pb.GetModelMetadataResponse.SerializeToString)),
        })
    server = grpc.server(cf.ThreadPoolExecutor(max_workers=8),
                         handlers=(handler,))
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    yield port
    server.stop(1)


def test_perf_analyzer_tfserving(native_build, fake_tfs_server, tmp_path):
    """Harness drives the TFS kind end to end: metadata via signature_def,
    Predict with tensor_content I/O, a short stable sweep."""
    csv = tmp_path / "tfs.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "toy", "--service-kind", "tfserving",
         "-u", f"127.0.0.1:{fake_tfs_server}",
         "-p", "300", "-r", "4", "-s", "70",
         "--concurrency-range", "1:1", "-f", str(csv)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


def test_perf_analyzer_tfs_signature_flag(native_build, fake_tfs_server):
    """--model-signature-name (reference flag, TFS kind): an explicit
    signature reaches GetModelMetadata/Predict; naming the served default
    works, naming a missing one fails with the signature in the error."""
    base = [os.path.join(native_build, "tpu_perf_analyzer"),
            "-m", "toy", "--service-kind", "tfserving",
            "-u", f"127.0.0.1:{fake_tfs_server}",
            "-p", "300", "-r", "4", "-s", "70",
            "--concurrency-range", "1:1"]
    ok = subprocess.run(base + ["--model-signature-name", "serving_default"],
                        capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(base + ["--model-signature-name", "nope"],
                         capture_output=True, text=True, timeout=120)
    assert bad.returncode != 0
    assert "nope" in (bad.stdout + bad.stderr)


@pytest.fixture(scope="module")
def fake_torchserve_server():
    """Minimal TorchServe inference API: POST /predictions/<model>."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):  # noqa: N802
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            if not self.path.startswith("/predictions/") or not body:
                self.send_response(400)
                self.end_headers()
                return
            resp = (b'{"prediction": [0.1, 0.9], "bytes": %d}'
                    % len(body))
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(resp)))
            self.end_headers()
            self.wfile.write(resp)

        def log_message(self, *a):  # quiet
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield httpd.server_address[1]
    httpd.shutdown()


def test_perf_analyzer_torchserve(native_build, fake_torchserve_server,
                                  tmp_path):
    """Harness drives the TorchServe kind: BYTES input names an upload file
    (reference --input-data flow, main.cc:1210-1216)."""
    upload = tmp_path / "payload.bin"
    upload.write_bytes(b"\x00\x01fake-image-bytes" * 64)
    data = tmp_path / "input.json"
    data.write_text(
        '{"data": [{"TORCHSERVE_INPUT": ["%s"]}]}' % upload)
    csv = tmp_path / "ts.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "toy", "--service-kind", "torchserve",
         "-u", f"127.0.0.1:{fake_torchserve_server}",
         "--input-data", str(data),
         "-p", "300", "-r", "4", "-s", "70",
         "--concurrency-range", "1:1", "-f", str(csv)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


def test_perf_analyzer_b64_input_data(native_build, server, tmp_path):
    """--input-data JSON with {"b64": ...} binary content (reference's
    base64 raw form) drives the sweep end to end."""
    import base64

    import numpy as np

    vals = np.arange(16, dtype=np.int32)
    b64 = base64.b64encode(vals.tobytes()).decode()
    data = tmp_path / "b64.json"
    data.write_text(
        '{"data": [{"INPUT0": {"b64": "%s", "shape": [16]}, '
        '"INPUT1": {"b64": "%s", "shape": [16]}}]}' % (b64, b64))
    csv = tmp_path / "b64.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", server.url, "--input-data", str(data),
         "-p", "300", "-r", "4", "-s", "70",
         "--concurrency-range", "1:1", "-f", str(csv)],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


def test_perf_analyzer_dir_input_data(native_build, server, tmp_path):
    """--input-data <directory>: raw little-endian bytes per input-named file
    (reference ReadDataFromDir, data_loader.cc:41-69)."""
    import numpy as np

    vals = np.arange(16, dtype=np.int32)
    ddir = tmp_path / "data"
    ddir.mkdir()
    (ddir / "INPUT0").write_bytes(vals.tobytes())
    (ddir / "INPUT1").write_bytes(vals.tobytes())
    csv = tmp_path / "dir.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", server.url, "--input-data", str(ddir),
         "-p", "300", "-r", "4", "-s", "70",
         "--concurrency-range", "1:1", "-f", str(csv)],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0

    # Size mismatch is a load-time error, not a silent truncation.
    (ddir / "INPUT0").write_bytes(vals.tobytes()[:-4])
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", server.url, "--input-data", str(ddir),
         "-p", "300", "-r", "4", "-s", "70", "--concurrency-range", "1:1"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "shape wants" in proc.stderr


def test_perf_analyzer_warmup_flag(native_build, server, tmp_path):
    """--warmup-request-count sends unmeasured requests first (keeps XLA
    per-bucket compiles out of the measurement windows)."""
    csv = tmp_path / "warm.csv"
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "simple", "-u", server.url, "--warmup-request-count", "4",
         "-p", "300", "-r", "4", "-s", "70",
         "--concurrency-range", "1:1", "-f", str(csv)],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "warmup" in proc.stderr
    lines = csv.read_text().strip().splitlines()
    header, row = lines[0].split(","), lines[1].split(",")
    assert float(row[header.index("Inferences/Second")]) > 0


def test_perf_analyzer_ensemble_composing_csv(native_build, tmp_path):
    """Ensemble sweeps export one CSV per composing model with the
    server-side phase breakdown (reference main.cc:1503-1668 writes
    `<path>.<model>` files)."""
    csv = tmp_path / "ens.csv"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CLIENT_TPU_WARMUP="1")
    proc = subprocess.run(
        [os.path.join(native_build, "tpu_perf_analyzer"),
         "-m", "ensemble_image",
         "--capi-models", "ensemble_image,image_preprocess,resnet50",
         "--service-kind", "tpu_capi",
         "--capi-library-path", os.path.join(native_build, "libtpuserver.so"),
         "--capi-repo-root", os.path.join(NATIVE, ".."),
         "--shape", "RAW_IMAGE:256,256,3",
         "--warmup-request-count", "2",
         "-p", "800", "-r", "6", "-s", "90",
         "--concurrency-range", "2:2", "-f", str(csv)],
        capture_output=True, text=True, timeout=400, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "Composing model" in proc.stdout
    for composing in ("image_preprocess", "resnet50"):
        child = tmp_path / f"ens.csv.{composing}"
        assert child.exists(), f"missing {child}"
        header, row = child.read_text().strip().splitlines()[:2]
        assert "Server Compute Infer" in header
        cols = dict(zip(header.split(","), row.split(",")))
        assert int(cols["Inference Count"]) > 0


@pytest.fixture(scope="module")
def sanitizer_builds():
    """ASan + TSan builds of the native tree (the reference ships no
    sanitizer configuration at all, SURVEY.md §5.2)."""
    outs = {}
    for san in ("address", "thread"):
        bdir = f"build-{san[:4] if san == 'address' else san}"
        bdir = {"address": "build-asan", "thread": "build-tsan"}[san]
        subprocess.run(
            ["cmake", "-B", bdir, "-G", "Ninja",
             "-DCMAKE_BUILD_TYPE=RelWithDebInfo",
             f"-DTPUCLIENT_SANITIZE={san}"],
            cwd=NATIVE, check=True, capture_output=True)
        proc = subprocess.run(
            ["ninja", "-C", bdir, "tpuclient_unit_tests",
             "simple_grpc_async_infer_client"],
            cwd=NATIVE, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        outs[san] = os.path.join(NATIVE, bdir)
    return outs


@pytest.mark.parametrize("san", ["address", "thread"])
def test_unit_tests_under_sanitizer(sanitizer_builds, san):
    proc = subprocess.run(
        [os.path.join(sanitizer_builds[san], "tpuclient_unit_tests")],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL UNIT TESTS PASSED" in proc.stdout


@pytest.mark.parametrize("san", ["address", "thread"])
def test_async_grpc_client_under_sanitizer(sanitizer_builds, grpc_server,
                                           san):
    """The async gRPC client (h2 transport + completion worker threads)
    against a live server under ASan/TSan — the hot concurrent paths the
    reference documents as thread-safety contracts but never checks."""
    proc = subprocess.run(
        [os.path.join(sanitizer_builds[san],
                      "simple_grpc_async_infer_client"),
         "-u", f"127.0.0.1:{grpc_server.port}"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_h2_settings_ack_precedes_frames_sized_under_new_limits(native_build):
    """RFC 7540 §6.5.3 contract: the peer may enforce its OLD limits until
    it receives our SETTINGS ACK (grpc-core does, for max_frame_size). A
    fake server advertises max_frame=4MB and asserts that any DATA frame
    larger than the 16384 default arrives only AFTER the client's ACK —
    the regression test for an intermittent 'Failed parsing HTTP/2'
    GOAWAY under load."""
    import socket
    import struct
    import threading as th

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    order: list = []
    done = th.Event()

    def fake_server():
        conn, _ = srv.accept()
        conn.settimeout(30)
        buf = b""

        def read(n):
            nonlocal buf
            while len(buf) < n:
                d = conn.recv(65536)
                if not d:
                    raise EOFError
                buf += d
            out, buf = buf[:n], buf[n:]
            return out

        try:
            read(24)  # client preface
            # Server SETTINGS: max_frame 4MB, initial window 4MB.
            settings = (struct.pack(">HI", 5, 4 * 1024 * 1024) +
                        struct.pack(">HI", 4, 4 * 1024 * 1024))
            conn.sendall(struct.pack(">I", len(settings))[1:] +
                         bytes([4, 0]) + struct.pack(">I", 0) + settings)
            while not done.is_set():
                hdr = read(9)
                length = int.from_bytes(hdr[:3], "big")
                typ, flags = hdr[3], hdr[4]
                read(length)
                if typ == 4 and flags & 1:
                    order.append(("ack", 0))
                elif typ == 0 and length > 16384:
                    order.append(("big-data", length))
                    done.set()
                elif typ == 0 and length > 0:
                    order.append(("data", length))
                # Enough frames observed either way after the body flows.
                if len(order) > 64:
                    done.set()
        except (EOFError, OSError):
            pass
        finally:
            conn.close()

    t = th.Thread(target=fake_server, daemon=True)
    t.start()
    # 1.2MB body: chunks of min(conn_window 65535, max_frame 4MB) exceed
    # 16384 once the client applies the server's SETTINGS.
    subprocess.run(
        [os.path.join(native_build, "image_client"),
         "-u", f"127.0.0.1:{port}", "-i", "grpc", "-m", "resnet50",
         "-b", "2", "-c", "1"],
        capture_output=True, text=True, timeout=60)
    done.set()
    t.join(timeout=30)
    srv.close()
    big = [i for i, (kind, _) in enumerate(order) if kind == "big-data"]
    acks = [i for i, (kind, _) in enumerate(order) if kind == "ack"]
    # The client must have applied the 4MB max frame (sent a big frame)...
    assert big, order[:8]
    # ...and the ACK must have reached the wire before the first big frame.
    assert acks and acks[0] < big[0], order[:8]


def _h2_frame(typ, flags, sid, payload=b""):
    return (len(payload).to_bytes(3, "big") + bytes([typ, flags]) +
            sid.to_bytes(4, "big") + payload)


@pytest.mark.parametrize("attack", ["rst_stream", "goaway"])
def test_h2_client_survives_server_abort(native_build, attack):
    """A server that kills the RPC (RST_STREAM, RFC 7540 §6.4) or the whole
    connection (GOAWAY, §6.8) mid-request must produce a prompt client-side
    error — not a hang, not a crash.  The reference client inherits this
    from grpc-core (/root/reference/src/c++/library/grpc_client.cc links
    grpc++); here the contract lives in native/src/h2.cc HandleFrame, so it
    gets its own scripted-peer test."""
    import socket
    import threading as th

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def fake_server():
        conn, _ = srv.accept()
        conn.settimeout(30)
        buf = b""

        def read(n):
            nonlocal buf
            while len(buf) < n:
                d = conn.recv(65536)
                if not d:
                    raise EOFError
                buf += d
            out, buf = buf[:n], buf[n:]
            return out

        try:
            read(24)  # client preface
            conn.sendall(_h2_frame(4, 0, 0))  # empty server SETTINGS
            while True:
                hdr = read(9)
                length = int.from_bytes(hdr[:3], "big")
                typ = hdr[3]
                read(length)
                if typ == 1:  # client HEADERS: strike
                    sid = int.from_bytes(hdr[5:9], "big") & 0x7FFFFFFF
                    if attack == "rst_stream":
                        conn.sendall(_h2_frame(
                            3, 0, sid, (8).to_bytes(4, "big")))  # CANCEL
                    else:
                        conn.sendall(_h2_frame(
                            7, 0, 0, (0).to_bytes(4, "big") +
                            (2).to_bytes(4, "big") + b"test-goaway"))
                    # keep draining until the client hangs up
        except (EOFError, OSError):
            pass
        finally:
            conn.close()

    t = th.Thread(target=fake_server, daemon=True)
    t.start()
    proc = subprocess.run(
        [os.path.join(native_build, "simple_grpc_health_metadata"),
         "-u", f"127.0.0.1:{port}"],
        capture_output=True, text=True, timeout=30)
    srv.close()
    t.join(timeout=10)
    assert proc.returncode != 0
    assert "error" in proc.stderr.lower(), proc.stderr


@pytest.mark.parametrize("attack", ["garbage", "truncated_body", "early_close"])
def test_http_client_survives_malformed_responses(native_build, attack):
    """The raw-socket HTTP/1.1 client against a hostile peer: a non-HTTP
    byte stream, a Content-Length promising more than is sent, or a
    connection closed mid-response must each yield a prompt client-side
    error — not a hang or crash.  The reference delegates these to
    libcurl (/root/reference/src/c++/library/http_client.cc); our client
    owns the parsing, so the contract is pinned against a scripted peer."""
    import socket
    import threading as th

    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(2)
    port = srv.getsockname()[1]
    stop = th.Event()

    def fake_server():
        while not stop.is_set():
            try:
                srv.settimeout(20)
                conn, _ = srv.accept()
            except OSError:
                return
            conn.settimeout(20)
            try:
                # read the request head (ignore its content)
                buf = b""
                while b"\r\n\r\n" not in buf:
                    d = conn.recv(65536)
                    if not d:
                        break
                    buf += d
                if attack == "garbage":
                    conn.sendall(b"\x00\xff NOT HTTP AT ALL \r\n\r\n")
                elif attack == "truncated_body":
                    conn.sendall(b"HTTP/1.1 200 OK\r\n"
                                 b"Content-Length: 100000\r\n\r\n"
                                 b"only this much")
                # early_close: say nothing at all
            except OSError:
                pass
            finally:
                conn.close()

    t = th.Thread(target=fake_server, daemon=True)
    t.start()
    # Binary-safe capture: the client's diagnostics are sanitized, but the
    # contract under test must hold even if they were not.
    proc = subprocess.run(
        [os.path.join(native_build, "simple_http_health_metadata"),
         "-u", f"127.0.0.1:{port}"],
        capture_output=True, timeout=30)
    stop.set()
    srv.close()
    t.join(timeout=10)
    stderr = proc.stderr.decode("utf-8", errors="replace")
    assert proc.returncode != 0
    assert "error" in stderr.lower(), stderr
    if attack == "garbage":
        # Sanitization contract: raw control bytes from the wire must not
        # reach the client's error output.
        assert b"\xff" not in proc.stderr and b"\x00" not in proc.stderr
