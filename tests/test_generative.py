"""Generative serving: tiny_gpt through the continuous-batching scheduler.

The defining property under test: iteration-level batching must be
*invisible* — a stream generated while sharing decode waves with other
streams is bit-identical to the same prompt generated alone.
"""

import threading

import numpy as np
import pytest

from client_tpu.engine import EngineError, InferRequest, TpuEngine
from client_tpu.models import build_repository


@pytest.fixture(scope="module")
def engine():
    eng = TpuEngine(build_repository(["tiny_gpt"]))
    yield eng
    eng.shutdown()


def generate_async(engine, prompt, max_tokens, timeout=120, **params):
    """Kick off one stream; returns a join() -> token list callable."""
    tokens: list[int] = []
    err: list = []
    done = threading.Event()

    def cb(resp):
        if resp.error is not None:
            err.append(resp.error)
            done.set()
            return
        if resp.final:
            done.set()
            return
        assert int(resp.outputs["INDEX"][0]) == len(tokens)
        tokens.append(int(resp.outputs["TOKEN"][0]))

    engine.async_infer(
        InferRequest(model_name="tiny_gpt",
                     inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
                     parameters={"max_tokens": max_tokens, **params}),
        cb)

    def join():
        assert done.wait(timeout), "stream did not finish"
        if err:
            raise err[0]
        return tokens

    return join


def generate(engine, prompt, max_tokens, timeout=120, **params):
    """Run one stream to completion; returns the token list."""
    return generate_async(engine, prompt, max_tokens, timeout, **params)()


class TestGenerative:
    def test_scheduler_selected(self, engine):
        from client_tpu.engine.generative import GenerativeScheduler

        assert isinstance(engine._schedulers["tiny_gpt"],
                          GenerativeScheduler)

    def test_stream_shape_and_determinism(self, engine):
        t1 = generate(engine, [1, 2, 3], 8)
        assert len(t1) == 8
        assert all(0 <= t < 512 for t in t1)
        assert generate(engine, [1, 2, 3], 8) == t1

    def test_batch_invariance(self, engine):
        """Streams sharing decode waves == the same streams generated solo."""
        prompts = [[i, i + 1, i + 2, i + 3] for i in range(1, 13)]
        solo = [generate(engine, p, 6) for p in prompts]
        results: list = [None] * len(prompts)
        errs: list = []

        def run(i):
            try:
                results[i] = generate(engine, prompts[i], 6)
            except Exception as exc:  # noqa: BLE001
                errs.append((i, repr(exc)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        assert results == solo

    def test_more_streams_than_slots_all_complete(self):
        from client_tpu.engine.repository import ModelRepository
        from client_tpu.models.generate import TinyGptBackend

        backend = TinyGptBackend(name="tiny_gpt_small", max_streams=4,
                                 n_layers=2, max_seq_len=64)
        repo = ModelRepository()
        repo.register_backend(backend)
        eng = TpuEngine(repo)
        try:
            results: list = [None] * 12
            errs: list = []

            def run(i):
                try:
                    tokens, done = [], threading.Event()

                    def cb(resp):
                        if resp.error is not None:
                            errs.append((i, str(resp.error)))
                            done.set()
                        elif resp.final:
                            done.set()
                        else:
                            tokens.append(int(resp.outputs["TOKEN"][0]))

                    eng.async_infer(InferRequest(
                        model_name="tiny_gpt_small",
                        inputs={"INPUT_IDS": np.asarray([i + 1], np.int32)},
                        parameters={"max_tokens": 5}), cb)
                    assert done.wait(120)
                    results[i] = tokens
                except Exception as exc:  # noqa: BLE001
                    errs.append((i, repr(exc)))

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs[:4]
            assert all(r is not None and len(r) == 5 for r in results)
        finally:
            eng.shutdown()

    def test_prompt_plus_budget_over_max_seq_rejected(self, engine):
        with pytest.raises(EngineError) as ei:
            generate(engine, list(range(120)), 16)
        assert ei.value.status == 400

    def test_bad_token_ids_rejected(self, engine):
        with pytest.raises(EngineError) as ei:
            generate(engine, [1, 99999], 4)
        assert ei.value.status == 400

    def test_zero_max_tokens_rejected(self, engine):
        with pytest.raises(EngineError) as ei:
            generate(engine, [1], 0)
        assert ei.value.status == 400

    def test_sync_infer_rejected(self, engine):
        with pytest.raises(EngineError):
            engine.infer(InferRequest(
                model_name="tiny_gpt",
                inputs={"INPUT_IDS": np.asarray([1], np.int32)}),
                timeout_s=10)

    def test_wave_batching_observable_in_stats(self, engine):
        """Concurrent streams share executions: per-token executions must be
        well under streams x tokens once waves form."""
        s0 = engine.model_statistics("tiny_gpt")["model_stats"][0]
        prompts = [[i] for i in range(1, 17)]
        threads = [threading.Thread(target=generate,
                                    args=(engine, p, 8)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        s1 = engine.model_statistics("tiny_gpt")["model_stats"][0]
        reqs = s1["inference_count"] - s0["inference_count"]
        execs = s1["execution_count"] - s0["execution_count"]
        assert reqs == 16  # one completed request per stream
        # 16 prefills + decode waves; without wave sharing the 16 streams'
        # 7 post-prefill tokens each would need 112 decode executions.
        assert execs - 16 < 60, execs


class TestSampling:
    """Per-request sampling (temperature / top-k / top-p / seed) and stop
    tokens — the r2 VERDICT #3 surface."""

    def test_temp_zero_equals_greedy_default(self, engine):
        base = generate(engine, [5, 6, 7], 8)
        assert generate(engine, [5, 6, 7], 8, temperature=0.0,
                        seed=123) == base
        assert generate(engine, [5, 6, 7], 8, temperature=0.0, top_k=3,
                        top_p=0.5) == base  # cuts are no-ops under greedy

    def test_sampling_deterministic_per_seed(self, engine):
        a = generate(engine, [5, 6, 7], 12, temperature=1.0, seed=42)
        b = generate(engine, [5, 6, 7], 12, temperature=1.0, seed=42)
        assert a == b
        c = generate(engine, [5, 6, 7], 12, temperature=1.0, seed=43)
        assert a != c  # 512-way categorical x12: collision ~ impossible

    def test_sampling_differs_from_greedy_and_varies(self, engine):
        greedy = generate(engine, [9, 9], 16)
        hot = generate(engine, [9, 9], 16, temperature=5.0, seed=7)
        assert hot != greedy
        assert len(set(hot)) > 1  # high temperature explores the vocab

    def test_top_k_one_is_greedy_regardless_of_temperature(self, engine):
        base = generate(engine, [3, 1, 4], 8)
        assert generate(engine, [3, 1, 4], 8, temperature=3.0, top_k=1,
                        seed=99) == base

    def test_batch_invariance_under_sampling(self, engine):
        """The fold_in(seed, position) contract: sampled streams sharing
        decode waves are bit-identical to the same request run solo."""
        prompts = [[i, i + 1] for i in range(1, 9)]
        solo = [generate(engine, p, 6, temperature=1.0, seed=100 + i)
                for i, p in enumerate(prompts)]
        results: list = [None] * len(prompts)
        errs: list = []

        def run(i):
            try:
                results[i] = generate(engine, prompts[i], 6,
                                      temperature=1.0, seed=100 + i)
            except Exception as exc:  # noqa: BLE001
                errs.append((i, repr(exc)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        assert results == solo

    def test_stop_token_terminates_stream(self, engine):
        full = generate(engine, [2, 4, 6], 12)
        stop = full[4]
        got = generate(engine, [2, 4, 6], 12, stop_token_ids=stop)
        # Tokens before the first stop occurrence, stop itself not emitted.
        assert got == full[:full.index(stop)]

    def test_stop_token_csv_and_eos_alias(self, engine):
        full = generate(engine, [2, 4, 6], 12)
        got = generate(engine, [2, 4, 6], 12,
                       stop_token_ids=f"{full[3]},{full[5]}")
        cut = min(full.index(full[3]), full.index(full[5]))
        assert got == full[:cut]
        got2 = generate(engine, [2, 4, 6], 12, eos_id=full[3])
        assert got2 == full[:full.index(full[3])]

    def test_invalid_sampling_params_rejected(self, engine):
        for bad in ({"temperature": -1.0}, {"top_p": 0.0},
                    {"top_p": 1.5}, {"top_k": -2},
                    {"temperature": "hot"},
                    {"stop_token_ids": "1,x"},
                    {"stop_token_ids": 99999}):
            with pytest.raises(EngineError) as ei:
                generate(engine, [1], 4, **bad)
            assert ei.value.status == 400, bad


class TestBatchedPrefill:
    def test_burst_admits_share_prefill_executions(self):
        """A burst of N admits with same-bucket prompts must cost far fewer
        prefill executions than N (r2: one prefill round trip per admit
        stalled every live stream's decode)."""
        eng = TpuEngine(build_repository(["tiny_gpt"]))
        try:
            generate(eng, [1, 2], 2)  # warm compile paths
            s0 = eng.model_statistics("tiny_gpt")["model_stats"][0]
            n = 16
            barrier = threading.Barrier(n)
            errs: list = []

            def run(i):
                try:
                    barrier.wait(30)
                    generate(eng, [i + 1, i + 2], 4)
                except Exception as exc:  # noqa: BLE001
                    errs.append(repr(exc))

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs[:3]
            s1 = eng.model_statistics("tiny_gpt")["model_stats"][0]
            execs = s1["execution_count"] - s0["execution_count"]
            # 16 admits in admit-bucket-8 chunks -> <= ~4 prefill
            # executions (+1 per decode wave, ~4 waves): far under the 16
            # prefills + 16*3 decodes the per-admit path would need.
            assert execs <= 14, execs
        finally:
            eng.shutdown()


class TestSlowConsumer:
    def test_backlogged_stream_is_cancelled_and_bounded(self, engine):
        """A reader that stops draining must not grow the response queue
        unboundedly (r2 VERDICT weak #6).  Round-5 semantics: past
        STREAM_PENDING_LIMIT the decode waves PAUSE for this stream
        (transport flow control, bounded queue), and a stream throttled
        continuously past BACKPRESSURE_TIMEOUT_S has its arena slot
        reclaimed with a cancel.  Drives the real servicer generator with
        a fake context — no sockets, so the backlog is fully
        controlled."""
        import time as _time

        from client_tpu.engine.generative import GenerativeScheduler
        from client_tpu.protocol import grpc_codec
        from client_tpu.protocol import grpc_service_pb2 as pb
        from client_tpu.server.grpc_server import _Servicer

        class FakeContext:
            def add_callback(self, cb):
                return True

            def is_active(self):
                return True

        servicer = _Servicer(engine)
        servicer.STREAM_PENDING_LIMIT = 8
        # Tiny timeout so the slot-reclaim path runs in test time (the
        # scheduler reads the class attribute at check time).
        saved_timeout = GenerativeScheduler.BACKPRESSURE_TIMEOUT_S
        GenerativeScheduler.BACKPRESSURE_TIMEOUT_S = 0.3

        req = pb.ModelInferRequest(model_name="tiny_gpt")
        t = req.inputs.add()
        t.name, t.datatype = "INPUT_IDS", "INT32"
        t.shape.extend([2])
        t.contents.int_contents.extend([1, 2])
        grpc_codec.set_param(req.parameters, "max_tokens", 120)

        try:
            stream = servicer.ModelStreamInfer(iter([req]), FakeContext())
            first = next(stream)  # starts the pump; then stop consuming
            assert not first.error_message
            deadline = _time.monotonic() + 60
            # Wait until the engine retires the stream (slot reclaimed).
            while _time.monotonic() < deadline:
                if not engine._schedulers["tiny_gpt"]._streams:
                    break
                _time.sleep(0.05)
            msgs = list(stream)  # drain what was produced
            # Bounded: far fewer than the 120 requested tokens (decode
            # paused at the mark + one wave's overshoot, then the slot was
            # reclaimed); and the cancel surfaced as a stream error.
            assert len(msgs) < 100, len(msgs)
            assert any(m.error_message for m in msgs), \
                [m.error_message for m in msgs[-3:]]
        finally:
            GenerativeScheduler.BACKPRESSURE_TIMEOUT_S = saved_timeout


class TestGenerativeGrpcStream:
    def test_tokens_stream_over_grpc(self):
        import client_tpu.grpc as grpcclient
        from client_tpu.server import GrpcInferenceServer

        eng = TpuEngine(build_repository(["tiny_gpt"]))
        srv = GrpcInferenceServer(eng, port=0).start()
        try:
            expected = generate(eng, [7, 8, 9], 6)

            c = grpcclient.InferenceServerClient(f"127.0.0.1:{srv.port}")
            tokens = []
            done = threading.Event()

            def cb(result, error):
                assert error is None, error
                params = result.get_response().parameters
                final = ("triton_final_response" in params
                         and params["triton_final_response"].bool_param)
                if result.get_response().outputs:
                    tokens.append(int(result.as_numpy("TOKEN")[0]))
                if final:
                    done.set()

            c.start_stream(cb)
            inp = grpcclient.InferInput("INPUT_IDS", [3], "INT32")
            inp.set_data_from_numpy(np.array([7, 8, 9], dtype=np.int32))
            c.async_stream_infer("tiny_gpt", [inp], request_id="g1",
                                 parameters={"max_tokens": 6})
            assert done.wait(timeout=120)
            c.stop_stream()
            c.close()
            assert tokens == expected
        finally:
            srv.stop()
            eng.shutdown()

    def test_coalesced_stream_identical_tokens(self, monkeypatch):
        """`response_coalesce` lets the writer merge backlogged tokens into
        [k]-shaped messages; the delivered token sequence (flattened, with
        INDEX continuity) must be identical to the uncoalesced stream, with
        final still terminating the request.  The writer-delay knob forces
        a backlog so the multi-response merge path actually runs (without
        it a fast reader drains token-by-token and the merge is never
        exercised)."""
        import client_tpu.grpc as grpcclient
        from client_tpu.server import GrpcInferenceServer

        monkeypatch.setenv("CLIENT_TPU_STREAM_WRITER_DELAY_MS", "40")
        eng = TpuEngine(build_repository(["tiny_gpt"]))
        srv = GrpcInferenceServer(eng, port=0).start()
        try:
            n_tok = 24
            expected = generate(eng, [7, 8, 9], n_tok)

            c = grpcclient.InferenceServerClient(f"127.0.0.1:{srv.port}")
            tokens: list[int] = []
            indices: list[int] = []
            shapes: list[int] = []
            done = threading.Event()

            def cb(result, error):
                assert error is None, error
                params = result.get_response().parameters
                final = ("triton_final_response" in params
                         and params["triton_final_response"].bool_param)
                if result.get_response().outputs:
                    toks = result.as_numpy("TOKEN")
                    idx = result.as_numpy("INDEX")
                    assert len(toks) == len(idx)  # rows stay aligned
                    shapes.append(len(toks))
                    tokens.extend(int(t) for t in toks)
                    indices.extend(int(i) for i in idx)
                if final:
                    done.set()

            c.start_stream(cb)
            inp = grpcclient.InferInput("INPUT_IDS", [3], "INT32")
            inp.set_data_from_numpy(np.array([7, 8, 9], dtype=np.int32))
            c.async_stream_infer(
                "tiny_gpt", [inp], request_id="gc1",
                parameters={"max_tokens": n_tok, "response_coalesce": True})
            assert done.wait(timeout=120)
            c.stop_stream()
            c.close()
            assert tokens == expected
            assert indices == list(range(n_tok))
            # the throttled writer must actually have merged: fewer
            # messages than tokens, at least one multi-token message
            assert max(shapes) > 1
            assert len(shapes) < n_tok
        finally:
            srv.stop()
            eng.shutdown()


class TestCancellation:
    def test_cancel_mid_generation_frees_the_slot(self):
        """Cancelling a stream stops decoding at the next wave, fails the
        request with 499, and returns its arena row to the free list."""
        from client_tpu.engine.repository import ModelRepository
        from client_tpu.models.generate import TinyGptBackend

        backend = TinyGptBackend(name="gpt_cancel", max_streams=2,
                                 n_layers=2, max_seq_len=64)
        repo = ModelRepository()
        repo.register_backend(backend)
        eng = TpuEngine(repo)
        try:
            got = []
            status = []
            done = threading.Event()
            req = InferRequest(
                model_name="gpt_cancel",
                inputs={"INPUT_IDS": np.asarray([1, 2], np.int32)},
                parameters={"max_tokens": 40})

            def cb(resp):
                if resp.error is not None:
                    status.append(resp.error.status)
                    done.set()
                elif resp.final:
                    status.append(200)
                    done.set()
                else:
                    got.append(int(resp.outputs["TOKEN"][0]))
                    if len(got) == 3:
                        req.cancel()

            eng.async_infer(req, cb)
            assert done.wait(120)
            assert status == [499]
            assert len(got) < 40  # stopped early
            # The slot is free again: two fresh streams fit (capacity 2).
            sched = eng._schedulers["gpt_cancel"]
            deadline = threading.Event()
            for _ in range(50):
                if len(sched._free) == 2:
                    break
                deadline.wait(0.05)
            assert len(sched._free) == 2
            # ...and the scheduler still serves fresh streams.
            after, fin = [], threading.Event()

            def cb2(resp):
                if resp.final or resp.error is not None:
                    fin.set()
                else:
                    after.append(int(resp.outputs["TOKEN"][0]))

            eng.async_infer(InferRequest(
                model_name="gpt_cancel",
                inputs={"INPUT_IDS": np.asarray([5], np.int32)},
                parameters={"max_tokens": 4}), cb2)
            assert fin.wait(120)
            assert len(after) == 4
        finally:
            eng.shutdown()

    def test_queued_cancelled_request_never_admits(self, engine):
        req = InferRequest(
            model_name="tiny_gpt",
            inputs={"INPUT_IDS": np.asarray([1], np.int32)},
            parameters={"max_tokens": 4})
        req.cancel()
        status = []
        done = threading.Event()

        def cb(resp):
            if resp.error is not None:
                status.append(resp.error.status)
            done.set()

        engine.async_infer(req, cb)
        assert done.wait(60)
        assert status == [499]

    def test_stream_close_cancels_generation_serverside(self):
        """Closing the gRPC stream mid-generation frees the server's
        arena slot (the scheduler stops decoding for the dead client)."""
        import client_tpu.grpc as grpcclient
        from client_tpu.engine.repository import ModelRepository
        from client_tpu.models.generate import TinyGptBackend
        from client_tpu.server import GrpcInferenceServer

        backend = TinyGptBackend(name="gpt_c2", max_streams=2,
                                 n_layers=2, max_seq_len=64)
        repo = ModelRepository()
        repo.register_backend(backend)
        eng = TpuEngine(repo)
        srv = GrpcInferenceServer(eng, port=0).start()
        try:
            c = grpcclient.InferenceServerClient(f"127.0.0.1:{srv.port}")
            got_one = threading.Event()

            def cb(result, error):
                if error is None and result.get_response().outputs:
                    got_one.set()

            c.start_stream(cb)
            inp = grpcclient.InferInput("INPUT_IDS", [2], "INT32")
            inp.set_data_from_numpy(np.array([1, 2], dtype=np.int32))
            c.async_stream_infer("gpt_c2", [inp],
                                 parameters={"max_tokens": 50})
            assert got_one.wait(60)
            c.stop_stream(cancel_requests=True)
            c.close()
            # The server notices the dead stream at the next wave and
            # returns the arena row.
            sched = eng._schedulers["gpt_c2"]
            for _ in range(100):
                if len(sched._free) == 2:
                    break
                threading.Event().wait(0.05)
            assert len(sched._free) == 2
        finally:
            srv.stop()
            eng.shutdown()


class TestSeedAndFiniteness:
    """Advisor r3: unseeded temperature sampling must not be one fixed
    'random' sequence for every request, and non-finite float parameters
    must be rejected (NaN passes every range comparison)."""

    def test_unseeded_sampling_varies_across_requests(self, engine):
        runs = [generate(engine, [5, 6, 7], 12, temperature=1.5)
                for _ in range(4)]
        assert any(r != runs[0] for r in runs[1:]), \
            f"unseeded sampling fully deterministic: {runs[0]}"

    def test_explicit_seed_still_deterministic(self, engine):
        a = generate(engine, [5, 6, 7], 12, temperature=1.5, seed=42)
        b = generate(engine, [5, 6, 7], 12, temperature=1.5, seed=42)
        assert a == b

    def test_unseeded_greedy_still_deterministic(self, engine):
        assert generate(engine, [8, 9], 8) == generate(engine, [8, 9], 8)

    def test_non_finite_float_params_rejected(self, engine):
        for bad in ({"temperature": float("nan")},
                    {"temperature": float("inf")},
                    {"top_p": float("nan")}):
            with pytest.raises(EngineError) as ei:
                generate(engine, [1], 4, **bad)
            assert ei.value.status == 400, bad
            assert "finite" in str(ei.value)

    def test_infinite_int_params_rejected(self, engine):
        # json.loads accepts Infinity; int(float('inf')) raises
        # OverflowError, which must surface as a 400, not a 500.
        for bad in ({"top_k": float("inf")}, {"seed": float("inf")}):
            with pytest.raises(EngineError) as ei:
                generate(engine, [1], 4, **bad)
            assert ei.value.status == 400, bad
        err: list = []
        done = threading.Event()

        def cb(resp):
            if resp.error is not None:
                err.append(resp.error)
            if resp.final or resp.error is not None:
                done.set()

        engine.async_infer(InferRequest(
            model_name="tiny_gpt",
            inputs={"INPUT_IDS": np.asarray([1], np.int32)},
            parameters={"max_tokens": float("inf")}), cb)
        assert done.wait(60)
        assert err and getattr(err[0], "status", None) == 400


class TestPipelinedDispatch:
    """Round-4 pipelining invariants: admits must interleave with decode
    (no pipeline drain to admit), and the dispatch-ahead bound holds."""

    def test_admits_dispatch_while_fetches_outstanding(self):
        """A burst of admits landing mid-generation must be dispatched
        while decode fetches are still in flight — the round-3 scheduler
        synchronously drained every admit chunk before the next wave,
        stalling every live stream for the whole burst."""
        from client_tpu.engine.generative import GenerativeScheduler

        eng = TpuEngine(build_repository(["tiny_gpt"]))
        try:
            sched = eng._schedulers["tiny_gpt"]
            assert isinstance(sched, GenerativeScheduler)
            inflight_at_prefill: list[int] = []
            orig = GenerativeScheduler._prefill_chunk

            def spy(self, bucket, chunk):
                inflight_at_prefill.append(len(self._inflight))
                return orig(self, bucket, chunk)

            sched._prefill_chunk = spy.__get__(sched)
            long_tokens = generate_async(eng, [7, 7, 7], 48)
            _time_wait_some(eng)
            burst = [generate_async(eng, [i + 1, i + 2], 6)
                     for i in range(16)]
            long_result = long_tokens()
            burst_results = [b() for b in burst]
            solo = generate(eng, [7, 7, 7], 48)
            assert long_result == solo, \
                "admit burst perturbed the live stream"
            assert all(len(b) == 6 for b in burst_results)
            assert len(inflight_at_prefill) >= 2
            assert any(n > 0 for n in inflight_at_prefill[1:]), \
                ("every admit saw an empty pipeline — admits are draining "
                 f"the inflight queue: {inflight_at_prefill}")
        finally:
            eng.shutdown()

    def test_pipeline_depth_bounds_inflight(self):
        eng = TpuEngine(build_repository(["tiny_gpt"]))
        try:
            sched = eng._schedulers["tiny_gpt"]
            sched._depth = 3
            max_seen: list[int] = []
            orig = type(sched)._dispatch_wave

            def spy(self, live):
                max_seen.append(len(self._inflight))
                return orig(self, live)

            sched._dispatch_wave = spy.__get__(sched)
            toks = generate(eng, [3, 4, 5], 40)
            assert len(toks) == 40
            # depth bounds dispatch-ahead: at each wave dispatch, at most
            # depth + 1 fetches can be outstanding (the drain runs after
            # dispatch, consuming down to depth).
            assert max_seen and max(max_seen) <= 3 + 1, max_seen
        finally:
            eng.shutdown()


def _time_wait_some(engine):
    import time as _t

    _t.sleep(0.05)  # let a few waves dispatch before the burst lands


class TestPerRequestShedding:
    """r3 VERDICT weak #6: a backlogged stream RPC must shed the request
    producing the backlog, not every live request on the RPC."""

    def test_fast_stream_survives_slow_sibling_shedding(self):
        """One RPC, two decoupled requests: a hog flooding responses and a
        well-behaved sibling trickling them, against a consumer stalled
        longer than the backpressure timeout. Flow control paces the hog
        first; once its emit wait expires and it floods a still-stalled
        consumer, the choke cancels the HOG only — the sibling survives
        and runs to completion."""
        import time as _time

        from client_tpu.engine.repository import ModelRepository
        from client_tpu.engine.scheduler import DecoupledScheduler
        from client_tpu.models.simple import RepeatBackend
        from client_tpu.protocol import grpc_service_pb2 as pb
        from client_tpu.server.grpc_server import _Servicer

        backend = RepeatBackend()
        backend.config.instance_count = 2  # hog and sibling stream together
        repo = ModelRepository()
        repo.register_backend(backend)
        eng = TpuEngine(repo)
        saved_timeout = DecoupledScheduler.BACKPRESSURE_TIMEOUT_S
        # The consumer below stalls 2 s; the emit wait must expire inside
        # that stall for the flood (and thus the shed) to happen.
        DecoupledScheduler.BACKPRESSURE_TIMEOUT_S = 0.3
        try:
            servicer = _Servicer(eng, stream_pending_limit=16)

            class FakeContext:
                def add_callback(self, cb):
                    return True

                def is_active(self):
                    return True

            def repeat_req(rid, values, delay_us):
                req = pb.ModelInferRequest(model_name="simple_repeat",
                                           id=rid)
                t = req.inputs.add()
                t.name, t.datatype = "IN", "INT32"
                t.shape.extend([len(values)])
                t.contents.int_contents.extend(values)
                d = req.inputs.add()
                d.name, d.datatype = "DELAY", "UINT32"
                d.shape.extend([len(values)])
                d.contents.uint_contents.extend([delay_us] * len(values))
                return req

            hog = repeat_req("hog", list(range(500)), 1000)      # ~1ms/resp
            meek = repeat_req("meek", list(range(10)), 30_000)   # 30ms/resp
            stream = servicer.ModelStreamInfer(
                iter([hog, meek]), FakeContext())
            first = next(stream)  # starts the pump; then stop consuming
            _time.sleep(2.0)      # hog floods past the mark; meek trickles
            msgs = [first] + list(stream)
            by_id: dict = {"hog": [], "meek": []}
            errors = []
            for m in msgs:
                if m.error_message:
                    errors.append(m.error_message)
                    continue
                by_id.setdefault(m.infer_response.id, []).append(m)
            # The meek stream delivered everything: 10 responses + final.
            assert len(by_id["meek"]) == 11, len(by_id["meek"])
            # The hog was shed well before its 500 responses...
            assert len(by_id["hog"]) < 300, len(by_id["hog"])
            # ...and the cancellation surfaced as a stream error.
            assert any("cancel" in e for e in errors), errors
        finally:
            DecoupledScheduler.BACKPRESSURE_TIMEOUT_S = saved_timeout
            eng.shutdown()

    def test_stalled_stream_pauses_without_blocking_sibling_decode(self):
        """Round-5 flow control, the generative arena case: a stalled
        consumer's stream is PAUSED (skipped at wave formation), not
        shed — and a sibling stream on the same model keeps decoding at
        full speed.  Two separate stream RPCs on one engine: A stalls
        after the first message; B drains fully.  B must complete all
        its tokens with no error; A must still be live (throttled, not
        cancelled) afterwards."""
        import time as _time

        from client_tpu.protocol import grpc_codec
        from client_tpu.protocol import grpc_service_pb2 as pb
        from client_tpu.server.grpc_server import _Servicer

        eng = TpuEngine(build_repository(["tiny_gpt"]))
        try:
            servicer = _Servicer(eng, stream_pending_limit=8)

            class FakeContext:
                def add_callback(self, cb):
                    return True

                def is_active(self):
                    return True

            def gen_req(rid, prompt, n):
                req = pb.ModelInferRequest(model_name="tiny_gpt", id=rid)
                t = req.inputs.add()
                t.name, t.datatype = "INPUT_IDS", "INT32"
                t.shape.extend([len(prompt)])
                t.contents.int_contents.extend(prompt)
                grpc_codec.set_param(req.parameters, "max_tokens", n)
                return req

            stream_a = servicer.ModelStreamInfer(
                iter([gen_req("a", [1, 2], 60)]), FakeContext())
            first = next(stream_a)  # starts A's pump; then stall
            assert not first.error_message
            _time.sleep(0.3)  # A floods to its mark and gets throttled

            stream_b = servicer.ModelStreamInfer(
                iter([gen_req("b", [3, 4], 12)]), FakeContext())
            msgs_b = list(stream_b)  # actively draining sibling
            errors_b = [m.error_message for m in msgs_b
                        if m.error_message]
            assert not errors_b, errors_b
            tokens_b = sum(
                1 for m in msgs_b
                if not m.error_message and m.infer_response.outputs)
            assert tokens_b == 12, tokens_b

            # A is parked, not shed: its stream still holds an arena row
            # (the reclaim timeout is 60s, far beyond this test).
            sched = eng._schedulers["tiny_gpt"]
            assert any(s.req.request_id == "a" for s in sched._streams), \
                "stalled stream was dropped instead of paused"
        finally:
            eng.shutdown()

    def test_burst_with_draining_reader_not_shed(self):
        """Round-5 regression (gen_net warmup failure on TPU): a producer
        that BURSTS past the soft mark while the consumer is actively
        draining must NOT be shed.  The real incident: 64 generative
        warmup streams x chunked decode waves crossed the 1024 mark in one
        burst and a well-behaved request was cancelled mid-warmup.  The
        soft mark is now progress-gated — it sheds only when the
        writer/consumer makes no progress for the grace window."""
        from client_tpu.engine.repository import ModelRepository
        from client_tpu.models.simple import RepeatBackend
        from client_tpu.protocol import grpc_service_pb2 as pb
        from client_tpu.server.grpc_server import _Servicer

        backend = RepeatBackend()
        repo = ModelRepository()
        repo.register_backend(backend)
        eng = TpuEngine(repo)
        try:
            # Tiny mark: the 300-response flood crosses it hundreds of
            # times over; only the progress gate keeps the request alive.
            servicer = _Servicer(eng, stream_pending_limit=8)

            class FakeContext:
                def add_callback(self, cb):
                    return True

                def is_active(self):
                    return True

            req = pb.ModelInferRequest(model_name="simple_repeat",
                                       id="burst")
            t = req.inputs.add()
            t.name, t.datatype = "IN", "INT32"
            t.shape.extend([300])
            t.contents.int_contents.extend(range(300))
            d = req.inputs.add()
            d.name, d.datatype = "DELAY", "UINT32"
            d.shape.extend([300])
            d.contents.uint_contents.extend([0] * 300)  # flood, no delay

            stream = servicer.ModelStreamInfer(iter([req]), FakeContext())
            msgs = list(stream)  # actively draining consumer
            errors = [m.error_message for m in msgs if m.error_message]
            assert not errors, errors
            # All 300 responses + the final marker arrived.
            assert len(msgs) == 301, len(msgs)
        finally:
            eng.shutdown()


class TestChunkedDecode:
    """CLIENT_TPU_GEN_CHUNK > 1 fuses K decode waves into one scanned
    dispatch; it must be invisible — token streams identical to per-wave
    decode, under greedy, sampling, and mid-chunk stop tokens."""

    @pytest.fixture()
    def chunk_engine(self, monkeypatch):
        monkeypatch.setenv("CLIENT_TPU_GEN_CHUNK", "4")
        eng = TpuEngine(build_repository(["tiny_gpt"]))
        yield eng
        eng.shutdown()

    def test_greedy_identical(self, engine, chunk_engine):
        # n=13: prefill + exactly three 4-chunks; n=4: remaining budget
        # < K so the scheduler falls back to single waves; n=32: long run
        for prompt, n in (([7, 8, 9], 13), ([1], 4), ([2, 3], 32)):
            assert generate(chunk_engine, prompt, n) == \
                generate(engine, prompt, n)

    def test_sampling_identical(self, engine, chunk_engine):
        kw = {"temperature": 0.9, "seed": 1234, "top_k": 24, "top_p": 0.9}
        want = generate(engine, [5, 9], 17, **kw)
        got = generate(chunk_engine, [5, 9], 17, **kw)
        assert got == want

    def test_stop_token_mid_chunk(self, engine, chunk_engine):
        free = generate(engine, [11, 12], 16)
        stop = free[5]  # lands inside a 4-chunk, not on its boundary
        want = generate(engine, [11, 12], 16, stop_token_ids=stop)
        got = generate(chunk_engine, [11, 12], 16, stop_token_ids=stop)
        assert got == want
        assert len(got) <= 16

    def test_batch_invariance_chunked(self, chunk_engine):
        prompts = [[3 + i, 50 + i] for i in range(8)]
        solo = [generate(chunk_engine, p, 12) for p in prompts]
        joins = [generate_async(chunk_engine, p, 12) for p in prompts]
        assert [j() for j in joins] == solo


class TestFlashPrefill:
    """Long-context generation path (`tiny_gpt_long` family): flash
    (Pallas, causal) prefill must agree with the dense einsum prefill —
    same model, same weights, only the attention kernel differs."""

    def _engine(self, impl, max_seq=256):
        from client_tpu.engine.repository import ModelRepository
        from client_tpu.models.generate import TinyGptBackend

        b = TinyGptBackend(name="gl", n_layers=2, d_model=64, n_heads=4,
                           d_ff=128, vocab=256, max_seq_len=max_seq,
                           max_streams=4, attention_impl=impl)
        # Shrunk tiles: the 100-token prompt (bucket 128) then runs a 4x4
        # flash grid, exercising the same multi-block configuration the
        # production 2048/512/1024 family compiles — not the single-block
        # degenerate case.
        b.flash_blocks = (32, 32)
        repo = ModelRepository()
        repo.register_backend(b)
        return TpuEngine(repo)

    def _gen(self, eng, prompt, n):
        toks: list[int] = []
        errs: list = []
        done = threading.Event()

        def cb(resp):
            if resp.error is not None:
                errs.append(resp.error)
                done.set()
            elif resp.final:
                done.set()
            else:
                toks.append(int(resp.outputs["TOKEN"][0]))

        eng.async_infer(InferRequest(
            model_name="gl",
            inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
            parameters={"max_tokens": n}), cb)
        assert done.wait(240), "stream stalled"
        assert not errs, errs
        return toks

    def test_flash_matches_dense_prefill(self):
        # 100-token prompt -> bucket 128 -> 4x4 grid at the shrunk 32/32
        # tiles (see _engine): a real multi-block flash prefill
        prompt = list(np.arange(100) % 256)
        dense_eng = self._engine("einsum")
        try:
            want = self._gen(dense_eng, prompt, 8)
        finally:
            dense_eng.shutdown()
        flash_eng = self._engine("flash")
        try:
            got = self._gen(flash_eng, prompt, 8)
        finally:
            flash_eng.shutdown()
        assert got == want

    def test_long_family_registered(self):
        from client_tpu.models import _REGISTRY, _import_all

        _import_all()
        b = _REGISTRY["tiny_gpt_long"]()
        assert b.max_seq_len == 2048
        assert b.attention_impl == "flash"


class TestFusedDecode:
    """The served decode step (attn_impl='fused', ops/decode_kernel.py)
    and the row-sharded arena (parallel/kv_shard.py) must be invisible:
    token streams bit-identical to the oracle step (attn_impl='reference'),
    greedy and sampled, solo and batched, chunked and not."""

    KW = dict(n_layers=2, d_model=64, n_heads=2, d_ff=128, vocab=128,
              max_seq_len=32, max_streams=4)

    def _engine(self, **overrides):
        from client_tpu.engine.repository import ModelRepository
        from client_tpu.models.generate import TinyGptBackend

        repo = ModelRepository()
        repo.register_backend(TinyGptBackend(name="tg",
                                             **{**self.KW, **overrides}))
        return TpuEngine(repo)

    def _gen(self, eng, prompt, n, **params):
        toks: list[int] = []
        errs: list = []
        done = threading.Event()

        def cb(resp):
            if resp.error is not None:
                errs.append(resp.error)
                done.set()
            elif resp.final:
                done.set()
            else:
                toks.append(int(resp.outputs["TOKEN"][0]))

        eng.async_infer(InferRequest(
            model_name="tg",
            inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
            parameters={"max_tokens": n, **params}), cb)
        assert done.wait(240), "stream stalled"
        assert not errs, errs
        return toks

    def _stream_suite(self, eng):
        """Greedy + sampled streams across prompt lengths; returns the
        token lists so impls can be compared token for token."""
        out = [self._gen(eng, p, 6) for p in ([1, 2, 3], [7] * 9, [5])]
        out.append(self._gen(eng, [4, 4], 8, temperature=1.0, seed=42))
        out.append(self._gen(eng, [4, 4], 8, temperature=0.8, seed=7,
                             top_k=24, top_p=0.9))
        return out

    def _oracle_suite(self, **kw):
        ref_eng = self._engine(attn_impl="reference", **kw)
        try:
            return self._stream_suite(ref_eng)
        finally:
            ref_eng.shutdown()

    @pytest.mark.parametrize("kv_shards", [1, 2, 4])
    def test_kernel_matches_oracle_token_for_token(self, kv_shards):
        """One chip, and the arena's rows over 2 and 4 mesh shards."""
        want = self._oracle_suite()
        eng = self._engine(attn_impl="fused", kv_shards=kv_shards)
        try:
            sched = eng._schedulers["tg"]
            assert sched.arena_shards() == kv_shards
            if kv_shards > 1:
                assert sched.model.backend._mesh().shape["kv"] == kv_shards
            assert self._stream_suite(eng) == want
        finally:
            eng.shutdown()

    def test_kernel_matches_oracle_at_gpt2_head_geometry(self):
        """12 heads x 64 on a 768-lane arena row, through the engine."""
        kw = dict(d_model=768, n_heads=12, d_ff=256)
        want = self._oracle_suite(**kw)
        eng = self._engine(attn_impl="fused", **kw)
        try:
            arena = eng._schedulers["tg"]._arena
            assert arena["k"].shape == (2, 5, 32, 768)
            assert self._stream_suite(eng) == want
        finally:
            eng.shutdown()

    @pytest.mark.parametrize("kv_shards", [1, 2, 4])
    def test_batched_streams_match_solo(self, kv_shards):
        eng = self._engine(attn_impl="fused", kv_shards=kv_shards)
        try:
            prompts = [[i + 1, i + 2] for i in range(6)]
            solo = [self._gen(eng, p, 6) for p in prompts]
            results: list = [None] * len(prompts)
            errs: list = []

            def run(i):
                try:
                    results[i] = self._gen(eng, prompts[i], 6)
                except Exception as exc:  # noqa: BLE001
                    errs.append((i, repr(exc)))

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs
            assert results == solo
        finally:
            eng.shutdown()

    @pytest.mark.parametrize("kv_shards", [1, 2])
    def test_chunked_decode_identical_to_unchunked(self, monkeypatch,
                                                   kv_shards):
        want_eng = self._engine(attn_impl="fused", kv_shards=kv_shards)
        try:
            want = self._gen(want_eng, [7, 8], 13)
        finally:
            want_eng.shutdown()
        monkeypatch.setenv("CLIENT_TPU_GEN_CHUNK", "4")
        chunk_eng = self._engine(attn_impl="fused", kv_shards=kv_shards)
        try:
            assert self._gen(chunk_eng, [7, 8], 13) == want
        finally:
            chunk_eng.shutdown()

    def test_the_constructor_selects_impl(self, monkeypatch):
        """``attn_impl=`` selects, a sharded arena is the kernel's, and the
        environment is not read."""
        from client_tpu.models.generate import TinyGptBackend

        monkeypatch.setenv("CLIENT_TPU_" + "ATTN_IMPL", "reference")
        assert TinyGptBackend(name="e1", **self.KW).attn_impl == ""
        assert TinyGptBackend(name="e2", attn_impl="reference",
                              **self.KW).attn_impl == "reference"
        assert TinyGptBackend(name="e3", attn_impl="fused",
                              **self.KW).attn_impl == "fused"
        # A sharded arena is the kernel's whatever the platform.
        assert TinyGptBackend(name="e4", kv_shards=2,
                              **self.KW).attn_impl == "fused"

    @pytest.mark.parametrize("interpreted", [False, True])
    def test_unset_the_platform_decides(self, monkeypatch, interpreted):
        """No setting: the kernel wherever Mosaic compiles it, the XLA step
        where Pallas would only be interpreted (this suite's CPU)."""
        import jax
        import jax.numpy as jnp

        from client_tpu.engine import backend_init
        from client_tpu.models.generate import TinyGptBackend

        monkeypatch.setattr(backend_init, "pallas_interpret",
                            lambda: interpreted)
        backend = TinyGptBackend(name="p", **self.KW)
        params = jax.eval_shape(lambda: jax.tree_util.tree_map(
            jnp.asarray, backend._init_params()))
        arena = jax.eval_shape(lambda: backend.init_arena(8))
        lanes_i = jax.ShapeDtypeStruct((2,), jnp.int32)
        lanes_f = jax.ShapeDtypeStruct((2,), jnp.float32)
        text = str(jax.make_jaxpr(
            backend.decode_fn(),
            static_argnums=backend.decode_static_argnums)(
                params, arena, lanes_i, lanes_i, lanes_i, lanes_f, lanes_i,
                lanes_f, False))
        assert ("pallas_call" in text) == (not interpreted)

    def test_invalid_configs_rejected(self):
        from client_tpu.models.generate import TinyGptBackend

        with pytest.raises(ValueError, match="attn_impl"):
            TinyGptBackend(name="bad1", attn_impl="nope", **self.KW)
        with pytest.raises(ValueError, match="fused"):
            TinyGptBackend(name="bad2", attn_impl="reference",
                           kv_shards=2, **self.KW)
        with pytest.raises(ValueError, match="divisible"):
            TinyGptBackend(name="bad3", attn_impl="fused", kv_shards=3,
                           **self.KW)

    def test_wave_stats_recorded(self):
        from client_tpu.observability.profiler import profiler, \
            reset_profiler

        reset_profiler()
        eng = self._engine(attn_impl="fused")
        try:
            self._gen(eng, [1, 2], 6)
            snap = profiler().snapshot(model="tg")
            entry = snap["models"].get("tg:1") or {}
            waves = entry.get("decode_waves") or []
            assert waves, snap["models"].keys()
            w = waves[0]
            assert w["bucket"] >= 1 and w["waves"] >= 1
            assert w["wave_ms_p50"] >= 0
        finally:
            eng.shutdown()
            reset_profiler()


class TestWaveBucketOverflow:
    def test_live_set_larger_than_max_bucket_splits(self):
        """Regression: a live set larger than the largest wave bucket used
        to raise StopIteration inside the bucket pick (killing the decode
        loop); it must clamp to the max bucket and split the wave."""
        from client_tpu.engine.repository import ModelRepository
        from client_tpu.models.generate import TinyGptBackend

        backend = TinyGptBackend(name="tg_of", n_layers=2, d_model=64,
                                 n_heads=2, d_ff=128, vocab=128,
                                 max_seq_len=32, max_streams=8)
        repo = ModelRepository()
        repo.register_backend(backend)
        eng = TpuEngine(repo)
        try:
            sched = eng._schedulers["tg_of"]
            solo: list = []
            for i in range(5):
                toks, done = [], threading.Event()

                def cb(resp, toks=toks, done=done):
                    if resp.error is not None or resp.final:
                        done.set()
                    else:
                        toks.append(int(resp.outputs["TOKEN"][0]))

                eng.async_infer(InferRequest(
                    model_name="tg_of",
                    inputs={"INPUT_IDS": np.asarray([i + 1], np.int32)},
                    parameters={"max_tokens": 5}), cb)
                assert done.wait(120)
                solo.append(toks)
            # Force the overflow: largest wave bucket (2) < live set (5).
            sched._wave_buckets = [1, 2]
            results: list = [None] * 5
            errs: list = []

            def run(i):
                try:
                    toks, done = [], threading.Event()

                    def cb(resp):
                        if resp.error is not None:
                            errs.append((i, str(resp.error)))
                            done.set()
                        elif resp.final:
                            done.set()
                        else:
                            toks.append(int(resp.outputs["TOKEN"][0]))

                    eng.async_infer(InferRequest(
                        model_name="tg_of",
                        inputs={"INPUT_IDS": np.asarray([i + 1], np.int32)},
                        parameters={"max_tokens": 5}), cb)
                    assert done.wait(120), "stream stalled"
                    results[i] = toks
                except Exception as exc:  # noqa: BLE001
                    errs.append((i, repr(exc)))

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(5)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs[:3]
            # Split waves are still batch-invariant.
            assert results == solo
        finally:
            eng.shutdown()


def _staged_prefill(backend):
    """The prefill formulation this path had until PR 29, kept as the new
    one's oracle: one lane at a time under ``vmap`` through ``[.., H, D]``
    einsum attention, every layer's K and V stacked over layers, transposed
    and scattered into the arena at the end.  Greedy."""
    import math

    import jax
    import jax.numpy as jnp

    from client_tpu.models.generate import _ln

    h_, d_ = backend.n_heads, backend.head_dim

    def prefill(p, arena, rows, ids, lens):
        n = ids.shape[1]
        pos = jnp.arange(n)
        mask = pos[None, :] <= pos[:, None]

        def one(ids_row):
            x = p["embed"][ids_row] + p["pos"][pos]
            ks, vs = [], []
            for lp in p["layers"]:
                h = _ln(x, lp["ln1g"], lp["ln1b"])
                q, k, v = ((h @ lp[w]).reshape(n, h_, d_)
                           for w in ("wq", "wk", "wv"))
                ks.append(k.reshape(n, -1))
                vs.append(v.reshape(n, -1))
                sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d_)
                sc = jnp.where(mask[None], sc, -1e30)
                o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc), v)
                x = x + o.reshape(n, -1) @ lp["wo"]
                x = x + backend._ffn(lp, _ln(x, lp["ln2g"], lp["ln2b"]))
            return x, jnp.stack(ks), jnp.stack(vs)

        x_b, k_b, v_b = jax.vmap(one)(ids)
        xf = _ln(x_b[jnp.arange(rows.shape[0]), lens - 1],
                 p["lnfg"], p["lnfb"])
        tokens = jnp.argmax(xf @ p["head"], axis=-1).astype(jnp.int32)
        return {"k": arena["k"].at[:, rows, :n].set(
                    k_b.transpose(1, 0, 2, 3)),
                "v": arena["v"].at[:, rows, :n].set(
                    v_b.transpose(1, 0, 2, 3)),
                "tok": arena["tok"].at[rows].set(tokens)}, tokens

    return prefill


class TestPrefillWritesTheArenaOnce:
    """``prefill_fn`` (K/V from the projection into the arena's rows, layer
    by layer; attention on ``[B, n, H*D]``) against the staged formulation:
    live lanes' rows and first tokens equal, padded lanes absorbed by the
    dummy row, every other row bitwise what it was."""

    KW = dict(n_layers=2, d_model=128, n_heads=2, d_ff=128, vocab=128,
              max_seq_len=32, max_streams=8)

    @pytest.mark.parametrize("attention_impl,attn_impl,kv_shards,n", [
        ("einsum", "reference", 1, 16),   # XLA: the in-place scatter a layer
        ("einsum", "fused", 1, 16),       # interpreted Pallas: a DMA a lane
        ("einsum", "fused", 2, 16), ("einsum", "fused", 4, 16),
        ("einsum", "fused", 1, 4),        # a bucket under a row group
        ("einsum", "fused", 2, 4),
        # The flash kernel on [B, n, H*D] (one chip: outside ``shard_map``
        # a Mosaic call has no partitioning rule).
        ("flash", "reference", 1, 16), ("flash", "fused", 1, 16),
        ("flash", "fused", 1, 4),
    ])
    def test_against_the_staged_formulation(self, attention_impl, attn_impl,
                                            kv_shards, n):
        import jax
        import jax.numpy as jnp

        from client_tpu.models.generate import TinyGptBackend

        backend = TinyGptBackend(
            name="pf", attention_impl=attention_impl, attn_impl=attn_impl,
            kv_shards=kv_shards, **self.KW)
        backend.flash_blocks = (8, 8)          # a grid of several blocks
        params = jax.tree_util.tree_map(jnp.asarray, backend._init_params())
        free, dummy = backend.arena_rows()
        # An arena with something in every row, placed as the backend
        # places its own.
        zeros = backend.init_arena(backend.max_streams)
        keys = jax.random.split(jax.random.PRNGKey(4), 2)
        before = {"k": np.asarray(jax.random.normal(keys[0],
                                                    zeros["k"].shape)),
                  "v": np.asarray(jax.random.normal(keys[1],
                                                    zeros["v"].shape)),
                  "tok": np.full(zeros["tok"].shape, -1, np.int32)}
        arena = jax.tree_util.tree_map(
            lambda a, z: jax.device_put(a, z.sharding), before, zeros)
        rng = np.random.default_rng(n)
        lens = np.asarray([n, 1, max(1, n - 3), 1, 2], np.int32)
        rows = np.asarray([free[5], dummy, free[0], dummy, free[-1]],
                          np.int32)
        ids = rng.integers(0, 128, (5, n)).astype(np.int32)
        lanes = (jnp.zeros(5, jnp.int32), jnp.zeros(5, jnp.float32),
                 jnp.zeros(5, jnp.int32), jnp.ones(5, jnp.float32))
        got, got_tok = jax.jit(backend.prefill_fn(), static_argnums=(9,),
                               donate_argnums=(1,))(
            params, arena, rows, ids, lens, *lanes, False)
        want, want_tok = jax.jit(_staged_prefill(backend))(
            params, before, rows, ids, lens)
        live = [0, 2, 4]
        np.testing.assert_array_equal(np.asarray(got_tok)[live],
                                      np.asarray(want_tok)[live])
        np.testing.assert_array_equal(np.asarray(got["tok"])[rows[live]],
                                      np.asarray(want_tok)[live])
        for leaf in ("k", "v"):
            g, w, b4 = (np.asarray(a[leaf]) for a in (got, want, before))
            for i in live:
                np.testing.assert_allclose(g[:, rows[i], :n],
                                           w[:, rows[i], :n], atol=2e-5)
            touched = np.zeros(b4.shape[:3], bool)
            touched[:, rows, :n] = True
            np.testing.assert_array_equal(g[~touched], b4[~touched])
            # The dummy row is where the padded lanes went: it changed.
            assert not np.array_equal(g[:, dummy, :n], b4[:, dummy, :n])

    def test_the_platform_decides_the_write_too(self, monkeypatch):
        """Unset, the arena write is the kernel where the decode wave is:
        the compiled program holds Mosaic's call on a chip and none where
        Pallas would only be interpreted."""
        import jax
        import jax.numpy as jnp

        from client_tpu.engine import backend_init
        from client_tpu.models.generate import TinyGptBackend

        for interpreted, kernel in ((False, True), (True, False)):
            monkeypatch.setattr(backend_init, "pallas_interpret",
                                lambda v=interpreted: v)
            backend = TinyGptBackend(name="w", **self.KW)
            write = backend._prompt_rows_writer()
            arena = jax.eval_shape(lambda b=backend: b.init_arena(8))
            slab = jax.ShapeDtypeStruct((2, 16, 128), jnp.float32)
            rows = jax.ShapeDtypeStruct((2,), jnp.int32)
            text = str(jax.make_jaxpr(
                lambda k_a, v_a, k, v, r, w=write: w(k_a, v_a, k, v, r, 1))(
                    arena["k"], arena["v"], slab, slab, rows))
            assert ("pallas_call" in text) == kernel
