"""Serving runtime tests: Predictor, HTTP server, and the embeddable C
inference ABI (reference ``paddle/capi`` + ``inference/tests/book``)."""

import ctypes
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.layers as layers
from paddle_tpu.serving import (InferenceServer, Predictor, ServingClient,
                                ServingError)


@pytest.fixture()
def model_dir(tmp_path):
    """Train a tiny regression and save an inference model."""
    rng = np.random.RandomState(0)
    xs = rng.rand(8, 4).astype("float32")
    ys = (xs @ np.array([[1.0], [2.0], [3.0], [4.0]], "float32"))
    x = layers.data(name="x", shape=[8, 4], append_batch_size=False)
    y = layers.data(name="y", shape=[8, 1], append_batch_size=False)
    pred = layers.fc(input=x, size=1)
    loss = layers.mean(layers.square_error_cost(input=pred, label=y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for _ in range(60):
        exe.run(fluid.default_main_program(), feed={"x": xs, "y": ys},
                fetch_list=[loss])
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    # reference predictions for the test inputs
    test_x = rng.rand(8, 4).astype("float32")
    (want,) = exe.run(fluid.io.get_inference_program([pred]),
                      feed={"x": test_x}, fetch_list=[pred])
    return d, test_x, np.asarray(want)


class TestPredictor:
    def test_run(self, model_dir):
        d, test_x, want = model_dir
        p = Predictor(d)
        assert p.feed_names == ["x"]
        (got,) = p.run({"x": test_x})
        np.testing.assert_allclose(got, want, rtol=1e-5)


class TestHTTPServer:
    def test_predict_roundtrip(self, model_dir):
        d, test_x, want = model_dir
        server = InferenceServer(d, port=0)
        server.start_background()
        try:
            host, port = server.addr
            meta = json.loads(urllib.request.urlopen(
                f"http://{host}:{port}/meta", timeout=30).read())
            assert meta["feeds"] == ["x"]
            req = urllib.request.Request(
                f"http://{host}:{port}/predict",
                data=json.dumps(
                    {"feeds": {"x": test_x.tolist()}}).encode(),
                headers={"Content-Type": "application/json"})
            resp = json.loads(urllib.request.urlopen(
                req, timeout=60).read())
            got = np.asarray(resp["outputs"][0], "float32")
            np.testing.assert_allclose(got, want, rtol=1e-4)
            health = json.loads(urllib.request.urlopen(
                f"http://{host}:{port}/health", timeout=30).read())
            assert health["status"] == "ok"
        finally:
            server.shutdown()


class TestGracefulDegradation:
    """/healthz is liveness, /readyz gates traffic, requests that beat
    the model load get 503 + retryable (not a crash/hang), errors are
    structured JSON, and saturation sheds load."""

    def _get(self, host, port, path):
        try:
            with urllib.request.urlopen(
                    f"http://{host}:{port}{path}", timeout=30) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def _post(self, host, port, path, obj):
        req = urllib.request.Request(
            f"http://{host}:{port}{path}", data=json.dumps(obj).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_requests_before_load_get_503_retryable(self, model_dir):
        from paddle_tpu.fault import chaos

        d, test_x, want = model_dir
        # hold the model load long enough to observe the loading window
        chaos.inject("serving.load", delay=1.0)
        try:
            server = InferenceServer(d, port=0, async_load=True)
            server.start_background()
            host, port = server.addr
            code, body = self._get(host, port, "/healthz")
            assert code == 200                     # alive while loading
            code, body = self._get(host, port, "/readyz")
            assert code == 503 and body["retryable"] is True
            assert body["error"]["type"] == "model_loading"
            code, body = self._post(host, port, "/run",
                                    {"feeds": {"x": test_x.tolist()}})
            assert code == 503 and body["retryable"] is True
            # once loaded, the same request succeeds
            assert server.wait_until_ready(60)
            code, body = self._get(host, port, "/readyz")
            assert code == 200
            code, body = self._post(host, port, "/run",
                                    {"feeds": {"x": test_x.tolist()}})
            assert code == 200
            np.testing.assert_allclose(
                np.asarray(body["outputs"][0], "float32"), want, rtol=1e-4)
            server.shutdown()
        finally:
            chaos.clear()

    def test_structured_errors_with_retryable_flag(self, model_dir):
        d, _, _ = model_dir
        server = InferenceServer(d, port=0)
        server.start_background()
        try:
            host, port = server.addr
            # bad feed name -> 400, permanent
            code, body = self._post(host, port, "/predict",
                                    {"feeds": {"nope": [1.0]}})
            assert code == 400 and body["retryable"] is False
            assert set(body["error"]) == {"type", "message"}
            # unknown route -> structured 404
            code, body = self._get(host, port, "/nope")
            assert code == 404 and body["error"]["type"] == "not_found"
        finally:
            server.shutdown()

    def test_load_shedding_when_saturated(self, model_dir):
        d, test_x, _ = model_dir
        server = InferenceServer(d, port=0, max_inflight=1)
        server.start_background()
        try:
            host, port = server.addr
            # saturate the single slot from another thread
            import threading
            from paddle_tpu.fault import chaos
            chaos.inject("serving.run", delay=1.5, times=1)
            slow = threading.Thread(
                target=self._post, args=(host, port, "/predict",
                                         {"feeds": {"x": test_x.tolist()}}))
            slow.start()
            time.sleep(0.3)  # let the slow request take the slot
            code, body = self._post(host, port, "/predict",
                                    {"feeds": {"x": test_x.tolist()}})
            assert code == 503 and body["error"]["type"] == "overloaded"
            assert body["retryable"] is True
            slow.join()
            chaos.clear()
            # slot free again: next request succeeds (the handler gives
            # the slot back AFTER it has sent its reply, so the client may
            # be here first: 3 of 8 runs alone on a busy machine)
            for _ in range(50):
                code, _ = self._post(host, port, "/predict",
                                     {"feeds": {"x": test_x.tolist()}})
                if code != 503:
                    break
                time.sleep(0.02)
            assert code == 200
        finally:
            server.shutdown()


class TestServingClient:
    def test_predict_retries_through_model_load(self, model_dir):
        """The retrying client rides out the 503 loading window that
        would kill a naive caller (the serving analog of the master RPC
        retry path)."""
        from paddle_tpu.fault import RetryPolicy, chaos

        d, test_x, want = model_dir
        chaos.inject("serving.load", delay=1.0)
        try:
            server = InferenceServer(d, port=0, async_load=True)
            server.start_background()
            client = ServingClient(server.addr, retry=RetryPolicy(
                max_attempts=30, base_delay=0.1, max_delay=0.25, jitter=0))
            assert client.healthy()              # liveness: up immediately
            assert not client.ready()            # readiness: still loading
            (got,) = client.predict({"x": test_x})  # retries until ready
            np.testing.assert_allclose(got, want, rtol=1e-4)
            assert client.ready()
            server.shutdown()
        finally:
            chaos.clear()

    def test_failed_async_load_surfaces_not_hangs(self, tmp_path):
        server = InferenceServer(str(tmp_path / "no_such_model"), port=0,
                                 async_load=True)
        server.start_background()
        try:
            # wait_until_ready must raise the load error, not block
            with pytest.raises(Exception):
                server.wait_until_ready(timeout=60)
            assert server.load_error is not None
            client = ServingClient(server.addr)
            assert client.healthy() and not client.ready()
            with pytest.raises(ServingError) as ei:
                client.predict({"x": [1.0]})
            assert ei.value.etype == "model_load_failed"
            assert ei.value.retryable is False
        finally:
            server.shutdown()

    def test_permanent_errors_not_retried(self, model_dir):
        d, _, _ = model_dir
        server = InferenceServer(d, port=0)
        server.start_background()
        try:
            client = ServingClient(server.addr)
            with pytest.raises(ServingError) as ei:
                client.predict({"wrong_name": [1.0, 2.0]})
            assert ei.value.retryable is False
        finally:
            server.shutdown()


class TestCAPI:
    def test_c_abi_inference(self, model_dir):
        from paddle_tpu import native
        lib = native.load_capi()
        assert lib is not None, "native toolchain expected in image"
        d, test_x, want = model_dir
        assert lib.pd_tpu_init() == 0, lib.pd_tpu_last_error()
        h = lib.pd_tpu_create(d.encode())
        assert h, lib.pd_tpu_last_error()
        try:
            assert lib.pd_tpu_num_feeds(h) == 1
            assert lib.pd_tpu_feed_name(h, 0) == b"x"

            data = np.ascontiguousarray(test_x)
            names = (ctypes.c_char_p * 1)(b"x")
            bufs = (ctypes.c_void_p * 1)(
                data.ctypes.data_as(ctypes.c_void_p))
            lens = (ctypes.c_longlong * 1)(data.nbytes)
            shape = (ctypes.c_longlong * 2)(*data.shape)
            shapes = (ctypes.POINTER(ctypes.c_longlong) * 1)(shape)
            ranks = (ctypes.c_int * 1)(2)
            dtypes = (ctypes.c_char_p * 1)(b"float32")
            res = lib.pd_tpu_run(h, 1, names, bufs, lens, shapes, ranks,
                                 dtypes)
            assert res, lib.pd_tpu_last_error()
            try:
                assert lib.pd_tpu_result_count(res) == 1
                rank = lib.pd_tpu_result_rank(res, 0)
                out_shape = tuple(lib.pd_tpu_result_dim(res, 0, i)
                                  for i in range(rank))
                assert lib.pd_tpu_result_dtype(res, 0) == b"float32"
                blen = ctypes.c_longlong()
                ptr = lib.pd_tpu_result_data(res, 0, ctypes.byref(blen))
                raw = ctypes.string_at(ptr, blen.value)
                got = np.frombuffer(raw, "float32").reshape(out_shape)
                np.testing.assert_allclose(got, want, rtol=1e-5)
            finally:
                lib.pd_tpu_free_result(res)
        finally:
            lib.pd_tpu_destroy(h)
