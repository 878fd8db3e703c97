"""The serving host's turn (PR 31): the token relay that writes every
streamed reply's token chunks from one thread, the client's reader of a
chunked ndjson body, the step key made on the host."""

import http.client
import io
import json
import threading
import time

import jax
import numpy as np
import pytest

from paddle_tpu.executor import _step_key
from paddle_tpu.gen.scheduler import GenStream
from paddle_tpu.serving import _ChunkedLines, _TokenRelay, _token_chunk


def _frame(obj):
    data = (json.dumps(obj) + "\n").encode()
    return b"%x\r\n" % len(data) + data + b"\r\n"


@pytest.mark.parametrize("seed", [0, 5, 7 * 1000003 + 12, 2 ** 31 + 5,
                                  2 ** 40 + 3, -3])
def test_the_host_made_step_key_is_prngkey(seed):
    assert np.array_equal(_step_key(seed),
                          np.asarray(jax.random.PRNGKey(seed)))
    assert _step_key(seed).dtype == np.uint32


@pytest.mark.parametrize("token,index", [(0, 0), (7, 3), (163839, 4095)])
def test_a_token_chunk_is_the_encoders_line_in_a_chunk(token, index):
    assert _token_chunk(token, index) == _frame({"token": token,
                                                 "index": index})


# -- the client's reader -----------------------------------------------------
class _Resp:
    def __init__(self, body, chunked=True):
        self.chunked, self.fp = chunked, io.BytesIO(body)

    def readline(self):
        return self.fp.readline()


def _lines(body, **kw):
    reader, out = _ChunkedLines(_Resp(body, **kw)), []
    while True:
        line = reader.readline()
        if not line:
            return out
        out.append(line)


def test_reader_one_line_a_chunk_then_the_last_chunk_and_its_trailers():
    body = _frame({"token": 1, "index": 0}) + _frame({"done": True}) + \
        b"0\r\nX-Trailer: 1\r\n\r\n"
    assert [json.loads(x) for x in _lines(body)] == [
        {"token": 1, "index": 0}, {"done": True}]


def test_reader_two_lines_in_one_chunk_and_a_line_over_two_chunks():
    two = b'{"a": 1}\n{"b": 2}\n'
    head, tail = b'{"c": ', b'3}\n'
    body = (b"%x\r\n%b\r\n" % (len(two), two)
            + b"%x;ext=1\r\n%b\r\n" % (len(head), head)
            + b"%x\r\n%b\r\n" % (len(tail), tail) + b"0\r\n\r\n")
    assert [json.loads(x) for x in _lines(body)] == [
        {"a": 1}, {"b": 2}, {"c": 3}]


def test_reader_socket_closed_between_chunks_is_an_end_not_a_fault():
    body = _frame({"token": 1, "index": 0})
    reader = _ChunkedLines(_Resp(body))
    assert json.loads(reader.readline()) == {"token": 1, "index": 0}
    assert reader.readline() == b"" and reader.readline() == b""


def test_reader_a_torn_chunk_is_a_fault_of_the_transport():
    body = _frame({"token": 1, "index": 0})[:-9]
    with pytest.raises(http.client.IncompleteRead):
        _ChunkedLines(_Resp(body)).readline()
    with pytest.raises(ValueError):
        _ChunkedLines(_Resp(b"zz\r\nabc\r\n")).readline()


def test_reader_leaves_a_body_that_is_not_chunked_to_the_response():
    assert _lines(b'{"a": 1}\n{"b": 2}\n', chunked=False) == [
        b'{"a": 1}\n', b'{"b": 2}\n']


# -- the relay ----------------------------------------------------------------
class _Sock:
    """What the relay asks of a connection: ``send`` takes ``room``
    bytes in all and then would block; ``fail`` is raised instead."""

    def __init__(self, room=None, timeout=None, fail=None):
        self.sent, self.room, self.fail = b"", room, fail
        self.timeout, self.blocking_sent = timeout, b""

    def gettimeout(self):
        return self.timeout

    def send(self, data, flags=0):
        if self.fail is not None:
            raise self.fail
        if self.room is None:
            self.sent += data
            return len(data)
        if self.room == 0:
            raise BlockingIOError()
        n = min(self.room, len(data))
        self.sent += data[:n]
        self.room -= n
        return n

    def sendall(self, data):
        self.blocking_sent += data


def _stream():
    return GenStream([1, 2], 8, -1, None, trace_id="t")


@pytest.fixture
def relay():
    r = _TokenRelay(stall_s=0.3)
    yield r
    r.close()


def test_relay_writes_the_token_chunks_and_hands_back_what_ends_them(relay):
    stream, sock = _stream(), _Sock()
    stream.emit(11)                 # queued before the handler came
    out = {}
    t = threading.Thread(target=lambda: out.update(
        r=relay.relay(stream, sock, 1)))
    t.start()
    for tok in (12, 13):
        stream.emit(tok)
    stream.finish("length")
    t.join(5)
    assert out["r"] == (("done", "length"), 4, True)
    assert sock.sent == b"".join(_token_chunk(k, i) for k, i in
                                 ((11, 1), (12, 2), (13, 3)))
    assert stream.on_event is None


def test_relay_serves_many_streams_from_one_thread_each_in_order(relay):
    streams = [(_stream(), _Sock()) for _ in range(8)]
    outs = [None] * 8

    def handler(k):
        outs[k] = relay.relay(streams[k][0], streams[k][1], 0)
    threads = [threading.Thread(target=handler, args=(k,))
               for k in range(8)]
    for t in threads:
        t.start()
    for step in range(5):
        for k, (stream, _) in enumerate(streams):
            stream.emit(100 * k + step)
    for stream, _ in streams:
        stream.finish("length")
    for t in threads:
        t.join(5)
    for k, (_, sock) in enumerate(streams):
        assert outs[k] == (("done", "length"), 5, True)
        assert sock.sent == b"".join(_token_chunk(100 * k + i, i)
                                     for i in range(5))


@pytest.mark.parametrize("room", [0, 7])
def test_a_reader_that_stopped_reading_goes_back_to_its_own_thread(relay,
                                                                   room):
    stream, sock = _stream(), _Sock(room=room)
    stream.emit(5)
    stream.emit(6)
    event, index, relayed = relay.relay(stream, sock, 0)
    # the chunk that did not fit is finished by the caller's blocking
    # write, and the stream's next event is the caller's to write
    assert sock.sent + sock.blocking_sent == _token_chunk(5, 0)
    assert len(sock.sent) == room
    assert (event, index, relayed) == (("token", 6), 1, False)


def test_a_write_fault_is_raised_in_the_handler(relay):
    stream, sock = _stream(), _Sock(fail=BrokenPipeError("gone"))
    stream.emit(5)
    with pytest.raises(BrokenPipeError):
        relay.relay(stream, sock, 0)
    # and the relay goes on serving the others
    other, osock = _stream(), _Sock()
    other.emit(1)
    other.finish("eos")
    assert relay.relay(other, osock, 0) == (("done", "eos"), 1, True)


def test_a_socket_with_a_timeout_is_not_relayed(relay):
    stream, sock = _stream(), _Sock(timeout=5.0)
    stream.emit(5)
    assert relay.relay(stream, sock, 3) == (("token", 5), 3, False)
    assert sock.sent == b""


def test_a_stream_with_no_event_for_stall_s_is_handed_back_as_stalled(relay):
    t0 = time.monotonic()
    assert relay.relay(_stream(), _Sock(), 2) == (None, 2, True)
    assert 0.3 <= time.monotonic() - t0 < 5


def test_close_hands_every_stream_back_and_later_ones_are_not_taken():
    relay = _TokenRelay(stall_s=300.0)
    out = {}
    t = threading.Thread(target=lambda: out.update(
        r=relay.relay(_stream(), _Sock(), 0)))
    t.start()
    time.sleep(0.05)
    relay.close()
    t.join(5)
    assert out["r"] == (None, 0, True)
    late = _stream()
    late.emit(9)
    assert relay.relay(late, _Sock(), 0) == (("token", 9), 0, False)


def test_next_event_with_no_time_to_wait_does_not_block():
    stream = _stream()
    assert stream.next_event(timeout=0) is None
    stream.emit(3)
    assert stream.next_event(timeout=0) == ("token", 3)
    assert stream.next_event(timeout=0.01) is None
