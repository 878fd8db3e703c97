"""Open-loop load generation: a schedule of Poisson arrivals and lognormal
lengths, sent on time whatever the system does, every request timed from
when it was DUE.

A corrected copy of ``paddle_tpu/fleet/traffic.py`` (``TrafficReplay``,
``heavy_tail_lengths``), which times a request from when it was sent and
reports no lateness.  The schedule is computed before the window opens;
the sender thread only sleeps and starts requests.

The traffic is ONE plain random sample (exponential gaps, lognormal
lengths, drawn independently from ``sample_seed`` in the cell's file), so
it has a Poisson stream's clusters and lulls and a heavy tail's long
requests side by side, and every run of a cell sends it as drawn.  It is
not drawn from ``--seed`` (which makes the weights and the prompts'
tokens), and ``--seed`` does not reorder it either: a fresh sample moved
the offered work by 5%, a free shuffle of one sample moved ``ttft_p95_ms``
82-2098 ms, and even a rotation moved ``out_tokens_per_s`` by 6% with what
was in flight when the window closed (the v5e, PERF.md section 6), each
more than any bound allows between two runs of one code.
"""

from __future__ import annotations

import math
import random
import statistics
import threading
import time

_NORMAL = statistics.NormalDist()


def _length(u, spec):
    """The lognormal's quantile at ``u``, rounded and clamped."""
    z = _NORMAL.inv_cdf(min(max(u, 1e-12), 1.0 - 1e-12))
    n = int(round(float(spec["median"]) * math.exp(float(spec["sigma"]) * z)))
    return max(int(spec.get("min", 1)), min(int(spec["cap"]), n))


def build_schedule(seconds, params):
    """The requests due inside ``[0, seconds)``: a list of dicts with
    ``due`` (seconds from the window's start), ``prompt_len`` and
    ``max_new``.  ``params``: ``rate_per_s``, ``prompt`` and ``output``
    (each ``median``/``sigma``/``min``/``cap``) and ``sample_seed``.

    ``round(rate * seconds)`` requests, each with an exponential gap
    before it and a lognormal prompt and output length, all drawn
    independently from ``random.Random(sample_seed).random()`` (the one
    stream Python keeps stable between versions).  The gaps are scaled by
    one factor so that the last arrival lands half a mean gap before the
    window closes: the offered rate is then exact."""
    rate = float(params["rate_per_s"])
    n = max(1, int(round(rate * float(seconds))))
    draw = random.Random(int(params["sample_seed"]))
    rows = [(-math.log(1.0 - draw.random()),
             _length(draw.random(), params["prompt"]),
             _length(draw.random(), params["output"])) for _ in range(n)]
    scale = (float(seconds) - 0.5 / rate) / sum(r[0] for r in rows)
    schedule, t = [], 0.0
    for i, (gap, prompt_len, max_new) in enumerate(rows):
        t += gap * scale
        schedule.append({"index": i, "due": t, "prompt_len": prompt_len,
                         "max_new": max_new})
    return schedule


class OpenLoop:
    """Send ``schedule`` against ``send(request) -> record`` on time.

    One sender thread sleeps until each request is due and hands it to a
    worker thread of its own (a request blocks on the reply stream, so
    the workers wait on sockets and do not compete for the interpreter).
    ``send`` gets ``request`` with ``due_t`` (absolute ``perf_counter``)
    and ``sent_t`` filled in, and returns what it measured; an exception
    marks the request failed.  Lateness = ``sent_t - due_t``."""

    def __init__(self, schedule, send, max_inflight=256):
        self.schedule = schedule
        self._send = send
        self._max_inflight = int(max_inflight)
        self._lock = threading.Lock()
        self._inflight = 0
        self.records = []
        self.t_start = None

    def _one(self, request):
        try:
            record = dict(self._send(request) or {})
            record.setdefault("ok", True)
        except Exception as e:   # boundary: one request's failure is data
            record = {"ok": False, "error": repr(e)}
        record["request"] = request
        with self._lock:
            self.records.append(record)
            self._inflight -= 1

    def run(self, drain_timeout=120.0):
        """Blocks until every request sent has ended (or the drain
        timeout passed; what is still open then counts as failed)."""
        threads = []
        self.t_start = time.perf_counter()
        for request in self.schedule:
            request = dict(request)
            request["due_t"] = self.t_start + request["due"]
            delay = request["due_t"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            request["sent_t"] = time.perf_counter()
            with self._lock:
                over = self._inflight >= self._max_inflight
                if not over:
                    self._inflight += 1
            if over:
                with self._lock:
                    self.records.append({"ok": False, "request": request,
                                         "error": "generator inflight cap"})
                continue
            t = threading.Thread(target=self._one, args=(request,),
                                 daemon=True)
            t.start()
            threads.append(t)
        deadline = time.perf_counter() + drain_timeout
        for t in threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        hung = sum(1 for t in threads if t.is_alive())
        with self._lock:
            records = list(self.records)
        for _ in range(hung):
            records.append({"ok": False, "error": "still open at drain "
                                                  "timeout", "request": {}})
        return records

    @staticmethod
    def lateness(records):
        return [r["request"]["sent_t"] - r["request"]["due_t"]
                for r in records if "sent_t" in r.get("request", {})]
